"""The main-path Pallas kernels compile for a TPU v5e at opt-proxy's widths.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, layouts it cannot relayout, more
VMEM than a kernel may use. These tests lower each kernel through its
``kernels.ops`` dispatcher — so they compile the tiles a user gets, not
hand-chosen ones — with ``interpret=False`` against a described (not
attached) ``v5e:2x2`` topology, at opt-proxy full's widths: d_model 768,
d_ff 3072, 12 heads of 64, a 16 x 512-token calibration batch, 8 decode
lanes and 128-token prefill.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test file that decided at
import whether its tests exist would give pytest-xdist workers different
collections. Where it cannot be described, the fixture skips.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_MODEL, D_FF, HEADS, HEAD_DIM = 768, 3072, 12, 64
TOKENS = 16 * 512           # one calibration batch
LANES, PREFILL = 8, 128
CAP = 128 + 32 + 1          # prompt + new tokens + 1 (the serving cache)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, n=1):
    assert text.count("tpu_custom_call") >= n, "no Mosaic kernel in the HLO"


@pytest.mark.parametrize("d", [D_MODEL, D_FF])
def test_hessian_accum(one_chip, d):
    fn = functools.partial(ops.hessian_accum, impl="pallas", interpret=False)
    _assert_kernel(_compile(one_chip, fn, ((TOKENS, d), jnp.float32)))


@pytest.mark.parametrize("m", [LANES, PREFILL])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL),
                                 (D_MODEL, 3 * D_MODEL)])
def test_w4a16_matmul(one_chip, m, k, n):
    fn = functools.partial(ops.w4a16_matmul, group_size=128, impl="pallas",
                           interpret=False)
    _assert_kernel(_compile(
        one_chip, fn, ((m, k), jnp.bfloat16), ((n, k // 2), jnp.uint8),
        ((n, k // 128), jnp.float32), ((n, k // 128), jnp.float32)))


@pytest.mark.parametrize("s", [CAP, 4096])
def test_int8_kv_attention(one_chip, s):
    fn = functools.partial(ops.int8_kv_attention, kv_block=HEAD_DIM,
                           impl="pallas", interpret=False)
    codes = ((LANES, s, HEADS, HEAD_DIM), jnp.int8)
    scales = ((LANES, s, HEADS, 1), jnp.float32)
    _assert_kernel(_compile(
        one_chip, fn, ((LANES, HEADS, 1, HEAD_DIM), jnp.bfloat16),
        codes, scales, codes, scales, ((LANES, s), jnp.int32)))


@pytest.mark.parametrize("b,out,inp", [(3, D_MODEL, D_MODEL),
                                        (1, D_FF, D_MODEL),
                                        (1, D_MODEL, D_FF)])
def test_gptq_block(one_chip, b, out, inp):
    fn = functools.partial(ops.gptq_block, impl="pallas", interpret=False)
    _assert_kernel(_compile(one_chip, fn, ((b, out, inp), jnp.float32),
                            ((b, inp, inp), jnp.float32)))


def test_gptq_block_streams_d_ff_input(one_chip, monkeypatch):
    """The d_ff-input sweep fits: U streams one lazy-block slab at a time,
    so the tile the dispatcher picks is within the budget, and "auto"
    lowers the Mosaic kernel there rather than the XLA fallback."""
    bo, fits = ops._gptq_block_out(D_MODEL, D_FF, 128, 128)
    assert fits
    assert ops._gptq_vmem_bytes(bo, D_FF, 128, 128) <= \
        ops._VMEM_BUDGET_BYTES
    # the described chip is not the backend: steer "auto" onto its TPU arm
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    ops.reset_fallback_stats()
    fn = functools.partial(ops.gptq_block, impl="auto", interpret=False)
    _assert_kernel(_compile(one_chip, fn, ((1, D_MODEL, D_FF), jnp.float32),
                            ((1, D_FF, D_FF), jnp.float32)))
    assert "gptq_block:vmem-budget" not in ops.fallback_stats()


@pytest.mark.parametrize("n", [16, 512])
def test_rpiq_block(one_chip, n):
    assert ops._rpiq_vmem_bytes(128, D_MODEL, n, 128) <= \
        ops._VMEM_BUDGET_BYTES
    fn = functools.partial(ops.rpiq_block, impl="pallas", interpret=False)
    w = ((D_MODEL, D_MODEL), jnp.float32)
    grid = ((D_MODEL, D_MODEL // 128), jnp.float32)
    _assert_kernel(_compile(
        one_chip, fn, w, w, ((n, D_MODEL), jnp.float32),
        ((D_MODEL // 128, 128, 128), jnp.float32), grid, grid))
