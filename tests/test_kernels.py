"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Every kernel is executed with interpret=True (kernel body runs in Python on
CPU) and compared against ref.py. Block-shape edge cases (non-divisible
sizes exercised through the ops.py padding wrappers) are included.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import pack_quantized
from repro.kernels import ops, ref
from repro.kernels.hessian_accum import hessian_accum_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels.w4a16_matmul import w4a16_matmul_pallas


def _rand(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


class TestHessianKernel:
    @pytest.mark.parametrize("n,d,bn,bd", [
        (128, 128, 64, 64), (256, 128, 128, 128), (512, 256, 256, 128),
        (64, 64, 32, 32),
        (1024, 256, 256, 128),    # multi-tile grid (2, 2, 4)
        (512, 256, 128, 256),     # one whole tile: x read once, grid (4,)
        (256, 384, 128, 384),     # whole tile, edge not a power of two
        (512, 768, 256, 384),     # multi-tile grid at a 384 edge
    ])
    def test_shapes(self, n, d, bn, bd):
        x = _rand((n, d), n + d)
        out = hessian_accum_pallas(x, block_d=bd, block_n=bn, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.hessian_accum_ref(x)),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = _rand((128, 64), 3, dtype)
        out = hessian_accum_pallas(x, block_d=64, block_n=64, interpret=True)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.hessian_accum_ref(x)),
                                   rtol=2e-2, atol=1e-1)

    def test_psd(self):
        x = _rand((256, 64), 9)
        H = hessian_accum_pallas(x, block_d=64, block_n=128, interpret=True)
        evs = np.linalg.eigvalsh(np.asarray(H))
        assert evs.min() > -1e-3

    @pytest.mark.parametrize("n,d", [
        (100, 200),     # slab 104 deeper than n; width padded to 256
        (1000, 384),    # whole 384 tile, one 1000-token slab
        (3, 64),        # tiny width: the whole (64, 64) square
    ])
    def test_dispatch(self, n, d):
        x = _rand((n, d), n * d)
        out = ops.hessian_accum(x, impl="pallas", interpret=True)
        assert out.shape == (d, d)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.hessian_accum_ref(x)),
                                   rtol=1e-4, atol=1e-3)


def _registry_hessian_shapes():
    """(tokens, width) of every dense linear input the registry's
    configurations calibrate: d_model and d_ff at the decoder's calibration
    tokens (and the encoder's), plus the benchmark's d 768 and d 3072."""
    from repro.configs.registry import ARCH_IDS, get_config
    shapes = {(8192, 768), (8192, 3072)}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        m, q = cfg.model, cfg.quant
        tokens = [q.calib_batch_size * min(q.calib_seq_len,
                                           m.max_seq_len - 8)]
        if m.is_encoder_decoder:
            tokens.append(q.calib_batch_size * m.encoder_seq_len)
        shapes |= {(n, d) for n in tokens for d in (m.d_model, m.d_ff)
                   if d > 0}
    return sorted(shapes)


def test_hessian_tile_choice():
    """The dispatcher's tiles: an edge that divides the 128-rounded width
    (so no padding beyond 128), a residency within the VMEM budget under
    the limit the kernel is given, little token padding, and nothing but
    the shape behind the choice."""
    shapes = _registry_hessian_shapes()
    assert list(inspect.signature(ops._hessian_tiles).parameters) == \
        ["n", "d"]
    first = {s: ops._hessian_tiles(*s) for s in shapes}
    assert first == {s: ops._hessian_tiles(*s) for s in reversed(shapes)}
    assert ops._HESSIAN_VMEM_BUDGET_BYTES <= ops._HESSIAN_VMEM_LIMIT_BYTES
    for (n, d), (bd, bn) in first.items():
        d_pad = -(-d // 128) * 128
        assert bd % 128 == 0 and d_pad % bd == 0, (n, d, bd)
        assert bd <= ops._HESSIAN_MAX_EDGE, (n, d, bd)
        assert bn % 8 == 0 and bn <= ops._HESSIAN_MAX_SLAB, (n, d, bn)
        slabs = -(-n // bn)
        assert slabs * bn - n < 8 * slabs, (n, d, bn)
        assert (ops._hessian_vmem_bytes(bd, bn, d_pad)
                <= ops._HESSIAN_VMEM_BUDGET_BYTES), (n, d, bd, bn)


class TestW4A16Kernel:
    def _mk(self, m, n, k, g, seed=0):
        x = _rand((m, k), seed, jnp.float32)
        w = _rand((n, k), seed + 1) * 0.2
        qt = pack_quantized(w, 4, g)
        return x, qt

    @pytest.mark.parametrize("m,n,k,g,bm,bn,bk", [
        (8, 128, 256, 128, 8, 128, 128),
        (128, 128, 512, 128, 64, 128, 256),
        (16, 256, 256, 64, 16, 128, 128),
        (8, 128, 128, 128, 8, 128, 128),
    ])
    def test_shapes(self, m, n, k, g, bm, bn, bk):
        x, qt = self._mk(m, n, k, g, seed=m + n)
        y = w4a16_matmul_pallas(x, qt.packed, qt.scales, qt.zeros,
                                group_size=g, block_m=bm, block_n=bn,
                                block_k=bk, interpret=True)
        y_ref = ref.w4a16_matmul_ref(x, qt.packed, qt.scales, qt.zeros, g)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = _rand((16, 256), 4, dtype)
        w = _rand((128, 256), 5) * 0.2
        qt = pack_quantized(w, 4, 128)
        y = w4a16_matmul_pallas(x, qt.packed, qt.scales, qt.zeros,
                                group_size=128, block_m=16, block_n=128,
                                block_k=256, interpret=True)
        assert y.dtype == dtype
        y_ref = ref.w4a16_matmul_ref(x, qt.packed, qt.scales, qt.zeros, 128)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_ops_padding_path(self):
        """Non-divisible m/n through the ops wrapper (pads + slices)."""
        from repro.kernels import ops
        x = _rand((5, 256), 6)
        w = _rand((100, 256), 7) * 0.3
        qt = pack_quantized(w, 4, 128)
        y = ops.w4a16_matmul(x, qt.packed, qt.scales, qt.zeros,
                             group_size=128, impl="xla")
        y_ref = ref.w4a16_matmul_ref(x, qt.packed, qt.scales, qt.zeros, 128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("k", [1152, 1408])
    def test_ops_pallas_pads_k(self, k):
        """k above one K step and off the 256-column tile: the dispatcher
        pads k (and m, n) with zero activations and (s=1, z=0) groups, and
        the padding never reaches a real output."""
        from repro.kernels import ops
        x = _rand((5, k), 8)
        w = _rand((100, k), 9) * 0.3
        qt = pack_quantized(w, 4, 128)
        y = ops.w4a16_matmul(x, qt.packed, qt.scales, qt.zeros,
                             group_size=128, impl="pallas")
        y_ref = ref.w4a16_matmul_ref(x, qt.packed, qt.scales, qt.zeros, 128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("lead", [(3, 1), (2, 5), (4,)])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_decode_shapes(self, lead, impl):
        """The serving decode path calls ops.w4a16_matmul with leading
        batch dims — (B, 1, k) single-token decode, (B, S, k) prefill.
        Every impl must match the 2-D ref on the flattened batch ≤1e-5."""
        from repro.kernels import ops
        k, n, g = 256, 128, 128
        x = _rand(lead + (k,), sum(lead), jnp.float32)
        w = _rand((n, k), 11) * 0.2
        qt = pack_quantized(w, 4, g)
        y = ops.w4a16_matmul(x, qt.packed, qt.scales, qt.zeros,
                             group_size=g, impl=impl)
        assert y.shape == lead + (n,)
        y_ref = ref.w4a16_matmul_ref(x.reshape(-1, k), qt.packed, qt.scales,
                                     qt.zeros, g).reshape(lead + (n,))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_shapes_impls_agree(self):
        """auto (CPU) == xla == pallas(interpret) bit-for-bit comparable on
        decode shapes, and the trace-time default-impl context routes the
        implicit (no-impl-arg) call sites used by models.linear.dense."""
        from repro.kernels import ops
        x = _rand((3, 1, 256), 21, jnp.float32)
        w = _rand((128, 256), 22) * 0.2
        qt = pack_quantized(w, 4, 128)
        ys = {}
        for impl in ("auto", "xla", "pallas"):
            with ops.w4a16_default_impl(impl):
                ys[impl] = np.asarray(ops.w4a16_matmul(
                    x, qt.packed, qt.scales, qt.zeros, group_size=128))
        np.testing.assert_allclose(ys["auto"], ys["xla"], rtol=0, atol=0)
        np.testing.assert_allclose(ys["xla"], ys["pallas"],
                                   rtol=1e-5, atol=1e-5)


class TestSelectiveScanKernel:
    def _mk(self, B, S, d, n, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        u = jax.random.normal(ks[0], (B, S, d))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d)) - 1)
        bm = jax.random.normal(ks[2], (B, S, n))
        cm = jax.random.normal(ks[3], (B, S, n))
        a_log = jnp.log(jnp.tile(
            jnp.arange(1, n + 1, dtype=jnp.float32)[None], (d, 1)))
        d_skip = jax.random.normal(ks[4], (d,))
        h0 = jax.random.normal(ks[5], (B, d, n)) * 0.1
        return u, dt, bm, cm, a_log, d_skip, h0

    @pytest.mark.parametrize("B,S,d,n,bd,bt", [
        (2, 64, 32, 8, 16, 16), (1, 32, 16, 4, 16, 32),
        (3, 128, 64, 16, 32, 64), (2, 64, 32, 8, 32, 64),
    ])
    def test_shapes(self, B, S, d, n, bd, bt):
        args = self._mk(B, S, d, n, seed=B * 7 + S)
        y_ref, h_ref = ref.selective_scan_ref(*args)
        y, h = selective_scan_pallas(*args, block_d=bd, block_t=bt,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_inputs(self):
        args = self._mk(2, 32, 16, 4, seed=9)
        args = tuple(a.astype(jnp.bfloat16) if a.ndim == 3 and i < 2
                     else a for i, a in enumerate(args))
        y_ref, _ = ref.selective_scan_ref(*args)
        y, _ = selective_scan_pallas(*args, block_d=16, block_t=16,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_state_carry_across_time_tiles(self):
        """Two time tiles must chain h exactly (scratch persistence)."""
        args = self._mk(1, 64, 16, 4, seed=3)
        y1, h1 = selective_scan_pallas(*args, block_d=16, block_t=64,
                                       interpret=True)
        y2, h2 = selective_scan_pallas(*args, block_d=16, block_t=16,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_dispatch_consistency(self):
        from repro.kernels import ops
        args = self._mk(2, 48, 32, 8, seed=11)
        y1, h1 = ops.selective_scan(*args, impl="pallas")
        y2, h2 = ops.selective_scan(*args, impl="xla")
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=2e-4, atol=2e-4)
