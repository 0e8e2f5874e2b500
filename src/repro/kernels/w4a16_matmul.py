"""Pallas TPU kernel: grouped int4-dequant matmul  y = x @ dequant(W)^T.

The deployment hot spot for RPIQ-quantized models: decode-time GEMV/GEMM
against 4-bit packed weights. GPU implementations unpack int4 in CUDA cores;
the TPU-native formulation here:

  - weight nibbles live packed in HBM as (n, k/2) uint8 and are unpacked
    with vector bit-ops in VREGs *after* the (bn, bk/2) tile is in VMEM —
    HBM traffic stays at 0.5 byte/weight + scales, which is what makes
    memory-bound decode ~3.8x faster than bf16 weights;
  - the low nibble of byte i is column 2i and the high nibble column 2i+1,
    so instead of interleaving the nibbles back into lanes (a lane shuffle
    Mosaic does not lower) the wrapper splits x into its even and odd
    columns and the kernel runs two dots, ``x_even @ W_lo^T + x_odd @
    W_hi^T`` — the same sum, no in-kernel reshape;
  - the per-(row, group) scale/zero tiles hold every group of a row tile
    (the full ``k // group_size`` minor dim, which is what makes the
    BlockSpec legal for any group count) and stay VMEM-resident across
    the K steps; the columns a K step needs are picked out by a one-hot
    lane reduction (exact: a single nonzero per sum) and broadcast over
    their ``group_size / 2`` packed lanes;
  - dequantized f32 tiles feed the MXU via dot_general with fp32
    accumulation; M/N tiles follow the (8, 128) lane geometry.

Grid: (m/bm, n/bn, k/bk), K innermost (sequential accumulation).
Validated in interpret mode on CPU against ``ref.w4a16_matmul_ref`` and
compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def _w4a16_kernel(xe_ref, xo_ref, packed_ref, scales_ref, zeros_ref, y_ref,
                  acc_ref, *, group_size: int, n_k_steps: int, out_dtype):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = packed_ref[...].astype(jnp.int32)                   # (bn, bk//2)
    lo = (p & 0x0F).astype(jnp.float32)                     # even columns
    hi = ((p >> 4) & 0x0F).astype(jnp.float32)              # odd columns
    bn, bkh = p.shape
    gh = group_size // 2                                    # lanes per group
    gpb = bkh // gh                                         # groups per K step

    s_all = scales_ref[...].astype(jnp.float32)             # (bn, G)
    z_all = zeros_ref[...].astype(jnp.float32)
    g_ids = jax.lax.broadcasted_iota(jnp.int32, s_all.shape, 1)
    lane_grp = jax.lax.broadcasted_iota(jnp.int32, (bn, bkh), 1) // gh
    s = jnp.zeros((bn, bkh), jnp.float32)
    z = jnp.zeros((bn, bkh), jnp.float32)
    for gg in range(gpb):
        sel = g_ids == kk * gpb + gg
        s_col = jnp.sum(jnp.where(sel, s_all, 0.0), axis=1, keepdims=True)
        z_col = jnp.sum(jnp.where(sel, z_all, 0.0), axis=1, keepdims=True)
        s = jnp.where(lane_grp == gg, s_col, s)
        z = jnp.where(lane_grp == gg, z_col, z)
    w_lo = (lo - z) * s                                     # (bn, bk//2) f32
    w_hi = (hi - z) * s

    dims = (((1,), (1,)), ((), ()))                         # x @ w.T
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...].astype(jnp.float32), w_lo, dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo_ref[...].astype(jnp.float32), w_hi, dims,
                              preferred_element_type=jnp.float32))

    @pl.when(kk == n_k_steps - 1)
    def _store():
        y_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=(
    "group_size", "block_m", "block_n", "block_k", "interpret"))
def w4a16_matmul_pallas(x: jax.Array, packed: jax.Array, scales: jax.Array,
                        zeros: jax.Array, *, group_size: int = 128,
                        block_m: int = DEFAULT_BLOCK_M,
                        block_n: int = DEFAULT_BLOCK_N,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = True) -> jax.Array:
    """x: (m, k); packed: (n, k//2) uint8; scales/zeros: (n, k//group_size).

    Returns (m, n) in x.dtype. Shape divisibility is the caller's contract
    (ops.py pads and picks the tiles); block_k must be a multiple of
    group_size, and group_size even. On the chip, ``block_k // 2`` must be
    a multiple of 128 or equal ``k // 2``.
    """
    m, kdim = x.shape
    n = packed.shape[0]
    block_m = min(block_m, m)
    block_k = min(block_k, kdim)
    assert group_size % 2 == 0 and block_k % group_size == 0, (
        block_k, group_size)
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0, (
        x.shape, packed.shape, (block_m, block_n, block_k))
    n_groups = kdim // group_size
    assert scales.shape == (n, n_groups) == zeros.shape, (
        scales.shape, zeros.shape, (n, n_groups))
    grid = (m // block_m, n // block_n, kdim // block_k)
    kernel = functools.partial(_w4a16_kernel, group_size=group_size,
                               n_k_steps=grid[2], out_dtype=x.dtype)
    bkh = block_k // 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, bkh), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, bkh), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, bkh), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_n, n_groups), lambda i, j, k: (j, 0)),
            pl.BlockSpec((block_n, n_groups), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jax.lax.slice(x, (0, 0), (m, kdim), (1, 2)),         # even columns
      jax.lax.slice(x, (0, 1), (m, kdim), (1, 2)),         # odd columns
      packed, scales, zeros)
