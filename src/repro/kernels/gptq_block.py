"""Pallas TPU kernel: one full GPTQ lazy-block sweep per grid cell.

The quantization hot path (paper §3.1 stage 1 / Frantar et al.) is a
sequential sweep over ``Cin`` columns in lazy blocks of ``blocksize``.  The
XLA formulation (``core/gptq._gptq_core``) lowers that sweep to a
``fori_loop``-of-``dynamic_slice`` chain — O(Cin) small dispatched ops per
member per sweep, which bounds warm executor wall-clock once the plan
batching (core/plan.py) has removed the per-linear dispatch overhead.

This kernel runs the ENTIRE sweep inside one ``pallas_call``:

  - grid ``(B, Cout/block_out, Cin/blocksize)`` — the stacked group-member
    axis times row tiles times lazy blocks; rows are independent given
    ``U`` (see gptq.py), so the row tiling is exact, not an approximation.
    The lazy-block axis is innermost and sequential ("arbitrary"): each
    step sweeps one block of one cell.  The same (member, Cout-tile) grid
    is the per-shard unit of the mesh-sharded executor: under
    ``ops.gptq_block_sharded``'s ``shard_map`` each device runs this
    kernel on its local ``(B/|data|, Cout/|model|, Cin)`` slab
    (DESIGN.md §2.6);
  - per cell the working ``(block_out, Cin)`` weight tile lives in the
    output ref, whose block index does not move with the lazy block, so it
    stays VMEM-resident for the whole sweep; ``U`` streams in one
    ``(blocksize, Cin)`` row slab per lazy block (the only part of the
    member's ``(Cin, Cin)`` factor a block reads), prefetched by the
    pipeline while the previous block runs;
  - per column: group (scale, zero) refresh via masked max/min (exact —
    the mask only excludes non-group columns from the reduction), column
    quantize on the (row, group) grid, and intra-block error propagation
    ``wb -= err · (U[j, j+1:] / U[j, j])`` — the same broadcasted
    expression as the XLA body, so interpret-mode output is bitwise-close;
  - per block: the rank-``blocksize`` tail update
    ``W[:, c2:] -= Err @ U[c1:c2, c2:]`` as one MXU dot with the same
    operand shapes as the XLA path.

VMEM contract: one cell holds the single-buffered w-in tile, the
double-buffered w-out tile and ``U`` slab, and the tail update's
temporaries — ~``4·Cin·(4·block_out + 3·blocksize)`` bytes + 1 MiB, linear
in Cin (``ops._gptq_vmem_bytes``).  At Cin = 3072 with 128-row tiles that
is ~11.9 MiB (the v5e compiler allocates 10.2 MiB); ``ops.gptq_block``
falls back to the XLA path where the row tile overflows the budget (with
128-row tiles, Cin above 3072).

Scales/zeros are carried through a block's columns as ``(block_out,
n_groups)`` values and kept between blocks in their (resident) output
refs, as is the per-row Σerr² diagnostic, which the ops.py wrapper sums to
the member scalar.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_OUT = 128     # row tile (MXU/lane aligned)


def _lane_iota(n: int) -> jax.Array:
    """(1, n) int32 column ids. Every mask and per-row quantity in the
    kernel stays 2-D — (1, n) rows and (out_t, 1) columns — because Mosaic
    cannot relayout a 1-D boolean into a column (``[:, None]`` on a mask)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _gptq_block_kernel(w_ref, u_ref, wq_ref, s_ref, z_ref, err_ref, *,
                       bits: int, group_size: int, blocksize: int,
                       symmetric: bool):
    """One grid step: lazy block ``k`` of one (member, row-tile) cell.

    The output refs keep their block index across ``k``, so they stay in
    VMEM for the whole sweep and carry the working tile, the group grids
    and Σerr² from one lazy block to the next; ``u_ref`` is this block's
    ``(blocksize, Cin)`` row slab of ``U``."""
    out_t, in_dim = wq_ref.shape[1], wq_ref.shape[2]
    n_groups = s_ref.shape[2]
    qmax = 2.0 ** bits - 1.0
    k = pl.program_id(2)

    cols_bs = _lane_iota(blocksize)               # (1, bs) in-block columns
    rows_bs = jax.lax.broadcasted_iota(jnp.int32, (blocksize, 1), 0)
    cols_in = _lane_iota(in_dim)                  # (1, Cin) absolute columns
    groups = _lane_iota(n_groups)                 # (1, n_groups)
    eye_bs = (jax.lax.broadcasted_iota(jnp.int32, (blocksize, blocksize), 0)
              == jax.lax.broadcasted_iota(jnp.int32,
                                          (blocksize, blocksize), 1))

    @pl.when(k == 0)
    def _():
        wq_ref[0] = w_ref[0].astype(jnp.float32)
        s_ref[0] = jnp.zeros((out_t, n_groups), jnp.float32)
        z_ref[0] = jnp.zeros((out_t, n_groups), jnp.float32)
        err_ref[0] = jnp.zeros((out_t, 1), jnp.float32)

    c1 = pl.multiple_of(k * blocksize, blocksize)
    wb0 = wq_ref[0, :, pl.ds(c1, blocksize)]                # (out_t, bs)
    u_rows = u_ref[0]                                       # (bs, Cin)
    ub = u_ref[0, :, pl.ds(c1, blocksize)]                  # (bs, bs)
    # (1, bs) diagonal — exact: one nonzero per column sum
    diag = jnp.sum(jnp.where(eye_bs, ub, 0.0), axis=0, keepdims=True)

    def col_step(j, cc):
        wb, errb, scale, zero, sfull, zfull = cc
        onehot = cols_bs == j                                # (1, bs)

        def refresh(args):
            wb, scale, zero, sfull, zfull = args
            # masked (scale, zero) — exact: the mask only drops
            # non-group columns from the max/min reductions (order-free)
            gmask = (cols_bs // group_size) == (j // group_size)
            if symmetric:
                absmax = jnp.max(jnp.where(gmask, jnp.abs(wb), 0.0),
                                 axis=1, keepdims=True)
                scale = jnp.maximum(absmax / (2.0 ** (bits - 1) - 1), 1e-8)
                zero = jnp.zeros_like(scale)
            else:
                wmax = jnp.maximum(jnp.max(
                    jnp.where(gmask, wb, -jnp.inf), axis=1,
                    keepdims=True), 0.0)
                wmin = jnp.minimum(jnp.min(
                    jnp.where(gmask, wb, jnp.inf), axis=1,
                    keepdims=True), 0.0)
                scale = jnp.maximum((wmax - wmin) / qmax, 1e-8)
                zero = jnp.clip(jnp.round(-wmin / scale), 0.0, qmax)
            gsel = groups == ((c1 + j) // group_size)        # (1, n_groups)
            sfull = jnp.where(gsel, scale, sfull)
            zfull = jnp.where(gsel, zero, zfull)
            return scale, zero, sfull, zfull

        # group-entry refresh only (the cond skips the reductions on the
        # other group_size-1 columns, like the XLA body)
        scale, zero, sfull, zfull = jax.lax.cond(
            j % group_size == 0, refresh,
            lambda args: (args[1], args[2], args[3], args[4]),
            (wb, scale, zero, sfull, zfull))

        # one-hot extraction is exact: a single nonzero per reduction
        wcol = jnp.sum(jnp.where(onehot, wb, 0.0), axis=1,
                       keepdims=True)                        # (out_t, 1)
        d = jnp.sum(jnp.where(onehot, diag, 0.0), axis=1,
                    keepdims=True)                           # (1, 1)
        if symmetric:
            lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
            q = jnp.clip(jnp.round(wcol / scale), lo, hi) * scale
        else:
            q = (jnp.clip(jnp.round(wcol / scale) + zero, 0.0, qmax)
                 - zero) * scale
        err = (wcol - q) / d                                 # (out_t, 1)
        urow = jnp.sum(jnp.where(rows_bs == j, ub, 0.0), axis=0,
                       keepdims=True)                        # (1, bs)
        mask = (cols_bs > j).astype(jnp.float32)
        wb = wb - err * (urow * mask)
        wb = jnp.where(onehot, q, wb)
        errb = jnp.where(onehot, err, errb)
        return wb, errb, scale, zero, sfull, zfull

    init = (wb0, jnp.zeros_like(wb0),
            jnp.zeros((out_t, 1), jnp.float32),
            jnp.zeros((out_t, 1), jnp.float32), s_ref[0], z_ref[0])
    wb, errb, _, _, sfull, zfull = jax.lax.fori_loop(
        0, blocksize, col_step, init)

    # lazy batch update: W[:, c2:] -= Err @ U[c1:c2, c2:] — same operand
    # shapes as the XLA path so the contraction rounds identically
    tail = (cols_in >= c1 + blocksize).astype(jnp.float32)
    wq_ref[0] = wq_ref[0] - jnp.dot(errb, u_rows * tail,
                                    preferred_element_type=jnp.float32)
    wq_ref[0, :, pl.ds(c1, blocksize)] = wb
    s_ref[0] = sfull
    z_ref[0] = zfull
    err_ref[0] = err_ref[0] + jnp.sum(errb * errb, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bits", "group_size",
                                             "blocksize", "block_out",
                                             "symmetric", "interpret"))
def gptq_block_pallas(w: jax.Array, hinv_u: jax.Array, *, bits: int = 4,
                      group_size: int = 128, blocksize: int = 128,
                      block_out: int = DEFAULT_BLOCK_OUT,
                      symmetric: bool = False, interpret: bool = True
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full GPTQ sweep for a stacked group. One ``pallas_call``.

    w: (B, out, in) f32; hinv_u: (B, in, in) upper Cholesky of H̃^{-1}.
    Returns (w_q (B, out, in), scales (B, out, in//group_size), zeros
    (same), err_rows (B, out, 1) per-row Σerr² — trailing singleton keeps
    the output block TPU-tileable).  Divisibility is the caller's
    contract: ``in % blocksize == 0``, ``blocksize % group_size == 0``,
    ``out % block_out == 0`` (ops.py pads rows and slices back).
    """
    b, out_dim, in_dim = w.shape
    assert in_dim % blocksize == 0 and blocksize % group_size == 0, \
        (w.shape, blocksize, group_size)
    assert out_dim % block_out == 0, (w.shape, block_out)
    n_groups = in_dim // group_size
    grid = (b, out_dim // block_out, in_dim // blocksize)
    kernel = functools.partial(_gptq_block_kernel, bits=bits,
                               group_size=group_size, blocksize=blocksize,
                               symmetric=symmetric)
    tile = lambda m, i, k: (m, i, 0)                  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # read at k == 0 only: one buffer, no prefetch to hide
            pl.BlockSpec((1, block_out, in_dim), tile,
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((1, blocksize, in_dim), lambda m, i, k: (m, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_out, in_dim), tile),
            pl.BlockSpec((1, block_out, n_groups), tile),
            pl.BlockSpec((1, block_out, n_groups), tile),
            pl.BlockSpec((1, block_out, 1), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, out_dim, in_dim), jnp.float32),
            jax.ShapeDtypeStruct((b, out_dim, n_groups), jnp.float32),
            jax.ShapeDtypeStruct((b, out_dim, n_groups), jnp.float32),
            jax.ShapeDtypeStruct((b, out_dim, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(w.astype(jnp.float32), hinv_u.astype(jnp.float32))
