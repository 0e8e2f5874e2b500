"""Pallas TPU kernel: the FULL RPIQ stage-2 closed loop per grid cell chain.

RPIQ's headline contribution — the stage-2 multi-collaborative closed-loop
refinement (paper §3.1–3.3, eq. 4–8, 19–23) — lowers in XLA
(``core/rpiq._rpiq_core``) to a ``while_loop``-of-``fori_loop`` chain of
``dynamic_slice`` / small-matmul ops: O(t_max × n_blocks) dispatched ops
per member per refinement, the remaining XLA-op and wall-clock dominator of
every quantize run after the stage-1 sweep was fused (gptq_block.py).

This kernel runs EVERY Gauss–Seidel round inside one ``pallas_call``:

  - grid ``(B, Cout/block_out, t_max+1)`` — the stacked group-member axis ×
    row tiles (exactly :mod:`gptq_block`'s (member, Cout-tile) unit, which
    stays the per-shard unit of the mesh-sharded executor — DESIGN.md §2.6)
    × refinement rounds, rounds iterating innermost so the working tile of
    ``W`` and the running ``Y_q`` slab stay VMEM-resident across the whole
    closed loop of a tile (their block index ignores the round axis);
  - step 0 initializes the tile (``Y_q ← X W₀^T``, Γ₀ partial, candidate
    slot 0 = W₀); step t ≥ 1 runs one full Gauss–Seidel sweep over all
    column blocks: directed residual (eq. 4/20), least-squares solve
    (eq. 13–14) as ONE matmul against the pre-factored explicit block
    inverse ``H_i^{-1}`` (both ``exact_gram`` modes produce the same
    ``(M, bs, bs)`` stack via the existing Cholesky OUTSIDE the kernel —
    no triangular solve in Mosaic), grid projection (eq. 7), damped update
    (eq. 8) and the immediate ``Y_q`` update (eq. 21–22);
  - **deferred closed-loop bookkeeping**: the Gauss–Seidel trajectory is
    independent of the early-stop/best-projection logic (stopping only
    truncates it, the best choice only selects from it), but Γ (eq. 23),
    the stop predicate and the best-projection choice are sums/decisions
    over ALL rows — global across row tiles.  So each round emits its
    per-tile Γ/projected-loss partials into per-member accumulators (the
    accumulator block's index ignores both non-member grid axes, so it is
    VMEM-resident for the member's whole chain) and its projected candidate
    ``Q(W^{(t)})`` into a per-round slot; ``ops.rpiq_block`` reduces the
    partials and replays the exact while-loop semantics (stop threshold,
    strict-improvement best, per-lane ``iters_run``) as a handful of
    vectorized ops on (B, t_max+1) scalars.  Under the row-sharded twin the
    partials are psum-folded across shards first, which is what makes row
    sharding exact for stage 2 (rpiq.py docstring).

Consequences of running rounds unconditionally (documented trade):
  - lanes that early-stop still execute their remaining ≤ t_max−1 rounds
    (dead weight bounded by the small t_max, default 5, against one
    dispatch for all rounds);
  - the returned ``w_cont`` is the t_max-round iterate, not the stop-round
    iterate, whenever early stop fires before t_max.  ``w_q``,
    ``loss_history``, ``proj_loss`` and ``iters_run`` — everything the
    pipeline consumes — replay the XLA path exactly; the XLA body remains
    the reference for ``w_cont``.

VMEM contract: one cell holds five ``(block_out, in)`` tiles (input W₀,
working W, round candidate, expanded scales/zeros), the instance slab
``(n, in)``, two ``(n, block_out)`` output slabs and the ``(in, bs)``
inverse stack — ~``4·(5·block_out·in + n·in + 2·n·block_out + bs·in)``
bytes; ``ops.rpiq_block(impl="auto")`` falls back to the XLA path when that
exceeds the budget instead of failing in Mosaic.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_OUT = 128     # row tile (MXU/lane aligned)


def _iota1d(n: int) -> jax.Array:
    """1D int32 iota via 2D broadcasted_iota (TPU: 1D iota is invalid)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)[0]


def _dot_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T — (m, k) × (n, k) → (m, n), fp32 MXU accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _project(b: jax.Array, s: jax.Array, z: jax.Array, *, bits: int,
             symmetric: bool) -> jax.Array:
    """Q(·): project onto the fixed stage-1 grid (eq. 7).

    The ONE definition, shared with the XLA body (core/rpiq.py imports
    it; this module is a cycle-free leaf).  ``s``/``z`` are pre-expanded
    to column resolution (same shape as ``b``) — the ``jnp.repeat`` grid
    expansion is hoisted OUT of the per-round Gauss–Seidel sweep (it used
    to re-materialize the full grid every block, every round).  Mirrors
    ``gptq._quant_col`` per mode: symmetric grids carry zero ``z`` and
    quantize onto the signed code range.
    """
    if symmetric:
        lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
        return jnp.clip(jnp.round(b / s), lo, hi) * s
    qmax = 2.0 ** bits - 1.0
    q = jnp.clip(jnp.round(b / s) + z, 0.0, qmax)
    return (q - z) * s


def _rpiq_block_kernel(w_ref, yo_ref, x_ref, hinv_ref, s_ref, z_ref,
                       wc_ref, wp_ref, yq_ref, hist_ref, pls_ref, *,
                       bits: int, block_size: int, n_blocks: int,
                       t_max: int, alpha: float, symmetric: bool):
    """One (member, row-tile, round) cell of the closed loop."""
    i = pl.program_id(1)
    t = pl.program_id(2)
    onehot_t = (_iota1d(t_max + 1) == t).astype(jnp.float32)

    @pl.when(t == 0)
    def _init():
        w0 = w_ref[0].astype(jnp.float32)
        wc_ref[0] = w0
        wp_ref[0, 0] = w0                       # candidate slot 0 = W₀
        y0 = _dot_t(x_ref[0], w0)               # Y_q ← X W₀^T
        yq_ref[0] = y0
        g0 = jnp.sum((yo_ref[0] - y0) ** 2)     # Γ₀ partial (this tile)

        @pl.when(i == 0)
        def _zero():
            hist_ref[0, 0] = jnp.zeros((t_max + 1,), jnp.float32)
            pls_ref[0, 0] = jnp.zeros((t_max + 1,), jnp.float32)

        hist_ref[0, 0] = hist_ref[0, 0] + g0 * onehot_t
        pls_ref[0, 0] = pls_ref[0, 0] + g0 * onehot_t

    @pl.when(t > 0)
    def _round():
        def block_step(b, carry):
            c1 = pl.multiple_of(b * block_size, block_size)
            b_old = wc_ref[0, :, pl.ds(c1, block_size)]       # (out_t, bs)
            x_i = x_ref[0, :, pl.ds(c1, block_size)]          # (n, bs)
            y_qi = _dot_t(x_i, b_old)                         # (n, out_t)
            d_i = yo_ref[0] - (yq_ref[0] - y_qi)              # eq. 4/20
            rhs = jax.lax.dot_general(                        # X_i^T D_i
                x_i, d_i, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (bs, out_t)
            hinv_i = hinv_ref[0, pl.ds(c1, block_size), :]    # (bs, bs)
            # eq. 13–14 as one MXU dot against the explicit inverse —
            # same contraction as the XLA body, so rounding matches
            b_star = jax.lax.dot_general(
                rhs, hinv_i, (((0,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (out_t, bs)
            s_i = s_ref[0, :, pl.ds(c1, block_size)]
            z_i = z_ref[0, :, pl.ds(c1, block_size)]
            b_proj = _project(b_star, s_i, z_i, bits=bits,
                              symmetric=symmetric)            # eq. 7
            b_new = b_old + alpha * (b_proj - b_old)          # eq. 8
            yq_ref[0] = yq_ref[0] - y_qi + _dot_t(x_i, b_new)  # eq. 21–22
            wc_ref[0, :, pl.ds(c1, block_size)] = b_new
            return carry

        jax.lax.fori_loop(0, n_blocks, block_step, 0)
        gamma = jnp.sum((yo_ref[0] - yq_ref[0]) ** 2)         # eq. 23
        w_proj = _project(wc_ref[0], s_ref[0], z_ref[0], bits=bits,
                          symmetric=symmetric)
        wp_ref[0, 0] = w_proj                    # candidate slot t
        y_p = _dot_t(x_ref[0], w_proj)
        ploss = jnp.sum((yo_ref[0] - y_p) ** 2)
        hist_ref[0, 0] = hist_ref[0, 0] + gamma * onehot_t
        pls_ref[0, 0] = pls_ref[0, 0] + ploss * onehot_t


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "block_size", "alpha", "t_max", "symmetric",
    "block_out", "interpret"))
def rpiq_block_pallas(w_init: jax.Array, y_orig: jax.Array, x_last: jax.Array,
                      hinv_flat: jax.Array, s_full: jax.Array,
                      z_full: jax.Array, *, bits: int = 4,
                      group_size: int = 128, block_size: int = 128,
                      alpha: float = 0.01, t_max: int = 5,
                      symmetric: bool = False,
                      block_out: int = DEFAULT_BLOCK_OUT,
                      interpret: bool = True
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array, jax.Array]:
    """Full stage-2 closed loop for a stacked group. One ``pallas_call``.

    w_init: (B, out, in) f32 stage-1 weights; y_orig: (B, n, out) reference
    outputs ``X W_fp^T``; x_last: (B, n, in) instance; hinv_flat:
    (B, in, bs) — the (M, bs, bs) explicit block-curvature inverses
    flattened on the row axis; s_full/z_full: (B, out, in) stage-1 grid
    expanded to column resolution (the hoisted ``jnp.repeat``).

    Returns ``(w_cont, w_proj_all, y_q, hist_raw, ploss_raw)``:
    w_cont (B, out, in) t_max-round continuous iterate; w_proj_all
    (B, t_max+1, out, in) per-round projected candidates (slot 0 = W₀);
    y_q (B, n, out) final running outputs; hist_raw/ploss_raw
    (B, 1, t_max+1) raw per-round Γ / projected-loss sums (no early-stop
    masking — ``ops.rpiq_block`` applies the closed-loop bookkeeping).

    Divisibility is the caller's contract: ``in % block_size == 0``,
    ``block_size % group_size == 0``, ``out % block_out == 0``,
    ``t_max >= 1`` (ops.py pads rows / slices back and routes t_max == 0
    to the XLA body).
    """
    b, out_dim, in_dim = w_init.shape
    n = x_last.shape[1]
    assert in_dim % block_size == 0 and block_size % group_size == 0, \
        (w_init.shape, block_size, group_size)
    assert out_dim % block_out == 0, (w_init.shape, block_out)
    assert t_max >= 1, t_max
    n_blocks = in_dim // block_size
    t2 = t_max + 1
    grid = (b, out_dim // block_out, t2)
    kernel = functools.partial(_rpiq_block_kernel, bits=bits,
                               block_size=block_size, n_blocks=n_blocks,
                               t_max=t_max, alpha=alpha, symmetric=symmetric)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_out, in_dim), lambda m, i, t: (m, i, 0)),
            pl.BlockSpec((1, n, block_out), lambda m, i, t: (m, 0, i)),
            pl.BlockSpec((1, n, in_dim), lambda m, i, t: (m, 0, 0)),
            pl.BlockSpec((1, in_dim, block_size), lambda m, i, t: (m, 0, 0)),
            pl.BlockSpec((1, block_out, in_dim), lambda m, i, t: (m, i, 0)),
            pl.BlockSpec((1, block_out, in_dim), lambda m, i, t: (m, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_out, in_dim), lambda m, i, t: (m, i, 0)),
            pl.BlockSpec((1, 1, block_out, in_dim),
                         lambda m, i, t: (m, t, i, 0)),
            pl.BlockSpec((1, n, block_out), lambda m, i, t: (m, 0, i)),
            pl.BlockSpec((1, 1, t2), lambda m, i, t: (m, 0, 0)),
            pl.BlockSpec((1, 1, t2), lambda m, i, t: (m, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, out_dim, in_dim), jnp.float32),
            jax.ShapeDtypeStruct((b, t2, out_dim, in_dim), jnp.float32),
            jax.ShapeDtypeStruct((b, n, out_dim), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, t2), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, t2), jnp.float32),
        ],
        interpret=interpret,
    )(w_init.astype(jnp.float32), y_orig.astype(jnp.float32),
      x_last.astype(jnp.float32), hinv_flat.astype(jnp.float32),
      s_full.astype(jnp.float32), z_full.astype(jnp.float32))
