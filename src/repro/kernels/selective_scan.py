"""Pallas TPU kernel: Mamba-1 selective scan with VMEM-resident state.

The pure-JAX associative scan materializes the (B, S, d_inner, n)
state-expansion tensors a = exp(Δ⊙A) and b = Δ⊙B⊙u in HBM (~25× the residual-stream bytes for falcon-mamba prefill_32k —
measured). The CUDA reference (selective_scan_cuda) keeps h in shared
memory; the TPU-native formulation here:

  - grid (B, d/bd, S/ts): batch × d-tiles parallel, the TIME axis is the
    innermost (sequential) grid dim, so the (bd, n) state scratch persists
    in VMEM across time tiles — the recurrence never touches HBM;
  - per time tile, (ts, bd) slabs of u/Δ and (ts, n) slabs of B/C stream
    through VMEM; a_t = exp(Δ_t ⊙ A) is computed in-register (A is a
    VMEM-resident (bd, n) constant per tile);
  - the time loop inside the tile is a ``fori_loop`` over ts steps of rank-1
    state updates h ← a_t ⊙ h + (Δ_t u_t)·B_t and y_t = h·C_t + D⊙u_t —
    vector ops on (bd, n), MXU-free by design (the op is bandwidth-bound;
    the win is HBM traffic, not flops).

HBM traffic: reads u, Δ (B,S,bd-tiled), B, C (B,S,n), writes y (B,S,d) —
O(B·S·d) instead of O(B·S·d·n). Validated in interpret mode against
``ref.selective_scan_ref`` over shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_D = 256    # d_inner tile (lane-aligned)
DEFAULT_BLOCK_T = 128    # time steps per VMEM slab


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, dskip_ref, h0_ref,
                y_ref, hout_ref, h_scratch, *, n_t_tiles: int, ts: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        h_scratch[...] = h0_ref[0].astype(jnp.float32)

    a_neg = -jnp.exp(a_ref[...].astype(jnp.float32))  # A = -exp(a_log)
    dskip = dskip_ref[...].astype(jnp.float32)        # (1, bd)

    def step(i, h):
        dt_i = dt_ref[0, i, :].astype(jnp.float32)          # (bd,)
        u_i = u_ref[0, i, :].astype(jnp.float32)            # (bd,)
        b_i = b_ref[0, i, :].astype(jnp.float32)            # (n,)
        c_i = c_ref[0, i, :].astype(jnp.float32)            # (n,)
        a_i = jnp.exp(dt_i[:, None] * a_neg)                # (bd, n)
        h = a_i * h + (dt_i * u_i)[:, None] * b_i[None, :]  # (bd, n)
        y_i = jnp.sum(h * c_i[None, :], axis=1) + dskip[0] * u_i
        y_ref[0, i, :] = y_i.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, ts, step, h_scratch[...])
    h_scratch[...] = h

    @pl.when(t_idx == n_t_tiles - 1)
    def _out():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "block_t",
                                             "interpret"))
def selective_scan_pallas(u: jax.Array, dt: jax.Array, bm: jax.Array,
                          cm: jax.Array, a_log: jax.Array,
                          d_skip: jax.Array, h0: jax.Array, *,
                          block_d: int = DEFAULT_BLOCK_D,
                          block_t: int = DEFAULT_BLOCK_T,
                          interpret: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
    """u/dt: (B, S, d); bm/cm: (B, S, n); a_log: (d, n) with A = -exp(a_log);
    d_skip: (d,); h0: (B, d, n). Returns (y (B,S,d), h_last (B,d,n)).

    Divisibility: S % block_t == 0, d % block_d == 0 (ops.py pads).
    """
    B, S, d = u.shape
    n = bm.shape[-1]
    block_d = min(block_d, d)
    block_t = min(block_t, S)
    assert S % block_t == 0 and d % block_d == 0, (u.shape, block_t, block_d)
    grid = (B, d // block_d, S // block_t)
    kernel = functools.partial(_ssm_kernel, n_t_tiles=grid[2], ts=block_t)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_t, block_d), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_t, n), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, n), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((block_d, n), lambda b, i, t: (i, 0)),
            pl.BlockSpec((1, block_d), lambda b, i, t: (0, i)),
            pl.BlockSpec((1, block_d, n), lambda b, i, t: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_d, n), lambda b, i, t: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d), u.dtype),
            jax.ShapeDtypeStruct((B, d, n), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
    )(u, dt, bm, cm, a_log, d_skip.reshape(1, d), h0)
    return y, h_last
