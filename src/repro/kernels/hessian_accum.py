"""Pallas TPU kernel: blocked Gram-matrix accumulation  H = X^T X.

Calibration hot spot of GPTQ/RPIQ stage 1 (paper eq. 9). The GPU reference
uses cuBLAS syrk on the full activation matrix; on TPU we tile the (d, d)
output into square (block_d, block_d) VMEM blocks and accumulate rank-
``block_n`` updates on the MXU, streaming the token dimension through VMEM
so arbitrarily long calibration batches never materialize in VMEM at once.

Grid: (d/block_d, d/block_d, n/block_n); the n-axis is the reduction
(innermost, sequential on TPU), so the output block stays resident in VMEM
across the accumulation. Small tiles take thousands of grid steps and
re-read ``x`` for every output tile, so the dispatcher
(``ops.hessian_accum``) picks large tiles and deep token slabs from
``(n, d)``: few steps, each a large dot.
Where one tile covers the whole square (``block_d == d``) the grid is
(n/block_n,) and ``x`` is read once, as a single operand; otherwise every
output tile reads its two column panels of ``x``.

Both triangles are computed: the kernel's work is 2·n·d² operations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hessian_kernel(*refs, k_axis: int):
    """One output tile; accumulate over the token-grid axis ``k_axis``.
    ``refs`` is (xi, xj, h), or (x, h) for the whole-square tile."""
    *x_refs, h_ref = refs

    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xi = x_refs[0][...].astype(jnp.float32)     # (bn, bi)
    xj = xi if len(x_refs) == 1 else x_refs[1][...].astype(jnp.float32)
    h_ref[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),       # contract token dim
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "block_n",
                                             "vmem_limit_bytes", "interpret"))
def hessian_accum_pallas(x: jax.Array, *, block_d: int, block_n: int,
                         vmem_limit_bytes: int | None = None,
                         interpret: bool = True) -> jax.Array:
    """H = X^T X. x: (n, d); n % block_n == 0 and d % block_d == 0
    (ops.py pads otherwise). ``vmem_limit_bytes`` raises Mosaic's scoped
    VMEM limit for large tiles (None keeps the compiler's default).
    Returns (d, d) float32."""
    n, d = x.shape
    assert n % block_n == 0 and d % block_d == 0, (x.shape, block_n, block_d)
    nt = d // block_d
    if nt == 1:
        grid = (n // block_n,)
        in_specs = [pl.BlockSpec((block_n, d), lambda k: (k, 0))]
        out_spec = pl.BlockSpec((d, d), lambda k: (0, 0))
        semantics, operands = ("arbitrary",), (x,)
    else:
        grid = (nt, nt, n // block_n)
        in_specs = [
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
        ]
        out_spec = pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j))
        semantics, operands = ("parallel", "parallel", "arbitrary"), (x, x)
    return pl.pallas_call(
        functools.partial(_hessian_kernel, k_axis=len(grid) - 1),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(*operands)
