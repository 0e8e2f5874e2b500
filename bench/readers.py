"""Arithmetic shared by the per-layer readers in ``bench/metrics/``.

Every share of a roofline or of a peak here is a plain ratio: the least
time the chip could take for the counted work over the device time it
took. None, never 0, when the trace holds nothing to read.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

# Pallas kernels by the jitted function around each ``pallas_call``: the
# device trace names a kernel's call after it
KERNEL = {"gptq_block": "gptq_block_pallas",
          "hessian_accum": "hessian_accum_pallas"}


def t_min(ctx, flops: float, byts: float) -> float:
    """Least seconds for the work: the larger of its compute bound (bf16
    peak: the program's f32 matmuls run as one bf16 pass) and its
    bandwidth bound."""
    p = ctx.peaks
    return max(flops / p["bf16_flops_per_s"], byts / p["hbm_bytes_per_s"])


def bound_name(ctx, flops: float, byts: float) -> str:
    p = ctx.peaks
    return ("compute" if flops / p["bf16_flops_per_s"]
            >= byts / p["hbm_bytes_per_s"] else "bandwidth")


def share(ctx, name: str, least_s: float, took_s: float) -> Optional[float]:
    if took_s <= 0.0 or least_s <= 0.0:
        return None
    pct = 100.0 * least_s / took_s
    ctx.log(f"{name}: least {least_s:.6g} s of {took_s:.6g} s -> {pct:.4g} %")
    return pct


def kernel_roofline(ctx, kernel: str,
                    work: Callable[[Tuple], Tuple[float, float]]
                    ) -> Optional[float]:
    """Roofline share of ``kernel``'s calls in the traced window. Each
    call's work is counted from the shapes its event names, by ``work``
    (the event's shapes → (flops, bytes)), so the share follows whichever
    linears the kernel really took."""
    tr = ctx.trace
    if tr is None or not ctx.peaks:
        return None
    ops = tr.select(KERNEL[kernel])
    if not ops:
        return None
    calls = [work(o.shapes) for o in ops]
    by_shape = {}
    for o in ops:
        by_shape[o.shapes[:1]] = by_shape.get(o.shapes[:1], 0) + 1
    ctx.log(f"{kernel}: {len(ops)} calls by result shape {by_shape}, bound "
            f"{bound_name(ctx, *calls[0])}")
    least = sum(t_min(ctx, f, b) for f, b in calls)
    return share(ctx, kernel + "_roofline", least, tr.seconds(ops))
