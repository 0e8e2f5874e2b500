"""Mesh construction.

Functions (not module-level constants) so importing never touches jax
device state. Axes are (data, model), or (pod, data, model) with a ``pod``
axis that crosses DCN; sharding rules keep per-layer traffic off it (DP
gradient reduction and optional GPipe stages are the only pod-axis
collectives).

``make_quant_mesh`` resolves the ``quant.mesh`` knob into the
``(data, model)`` — or, with an expert-parallel axis, ``(data, model,
expert)`` — mesh the sharded quantization executor runs on (DESIGN.md
§2.6, docs/QUANTIZATION.md); the default "off" keeps every config on the
single-device path.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules here are
    GSPMD-style (the compiler propagates shardings), which Explicit axes —
    the default since JAX 0.9 — reject at the first resharding op."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1,
                   expert: int = 1):
    """Small CPU mesh for tests (requires forced host device count).

    ``expert > 1`` appends the expert-parallel axis (quantization-side
    only: stacked MoE slabs shard their lane axis over it — DESIGN.md
    §2.6); it composes with ``data``/``model`` but not ``pod``.
    """
    if expert > 1:
        if pod > 1:
            raise ValueError("expert axis does not compose with pod axis")
        return _mesh((data, model, expert), ("data", "model", "expert"))
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_quant_mesh(spec: str = "off") -> Optional[Mesh]:
    """``quant.mesh`` knob → Mesh for sharded group execution.

    - "off" (default) / "" / "none" / "1x1" → None: single-device batched
      execution;
    - "auto" → all local devices on the ``data`` axis (lane parallelism
      needs no Cout divisibility, so it degrades most gracefully);
    - "DxM" (e.g. "2x2", "8x1") → explicit (data, model) axis sizes over
      the first D·M local devices;
    - "DxMxE" (e.g. "1x1x8", "2x1x4") → adds the ``expert`` axis:
      groups made entirely of stacked expert slabs shard lanes over
      expert (×data), everything else ignores the axis.

    Raises ``ValueError`` when the spec is malformed or asks for more
    devices than the process has: a run that asked for a mesh and got one
    device would look like a pass of the sharded path without having run
    it.
    """
    if not spec or spec in ("off", "none", "1", "1x1", "1x1x1"):
        return None
    if spec == "auto":
        n = jax.device_count()
        if n <= 1:
            return None
        return make_host_mesh(data=n, model=1)
    parts = spec.lower().split("x")
    try:
        sizes = [int(p) for p in parts]
    except ValueError:
        sizes = []
    if len(sizes) not in (2, 3) or any(s < 1 for s in sizes):
        raise ValueError(f"quant.mesh={spec!r} is not 'off', 'auto', 'DxM' "
                         "or 'DxMxE' with positive axis sizes")
    d, m = sizes[0], sizes[1]
    e = sizes[2] if len(sizes) == 3 else 1
    total = d * m * e
    if total <= 1:
        return None
    if jax.device_count() < total:
        raise ValueError(f"quant.mesh={spec!r} needs {total} devices, have "
                         f"{jax.device_count()}")
    return make_host_mesh(data=d, model=m, expert=e)
