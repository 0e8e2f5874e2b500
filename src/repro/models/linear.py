"""Tappable, quantization-aware dense layer.

Every matmul the quantizer can touch goes through :func:`dense`. Three
behaviours, decided by the *value* stored under ``"w"``:

  - plain ``jax.Array`` of shape (in, out): ordinary ``x @ w``;
  - :class:`~repro.core.quant.QuantizedTensor` (packed int4, stored
    (out, in)-major like GPTQ): the W4A16 path via ``repro.kernels.ops``;
  - during calibration a :class:`Tap` context records the layer inputs by
    name, which is how the quantization pipeline collects Hessians and the
    single-instance batch without any framework hooks.

Default taps only fire outside jit — inside jit the records would be
tracers, so ``Tap.record`` refuses them loudly.  The jitted calibration
forward (core/pipeline.py) instead opens a ``Tap(collect_tracers=True)``
*inside* the traced function: records are then collected as tracers and
returned as part of the jitted function's output, which is how capture
runs compiled without framework hooks.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedTensor
from repro.kernels import ops as kops

_ACTIVE_TAPS: List["Tap"] = []


class Tap:
    """Context manager that observes inputs of named dense layers: each
    time a matching dense layer runs, its input is appended to
    ``records[name]``."""

    def __init__(self, prefix: str = "", collect_tracers: bool = False):
        self.prefix = prefix
        self.records: Dict[str, List[jax.Array]] = {}
        self._collect_tracers = collect_tracers

    def __enter__(self) -> "Tap":
        _ACTIVE_TAPS.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE_TAPS.remove(self)

    def record(self, name: str, x: jax.Array) -> None:
        if not name.startswith(self.prefix):
            return
        if self._collect_tracers:
            # jitted-capture mode: tracers are expected; the caller returns
            # self.records from the traced function (core/pipeline.py)
            self.records.setdefault(name, []).append(x)
            return
        if isinstance(x, jax.core.Tracer):
            raise RuntimeError(
                f"Tap saw a tracer for {name!r}: calibration forwards must "
                "run eagerly (outside jit)")
        self.records.setdefault(name, []).append(x)


def dense(p: Dict, x: jax.Array, name: str = "") -> jax.Array:
    """y = x @ w (+ b). p: {"w": (in, out) array | QuantizedTensor, "b"?}."""
    w = p["w"]
    if name and _ACTIVE_TAPS:
        for tap in _ACTIVE_TAPS:
            tap.record(name, x)
    if isinstance(w, QuantizedTensor):
        y = kops.w4a16_matmul(x, w.packed, w.scales, w.zeros,
                              group_size=w.group_size)
    else:
        y = jnp.dot(x, w.astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p and p["b"] is not None:
        y = y + p["b"].astype(y.dtype)
    return y


def init_dense(key: jax.Array, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: float | None = None) -> Dict:
    if scale is None:
        scale = d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_weight_oi(p: Dict) -> jax.Array:
    """The (out, in)-major float view the quantizer consumes."""
    w = p["w"]
    if isinstance(w, QuantizedTensor):
        from repro.core.quant import dequantize_packed
        return dequantize_packed(w)     # QuantizedTensor is (out, in)-major
    return jnp.asarray(w).T             # model storage is (in, out)


def set_dense_weight_oi(p: Dict, w_oi: jax.Array) -> Dict:
    """Replace the weight from an (out, in) float matrix, keeping dtype."""
    old = p["w"]
    dtype = old.dtype if isinstance(old, jax.Array) else jnp.float32
    out = dict(p)
    out["w"] = w_oi.T.astype(dtype)
    return out
