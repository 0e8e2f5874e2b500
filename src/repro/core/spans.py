"""Named host spans of the quantize path.

``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation``: while
a profiler session is open it writes a host event into the trace, on the
clock of the device's ``XLA Ops`` / ``XLA Modules`` lines, so that idle
device time can be put down to the phase the host was in. With no
session open it records nothing and costs a check.

Inside ``recording(into)``, and only while a session is open, each span
that closes is also appended to ``into`` as ``(name, start_ns, end_ns)``
on ``time.perf_counter_ns``. ``quantize_model`` records into its report
(``QuantReport.spans``), so a reader of a traced job's report has its
spans without the raw profile.

The spans, each opened where its work happens:

==========================  ==============================================
``quant.job``               ``pipeline.quantize_model``, the whole call
``quant.walker``            building the walker (embedding the batches)
``quant.step``              one layer step of the walk (``layer=<item>``)
``quant.resolve``           slicing a layer's params out of the stack
``quant.capture``           capture forwards + Hessian accumulation
``quant.fwd_build``         a ``ForwardCache`` miss: the jit's first call
``quant.plan``              param copy, plan members, ``build_plan``
``quant.stage1.inputs``     stacking a group's stage-1 inputs
``quant.stage1``            the stage-1 call, guardrail check and sync
``quant.stage2.inputs``     stacking a group's stage-2 inputs
``quant.stage2``            the stage-2 call and its sync
``quant.results``           masks, gather, report records, member slices
``quant.scatter``           writing the results into the layer's params
``quant.propagate``         the quantized layer's forward
==========================  ==============================================
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import time
from typing import Callable, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

JOB = "quant.job"
WALKER = "quant.walker"
STEP = "quant.step"
RESOLVE = "quant.resolve"
CAPTURE = "quant.capture"
FWD_BUILD = "quant.fwd_build"
PLAN = "quant.plan"
STAGE1_INPUTS = "quant.stage1.inputs"
STAGE1 = "quant.stage1"
STAGE2_INPUTS = "quant.stage2.inputs"
STAGE2 = "quant.stage2"
RESULTS = "quant.results"
SCATTER = "quant.scatter"
PROPAGATE = "quant.propagate"

Record = Tuple[str, int, int]          # (name, start_ns, end_ns)

_SINK: contextvars.ContextVar[Optional[List[Record]]] = \
    contextvars.ContextVar("quant_span_sink", default=None)


def span(name: str, **meta):
    """``with span(name, **meta):`` — a host span (module docstring)."""
    sink = _SINK.get()
    if sink is None or not TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **meta)
    return _Recorded(name, meta, sink)


class _Recorded:
    """A span that is also appended to a ``recording`` when it closes."""
    __slots__ = ("_name", "_ann", "_sink", "_t0")

    def __init__(self, name: str, meta: dict, sink: List[Record]):
        self._name, self._sink = name, sink
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "_Recorded":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._sink.append((self._name, self._t0, time.perf_counter_ns()))
        self._ann.__exit__(*exc)


def spanned(name: str) -> Callable[[Callable], Callable]:
    """Decorator: each call of the function runs inside ``span(name)``."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def recording(into: List[Record]) -> Iterator[List[Record]]:
    """Append the spans that close inside the block, while a profiler
    session is open, to ``into``."""
    token = _SINK.set(into)
    try:
        yield into
    finally:
        _SINK.reset(token)
