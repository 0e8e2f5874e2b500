"""Idle device time and device programs put down to the program's own
host spans, for the per-layer readers of the quantize walk and the plan
executor.

The program opens named host spans along the quantize path
(``repro.core.spans``: ``quant.job``, ``quant.step``, ``quant.capture``,
``quant.stage1`` ...). ``quantize_model`` keeps those of a traced job on
its report, ``QuantReport.spans``: ``(name, start_ns, end_ns)`` on the
host's ``perf_counter``. ``Phases.of(ctx)`` maps them onto the clock of
the reduced profile (``bench/trace.py``) and answers what the readers
ask: seconds in spans of a name, idle seconds whose innermost span is in
a set, device programs that start inside spans of a name, and the count
of spans of a name.

Nesting is rebuilt by containment. Each idle gap of the traced window is
split at span boundaries, and each piece is named after the innermost
span covering it: a program span first, then a harness span
(``quantize_model``, ``pack_for_serving``), then ``other``.

The clock: ``bench/drivers/quantize.py`` opens its ``quantize_model``
span around the call, and the program opens ``quant.job`` first thing
inside it, tens of microseconds later (47 us on a TPU v5e host, with the
profiler's Python tracer on). The shift that lines up the two openings
is taken, so every program span lands that much early: on the recorded
v5e job 0.2 % of the idle time moves to a neighbouring phase. The two
closings are no use: ``quantize_model`` frees the run's compiled
forwards and streams after ``quant.job`` closes (6 ms on that host).

A report without spans (an untraced job, or a program that opens none)
gives ``None``: the readers then leave their metric out.
"""
from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace import Op, Summary

# the program's span names (repro.core.spans), as the readers use them
JOB = "quant.job"
STEP = "quant.step"
FWD_BUILD = "quant.fwd_build"
WALK = ("quant.step", "quant.resolve", "quant.capture", "quant.plan",
        "quant.scatter", "quant.propagate")
EXECUTOR = ("quant.stage1.inputs", "quant.stage1", "quant.stage2.inputs",
            "quant.stage2", "quant.results")
PREFIX = "quant."
HARNESS_JOB = "quantize_model"     # the harness span around the call


class Phases:
    """A reduced profile with the program's spans on its clock."""

    def __init__(self, trace: Summary, spans: Iterable[Op]):
        self.trace = trace
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))
        self.idle = self._idle_by_innermost()

    @classmethod
    def of(cls, ctx) -> Optional["Phases"]:
        """The traced job's spans over ``ctx.trace``, or None."""
        tr, k = ctx.trace, ctx.records.get("traced_job")
        if tr is None or k is None:
            return None
        rec = getattr(ctx.records["jobs"][k]["report"], "spans", None)
        jobs = [(a, b) for name, a, b in rec or () if name == JOB]
        hosts = [s for s in tr.spans if s.name == HARNESS_JOB]
        if len(jobs) != 1 or not hosts:
            return None
        (a, b), host = jobs[0], hosts[0]
        shift = host.start - a
        ctx.log(f"phases: {len(rec)} program spans; {HARNESS_JOB} closes "
                f"{(host.end - b - shift) * 1e-3:.1f} us after {JOB}")
        return cls(tr, [Op(name, s + shift, e + shift)
                        for name, s, e in rec])

    # -- what readers ask -------------------------------------------------
    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def seconds_in(self, name: str) -> float:
        return sum(s.end - s.start for s in self.spans
                   if s.name == name) * 1e-9

    def idle_in(self, names: Iterable[str]) -> float:
        """Idle seconds whose innermost span is one of ``names``."""
        names = set(names)
        return sum(v for k, v in self.idle.items() if k in names)

    def programs_in(self, name: str) -> int:
        """Device programs (``XLA Modules`` events) that start inside a
        span of ``name``."""
        ivs = [(s.start, s.end) for s in self.spans if s.name == name]
        starts = [a for a, _ in ivs]
        n = 0
        for m in self.trace.modules:
            i = bisect.bisect_right(starts, m.start) - 1
            if i >= 0 and m.start < ivs[i][1]:
                n += 1
        return n

    def idle_gaps(self, top: int = 10) -> List[List]:
        """[[innermost span, idle seconds]], largest first."""
        return [[k, v] for k, v in sorted(self.idle.items(),
                                          key=lambda kv: -kv[1])[:top]]

    # -- the split ----------------------------------------------------------
    def _pieces(self) -> List[Tuple[int, int, str]]:
        """The window cut at every span boundary, each piece with the
        name of its innermost span."""
        spans = self.spans + self.trace.spans
        edges = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                       + [(s.end, 0, i) for i, s in enumerate(spans)])
        active: set = set()
        out: List[Tuple[int, int, str]] = []
        prev = None
        for t, opens, i in edges:
            if prev is not None and t > prev:
                out.append((prev, t, _innermost(spans, active)))
            (active.add if opens else active.discard)(i)
            prev = t
        return out

    def _idle_by_innermost(self) -> Dict[str, float]:
        pieces = self._pieces()
        by: Dict[str, int] = {}
        j = 0
        for a, b in self.trace.gaps():
            covered = a
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                pa, pb, name = pieces[k]
                lo, hi = max(a, pa), min(b, pb)
                if hi > lo:
                    if lo > covered:
                        by["other"] = by.get("other", 0) + lo - covered
                    by[name] = by.get(name, 0) + hi - lo
                    covered = hi
                k += 1
            if b > covered:
                by["other"] = by.get("other", 0) + b - covered
        return {k: v * 1e-9 for k, v in by.items()}


def _innermost(spans: List[Op], active: set) -> str:
    """A program span before a harness span; among either, the one
    opened last (the shorter on a tie)."""
    if not active:
        return "other"
    best = max(active, key=lambda i: (spans[i].name.startswith(PREFIX),
                                      spans[i].start, -spans[i].end))
    return spans[best].name
