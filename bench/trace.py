"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
readers need: the device's operations and programs on one clock, the
harness's own host spans, busy and idle time, and the breakdown the
result line carries.

Only the traced window counts: the span ``bench_trace_window`` that the
harness opens right after the profiler starts and closes right before it
stops. A device is busy where any operation of its ``XLA Ops`` line runs;
busy time is the union of those intervals, averaged over the devices. An
idle gap is named after the harness span (``quantize_model``,
``pack_for_serving``) that covers most of it, or ``other``.

An operation's event is named by its HLO text (``%name.N = type
op(...)``); ``Op.name`` is the instruction's own name, ``name.N``,
``Op.shapes`` the array shapes the text names (results first, then
operands), and a
Pallas kernel's is that of the jitted function around its
``pallas_call`` (``gptq_block_pallas.1``), so ``select("gptq_block_pallas")``
finds the kernel's calls and not the ops that read their results. Each
operation also carries the program (``XLA Modules`` event) it ran in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_trace_window"
HOST_SPANS = ("quantize_model", "pack_for_serving")
_ARRAY = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


@dataclass
class Op:
    name: str           # the HLO instruction's name, or the event's
    start: int          # ns
    end: int            # ns
    module: str = ""
    shapes: Tuple[Tuple[int, ...], ...] = ()


@dataclass
class Summary:
    window: Tuple[int, int]                  # ns, on the trace's clock
    ops: List[Op]                            # device ops inside the window
    modules: List[Op]                        # device programs in the window
    spans: List[Op]                          # harness host spans
    n_devices: int = 1
    _busy: Optional[float] = field(default=None, repr=False)

    # -- reading --------------------------------------------------------
    @classmethod
    def from_dir(cls, trace_dir: str) -> "Summary":
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(max(files)))

    @classmethod
    def from_profile(cls, pd) -> "Summary":
        dev_ops: List[List[Op]] = []
        modules: List[Op] = []
        spans: List[Op] = []
        window = None
        for plane in pd.planes:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                dev_ops.append([_op(e) for e in lines["XLA Ops"].events])
                if "XLA Modules" in lines:
                    modules.extend(_op(e) for e in lines["XLA Modules"].events)
                continue
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == WINDOW_SPAN:
                            window = (e.start_ns, e.start_ns + e.duration_ns)
                        elif e.name in HOST_SPANS:
                            spans.append(_op(e))
        if window is None:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        ops: List[Op] = []
        for per_dev in dev_ops:
            ops.extend(o for o in per_dev if o.end > window[0]
                       and o.start < window[1])
        modules = sorted((m for m in modules if m.end > window[0]
                          and m.start < window[1]), key=lambda m: m.start)
        _attribute(ops, modules)
        return cls(window, sorted(ops, key=lambda o: o.start), modules,
                   sorted(spans, key=lambda s: s.start),
                   max(1, len(dev_ops)))

    # -- what readers ask -----------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        if self._busy is None:
            ivs = [(max(o.start, self.window[0]), min(o.end, self.window[1]))
                   for o in self.ops]
            self._busy = sum(b - a for a, b in union(ivs)) * 1e-9 \
                / self.n_devices
        return self._busy

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def select(self, pattern: str, module: str = "") -> List[Op]:
        """Ops whose name holds ``pattern`` (and whose program's name
        holds ``module``)."""
        return [o for o in self.ops
                if pattern in o.name and module in o.module]

    def programs(self, pattern: str) -> List[Op]:
        return [m for m in self.modules if pattern in m.name]

    def seconds(self, ops: Iterable[Op]) -> float:
        return sum(o.end - o.start for o in ops) * 1e-9

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        by_op: Dict[str, int] = {}
        for o in self.ops:
            key = f"{_short(o.module)}/{re.sub(r'[.][0-9]+$', '', o.name)}"
            by_op[key] = by_op.get(key, 0) + (o.end - o.start)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        by_gap: Dict[str, int] = {}
        for a, b in self.gaps():
            name = self.host_during(a, b)
            by_gap[name] = by_gap.get(name, 0) + (b - a)
        gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}

    def gaps(self) -> List[Tuple[int, int]]:
        ivs = union([(max(o.start, self.window[0]),
                      min(o.end, self.window[1])) for o in self.ops])
        out, t = [], self.window[0]
        for a, b in ivs:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def host_during(self, a: int, b: int) -> str:
        best, cover = "other", 0
        for s in self.spans:
            c = min(b, s.end) - max(a, s.start)
            if c > cover:
                best, cover = s.name, c
        return best


def union(ivs: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op(e) -> Op:
    name, _, text = e.name.partition(" = ")
    shapes = tuple(tuple(int(d) for d in dims.split(",") if d)
                   for dims in _ARRAY.findall(text))
    return Op(name.lstrip("%"), e.start_ns, e.start_ns + e.duration_ns,
              shapes=shapes)


def _attribute(ops: List[Op], modules: List[Op]) -> None:
    starts = [m.start for m in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and modules[i].end >= o.end:
            o.module = modules[i].name


def _short(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module) or "none"
