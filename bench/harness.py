"""The harness behind ``bench/run.py``: it finds a cell's files by name,
checks the device, hands the run to the cell's driver, reads the per-layer
metrics and prints the result line.

A driver is ``bench/drivers/<kind>.py`` with ``run(ctx) -> dict``. It sets
up, calls ``ctx.window_start()`` when the measured window opens and
``ctx.window_end()`` when it closes, reads ``ctx.snapshot_memory()``
before it frees the program's state and runs the reference, and returns::

    {"attempted": int, "failed": int,
     "e2e": {metric: value},          # the cell's end-to-end metrics
     "checks": [(name, value, limit)],  # correct iff every value <= limit
     "records": {...}}                # what the per-layer readers read

A per-layer reader is ``bench/metrics/<metric>.py`` with ``read(ctx)``,
returning a number or None when it finds nothing to read (the metric is
then left out of the line). Readers see ``ctx.records``, ``ctx.trace``
(the reduced profile of a traced run, ``bench/trace.py``), ``ctx.peaks``
and ``ctx.opcount(kernel)``, the op and byte function of
``bench/opcount/<kernel>.py``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry with the files its names point to."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        self.entry = by_name[workload]
        self.name = workload
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _read_json(os.path.join(root,
                                              self.config_entry["file"]))
        self.mix = _read_json(self.path("traffic",
                                         self.entry["traffic"] + ".json"))
        self.limits = _read_json(self.path("limits", workload + ".json"))
        self.chips = int(self.entry["chips"])

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)

    def driver(self):
        kind = self.mix["kind"]
        return load_module(self.path("drivers", kind + ".py"),
                           f"bench_driver_{kind}")

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


class CompileCounter:
    """Counts, while armed, the programs JAX compiled (persistent-cache
    misses) and those it loaded from the persistent cache (hits). One
    listener a process."""
    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.armed = False
        self.misses = 0
        self.hits = 0

    def arm(self, on: bool) -> None:
        if on:
            self.misses = self.hits = 0
        self.armed = on

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax
            c = cls()

            def on_event(event: str, **_kw) -> None:
                if not c.armed:
                    return
                if event.endswith("compilation_cache/cache_misses"):
                    c.misses += 1
                elif event.endswith("compilation_cache/cache_hits"):
                    c.hits += 1
            jax.monitoring.register_event_listener(on_event)
            cls._installed = c
        return cls._installed


class Ctx:
    """What a driver and the per-layer readers are handed."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t0: float, log: Callable[[str], None],
                 hooks: Optional[Dict[str, Any]] = None,
                 keep_trace: str = ""):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.limits = cell.limits
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        self.t0 = t0
        self.log = log
        self.hooks = hooks or {}
        self.keep_trace = keep_trace
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes = 0
        self.records: Dict[str, Any] = {}
        self.trace = None              # bench.trace.Summary of a traced run
        self.compiles = CompileCounter.get()
        self.compiles_in_window = 0
        self.cache_loads_in_window = 0
        self.peaks: Dict[str, Any] = {}
        self._opcount: Dict[str, Any] = {}
        self._trace_dir = os.path.join(cell.root, ".bench_trace")

    # -- the window ---------------------------------------------------------
    def window_start(self) -> float:
        now = time.perf_counter()
        self.setup_s = now - self.t0
        self.compiles.arm(True)
        return now

    def window_end(self) -> None:
        self.compiles.arm(False)
        self.compiles_in_window = self.compiles.misses
        self.cache_loads_in_window = self.compiles.hits

    def snapshot_memory(self) -> int:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        self.memory_limit_bytes = int(stats.get("bytes_limit", 0))
        return self.memory_peak_bytes

    # -- tracing --------------------------------------------------------------
    def trace_start(self) -> None:
        """Open the profiler's window (traced runs only)."""
        if not self.trace_on:
            return
        import jax
        from bench import trace as tr
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self._trace_dir)
        self._window_span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._window_span.__enter__()

    def trace_stop(self) -> None:
        if not self.trace_on:
            return
        import jax
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._traced = True

    def reduce_trace(self) -> None:
        """Read the profile the window wrote (after the run, so the
        reduction never stalls the window) and delete it."""
        if not getattr(self, "_traced", False):
            return
        from bench import trace as tr
        if self.keep_trace:
            shutil.rmtree(self.keep_trace, ignore_errors=True)
            shutil.copytree(self._trace_dir, self.keep_trace)
        try:
            self.trace = tr.Summary.from_dir(self._trace_dir)
        except (OSError, ValueError) as e:
            self.log(f"trace: nothing to read ({e!r})")
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    # -- the yardstick -------------------------------------------------
    def opcount(self, kernel: str):
        if kernel not in self._opcount:
            self._opcount[kernel] = load_module(
                self.cell.path("opcount", kernel + ".py"),
                "bench_opcount_" + kernel.replace(".", "_"))
        return self._opcount[kernel]


def _device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"bench: this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} device(s) of platform {d.platform!r} "
            f"({d.device_kind}). No result.")
    return info


def _load_peaks(cell: Cell, kind: str, require_chip: bool) -> Dict:
    table = _read_json(cell.path("peaks.json"))
    if kind in table["devices"]:
        return table["devices"][kind]
    if require_chip:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         "bench/peaks.json; no peaks, no result.")
    return {}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, *, require_chip: bool = True,
             hooks: Optional[Dict[str, Any]] = None,
             keep_trace: str = "") -> Dict:
    """Run one cell once and return its result dict (no printing of the
    result line). ``require_chip=False`` is for the tests, which drive a
    run on the CPU; ``hooks`` put the control or a fault in the program's
    place (``bench/faults.py``), for the tests and ``bench/control.py``."""
    cell = Cell(root, workload)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = _device_info(cell.chips, require_chip)
    tag = f"[bench {dev['platform']} {dev['kind']} x{dev['count']}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", file=sys.stderr, flush=True)

    ctx = Ctx(cell, seed, seconds, trace, t0, log, hooks, keep_trace)
    ctx.peaks = _load_peaks(cell, dev["kind"], require_chip)
    ctx.device = dev
    log(f"{workload} seed={seed} seconds={seconds} trace={int(trace)}")
    out = cell.driver().run(ctx)
    gc.collect()

    metrics: Dict[str, Dict] = {}
    if not trace:
        for m in cell.end_to_end():
            v = out["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx.records.update(out.get("records", {}))
        ctx.reduce_trace()
        for m in cell.per_layer():
            reader = load_module(cell.path("metrics", m["name"] + ".py"),
                                 "bench_metric_" + m["name"]
                                 .replace(".", "_"))
            v = reader.read(ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in out["checks"]}
    correct = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = dict(dev, memory_peak_bytes=ctx.memory_peak_bytes)
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": int(out["attempted"]),
                              "failed": int(out["failed"]),
                              "metrics": metrics, "device": device}
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    log(f"setup_s={ctx.setup_s} compiles_in_window={ctx.compiles_in_window}"
        f" cache_loads_in_window={ctx.cache_loads_in_window}"
        f" memory_peak_bytes={ctx.memory_peak_bytes}")
    result["checks"] = checks
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the raw profile of a traced run here")
    return ap.parse_args(argv)


def main(root: str, argv, t0: float) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t0, keep_trace=args.keep_trace)
    except SystemExit as e:
        print(str(e), file=sys.stderr, flush=True)
        return 1
    except Exception:                   # noqa: BLE001 — the run's boundary
        traceback.print_exc()
        print("bench: the run failed; no result.", file=sys.stderr,
              flush=True)
        return 1
    d = result["device"]
    for name, c in result["checks"].items():
        print(f"[bench {d['platform']} {d['kind']} x{d['count']}] check "
              f"{name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
