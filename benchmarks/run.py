"""Benchmark driver: one module per paper table + the roofline summary.

    PYTHONPATH=src python -m benchmarks.run [table1 table2 ...] [--tiny]

Writes artifacts/bench/<table>.json and prints a flat CSV-ish summary.
``--tiny`` shrinks table4 to a CI smoke (single config, fewer repeats) and
table5 to one cell per curvature mode (the stage-2 convergence-path smoke)
— scripts/check.sh runs both. A FULL table4 run additionally rewrites the
stable machine-trackable ``BENCH_table4.json`` at the repo root — flat rows
of ``{config, impl, cold_s, warm_s, executor_s, stage1_s, stage2_s,
xla_ops, xla_ops_s2}`` so the perf trajectory (per-linear → batched-xla →
batched-pallas, per stage) is diffable across PRs; docs/BENCHMARKS.md
documents the schema, the regeneration contract, and why interpret-mode
pallas wall-times must not be read as perf. Set REPRO_BENCH_STEPS to raise
the training budget (default keeps the whole suite a few CPU-minutes)."""
from __future__ import annotations

import json
import os
import sys
import time


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    tiny = "--tiny" in argv
    argv = [a for a in argv if a != "--tiny"]
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "100"))

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    from benchmarks import (table1_lm_quality, table2_vlm_overfit,
                            table3_memory, table4_time, table5_convergence,
                            roofline, serving_bench)
    suites = {
        "table1": lambda: table1_lm_quality.run(steps=steps),
        "table2": lambda: table2_vlm_overfit.run(steps=max(40, steps // 2)),
        "table3": table3_memory.run,
        "table4": lambda: table4_time.run(tiny=tiny),
        "table5": lambda: table5_convergence.run(steps=max(40, steps // 2),
                                                 tiny=tiny),
        "roofline": roofline.run,
        "serving": lambda: serving_bench.run(tiny=tiny),
    }
    wanted = argv or list(suites)
    os.makedirs("artifacts/bench", exist_ok=True)
    all_rows = []
    for name in wanted:
        t0 = time.perf_counter()
        print(f"== {name} ==", flush=True)
        rows = suites[name]()
        dt = time.perf_counter() - t0
        with open(f"artifacts/bench/{name}.json", "w") as f:
            json.dump(rows, f, indent=1)
        if name == "table4" and not tiny:
            # --tiny is a smoke run (single config, no MoE row) — don't let
            # it clobber the full cross-PR trajectory at the repo root
            flat = [b for r in rows for b in r.get("bench", [])]
            with open("BENCH_table4.json", "w") as f:
                json.dump(flat, f, indent=1)
            print(f"  wrote BENCH_table4.json ({len(flat)} impl rows)")
        if name == "serving" and not tiny:
            with open("BENCH_serving.json", "w") as f:
                json.dump(rows, f, indent=1)
            print(f"  wrote BENCH_serving.json ({len(rows)} rows)")
        for r in rows:
            print("  " + ",".join(f"{k}={v}" for k, v in r.items()
                                  if k != "bench"))
        print(f"  ({dt:.1f}s)")
        all_rows.extend(rows)
    print(f"\nwrote {len(all_rows)} rows to artifacts/bench/")


if __name__ == "__main__":
    main()
