"""Benchmark driver: one module per paper quality/memory table + the
serving record.

    PYTHONPATH=src python -m benchmarks.run [table1 table2 ...] [--tiny]

Writes artifacts/bench/<table>.json and prints a flat CSV-ish summary.
``--tiny`` shrinks table5 to one cell per curvature mode (the stage-2
convergence-path smoke, run by scripts/check.sh) and the serving sweep to
a smoke. Speed is measured on the chip by ``bench/``, not here. Set
REPRO_BENCH_STEPS to raise the training budget (default keeps the whole
suite a few CPU-minutes)."""
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
                            table3_memory, table5_convergence, serving_bench)
    suites = {
        "table1": lambda: table1_lm_quality.run(steps=steps),
        "table2": lambda: table2_vlm_overfit.run(steps=max(40, steps // 2)),
        "table3": table3_memory.run,
        "table5": lambda: table5_convergence.run(steps=max(40, steps // 2),
                                                 tiny=tiny),
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
        if name == "serving" and not tiny:
            with open("BENCH_serving.json", "w") as f:
                json.dump(rows, f, indent=1)
            print(f"  wrote BENCH_serving.json ({len(rows)} rows)")
        for r in rows:
            print("  " + ",".join(f"{k}={v}" for k, v in r.items()))
        print(f"  ({dt:.1f}s)")
        all_rows.extend(rows)
    print(f"\nwrote {len(all_rows)} rows to artifacts/bench/")


if __name__ == "__main__":
    main()
