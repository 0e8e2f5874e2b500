#!/usr/bin/env python3
"""Runs of a cell with the control or a planted fault in the program's
place, several seeds in one process: the readings that set and test the
cell's correctness limits.

    python bench/control.py --workload <name> --hooks control \
        --seeds 11 12 13 --seconds 5 [--out readings.jsonl]

``--hooks`` names entries of ``bench/faults.py`` (``none`` runs the
program as it is). Each run goes through the harness's own comparison;
one JSON line per run gives its ``correct``, the numbers compared beside
their limits, its end-to-end metrics and its memory peak. The
benchmark's own runs never run these.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    os.environ["TPU_LOG_DIR"] = "disabled"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hooks", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from bench import faults, harness
    for name in args.hooks:
        for seed in args.seeds:
            hooks = {} if name == "none" else faults.HOOKS[name]()
            res = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, time.perf_counter(), hooks=hooks)
            line = {"hooks": name, "seed": seed, "correct": res["correct"],
                    "checks": res["checks"],
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
