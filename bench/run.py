#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

``<name>`` is a ``workloads`` entry of ``BENCHMARK.json`` at the root of
the checkout. Everything the cell needs is found from that name: the
configuration ``bench/configs/<config>.json``, the traffic mix or job
``bench/traffic/<traffic>.json`` (whose ``kind`` picks the driver
``bench/drivers/<kind>.py``), the correctness limits
``bench/limits/<workload>.json`` and, under ``--trace 1``, one reader per
per-layer metric, ``bench/metrics/<metric>.py``.

The run fails, printing no result, on anything but a TPU with as many
chips as the cell asks for. The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (traced runs) and ``checks``, the numbers compared with the
reference beside their limits, which also close standard error.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    # before JAX is imported: no compiler logs under a fixed /tmp path, and
    # the persistent compile cache at a fixed path inside this checkout
    os.environ["TPU_LOG_DIR"] = "disabled"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    return harness.main(ROOT, argv, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
