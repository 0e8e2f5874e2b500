#!/usr/bin/env python3
"""Record a traced run's profile as the small JSON the span tests read.

    python bench/tests/record_spans.py <raw trace dir> <out.json.gz>

The raw directory is what ``bench/run.py --trace 1 --keep-trace <dir>``
copies out of a traced run. Kept, each event as (name, start, duration):
every device plane's ``XLA Modules`` events, its ``XLA Ops`` line reduced
to the merged intervals in which some operation ran (one event
``busy`` each, so busy and idle time read as from the whole line), and
the host spans: the harness's and the program's ``quant.*`` ones, named
by what precedes any ``#`` metadata. Nothing else.
"""
import glob
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(src: str, out: str) -> int:
    sys.path.insert(0, ROOT)
    from jax.profiler import ProfileData
    from bench import trace as tr
    keep_host = set(tr.HOST_SPANS) | {tr.WINDOW_SPAN}
    pd = ProfileData.from_file(max(glob.glob(
        os.path.join(src, "**", "*.xplane.pb"), recursive=True)))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
            elif line.name == "XLA Ops":
                evs = [["busy", a, b - a] for a, b in tr.union(
                    [(e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events])]
            elif plane.name.startswith("/host"):
                evs = [[name, e.start_ns, e.duration_ns]
                       for e in line.events
                       for name in [e.name.split("#")[0]]
                       if name in keep_host or name.startswith("quant.")]
            else:
                evs = []
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f)
    print(out, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
