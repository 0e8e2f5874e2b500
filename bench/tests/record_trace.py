#!/usr/bin/env python3
"""Record a traced run's profile as the small JSON the trace tests read.

    python bench/tests/record_trace.py <raw trace dir> <out.json.gz>

The raw directory is what ``bench/run.py --trace 1 --keep-trace <dir>``
copies out of a traced run. Kept: every plane's ``XLA Modules`` and
``XLA Ops`` events and the harness's host spans, each as (name, start,
duration) with the name cut to 400 characters; nothing else.
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
            device = line.name in ("XLA Ops", "XLA Modules")
            evs = [[e.name[:400], e.start_ns, e.duration_ns]
                   for e in line.events
                   if device or (plane.name.startswith("/host")
                                 and e.name in keep_host)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f)
    print(out, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
