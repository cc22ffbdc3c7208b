#!/usr/bin/env python3
"""Cut a recorded trace down to a few window steps, as the tests keep one.

  python3 benchmarks/chip/cut_trace.py TRACE_DIR_OR_XPLANE OUT.pbtxt.gz \\
      FIRST_STEP N_STEPS

TRACE is a profiler trace (``jax.profiler.start_trace(DIR)``) of steps
that ``run.Cell.step`` took after ``Cell.setup()``, so that it holds the
benchmark's host spans as a ``--trace 1`` run's does. The output
is an XSpace in text form holding, from step FIRST_STEP of the window on
for N_STEPS steps, the device planes' "XLA Modules" and "XLA Ops" events
and the benchmark's own host spans, with event names and times only (no
stats: ``trace.py`` reads none), shifted to start at 1 us.
"""
from __future__ import annotations

import gzip
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from benchmarks.chip import trace as TR  # noqa: E402


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def cut(pd, first: int, n: int) -> str:
    spans = TR.host_spans(pd)
    starts = [s for name, s, _ in spans if name == "bench.batch"]
    ends = [e for name, _, e in spans if name == "bench.read"]
    w0, w1 = starts[first], ends[first + n - 1]
    planes, lid = [], 0
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        if not (dev or plane.name.startswith("/host:")):
            continue
        meta, lines = {}, []
        for line in plane.lines:
            if dev and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            evs = []
            for ev in line.events:
                s, d = ev.start_ns, ev.duration_ns
                if (not dev and ev.name not in TR.HOST_SPANS) \
                        or s + d <= w0 or s >= w1:
                    continue
                mid = meta.setdefault(ev.name, len(meta) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{round((s - w0 + 1000) * 1000)} duration_ps: "
                           f"{round(d * 1000)} }}")
            if evs:
                lid += 1
                lines.append(f"lines {{ id: {lid} name: {_q(line.name)} "
                             f"timestamp_ns: 0 {' '.join(evs)} }}")
        if lines:
            em = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                          f"name: {_q(name)} }} }}"
                          for name, i in meta.items())
            planes.append(f"planes {{ id: {len(planes) + 1} name: "
                          f"{_q(plane.name)} {' '.join(lines)} {em} }}")
    return "\n".join(planes) + "\n"


def main(argv=None) -> int:
    src, out, first, n = (argv if argv is not None else sys.argv[1:])
    path = TR.find_xplane(src) if os.path.isdir(src) else src
    with gzip.open(out, "wt") as f:
        f.write(cut(TR.load(path), int(first), int(n)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
