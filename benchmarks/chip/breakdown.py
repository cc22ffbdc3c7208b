#!/usr/bin/env python3
"""The optimizer's device time by the program's scopes, and the host's
compiles, over a traced window of one cell.

  python3 benchmarks/chip/breakdown.py --workload <name> --seed <n> \\
      [--steps N] [--cut OUT_PREFIX FIRST N_CUT]

Builds the cell and takes its set-up steps as ``run.py`` does, then
traces N window steps (the mix's ``trace_steps`` by default) between two
``telemetry.clock_anchor()``s and prints one JSON line: the per-step
classes of ``trace.summarize``, ``scopes.optimizer_scopes``, the
program's ``CompileCounters`` over the window, the anchors' offsets and
``scopes.idle_in_compile_share``, with the optimizer-class ops that no
scope claims (``scopes.unscoped_ops``), by opcode and longest first. It makes no correctness check. With
``--cut`` it also writes ``OUT_PREFIX.trace.pbtxt.gz`` (window steps
FIRST to FIRST + N_CUT - 1, cut by ``cut_trace.py``) and
``OUT_PREFIX.step_hlo.txt.gz``, as the tests keep a recorded trace.

It needs a program with ``repro.telemetry``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from benchmarks.chip import cut_trace  # noqa: E402
from benchmarks.chip import run as RUN  # noqa: E402
from benchmarks.chip import scopes as SC  # noqa: E402
from benchmarks.chip import trace as TR  # noqa: E402


def breakdown(cell: dict, seed: int, steps=None, cut=None) -> dict:
    from repro import telemetry
    c = RUN.Cell(cell, seed)
    c.setup()
    counters = telemetry.CompileCounters.installed()
    n = steps or c.mix["trace_steps"]
    chips = cell["workload"]["chips"]
    tracer = tempfile.mkdtemp(prefix="breakdown_trace_")
    spans = []
    jax.profiler.start_trace(tracer)
    anchors = [telemetry.clock_anchor()]
    snap = counters.snapshot()
    for _ in range(n):
        c.step(spans)
    done = counters.since(snap)
    anchors.append(telemetry.clock_anchor())
    jax.profiler.stop_trace()
    off = time.time() - time.perf_counter()
    window = counters.window(spans[0][0] + off, spans[-1][2] + off)
    hlo = c.step_fn.lower(c.params, c.state,
                          c.data.batch(c.t)).compile().as_text()
    path = TR.find_xplane(tracer)
    summ = TR.summarize(path, n, chips, hlo=hlo)
    comp = [(s, e) for k, _, s, e in list(counters.spans)
            if k in ("compile", "lower")]
    loose = SC.unscoped_ops(path, n, chips, hlo)
    by_opcode = {}
    for op, t in loose.values():
        by_opcode[op] = by_opcode.get(op, 0.0) + t
    out = {
        "workload": cell["workload"]["name"], "seed": seed, "steps": n,
        "per_step_s": summ["per_step_s"],
        "optimizer_scopes_s": SC.optimizer_scopes(path, n, chips, hlo),
        "window_s": summ["window_s"], "busy_s": summ["busy_s"],
        "compiles_per_step": window["compiles"] / n,
        "host_compile_ms": 1e3 * window["busy_s"] / n,
        "counters": done,
        "anchor_offsets_ns": SC.anchor_offsets_ns(TR.load(path), anchors),
        "idle_in_compile_share": SC.idle_in_compile_share(path, comp,
                                                          anchors),
        "input_ms": 1e3 * sum(t1 - t0 for t0, t1, _ in spans) / n,
        "unscoped_by_opcode_s": by_opcode,
        "unscoped_ops_s": sorted(([i, op, t] for i, (op, t) in loose.items()),
                                 key=lambda r: -r[2])[:15],
    }
    if cut:
        prefix, first, n_cut = cut
        with gzip.open(f"{prefix}.trace.pbtxt.gz", "wt") as f:
            f.write(cut_trace.cut(TR.load(path), int(first), int(n_cut)))
        with gzip.open(f"{prefix}.step_hlo.txt.gz", "wt") as f:
            f.write(hlo)
    shutil.rmtree(tracer, ignore_errors=True)
    c.free()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--cut", nargs=3, default=None,
                    metavar=("OUT_PREFIX", "FIRST", "N_CUT"))
    args = ap.parse_args(argv)
    cell = RUN.load_cell(args.workload)
    try:
        RUN.check_devices(cell["workload"]["chips"])
    except RUN.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".jax_cache"))
    with RUN.cache_small_programs():
        res = breakdown(cell, args.seed, args.steps, args.cut)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
