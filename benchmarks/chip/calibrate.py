#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

  python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1 2 3 \\
      [--variants program control half_batch no_exchange]

For each seed, in this one process: the program's readings from the
cell's set-up steps (the window's own call and feed, at the cell's size),
then the plain reference (float32, matmuls at ``highest``), and each
variant put in the program's place: ``control`` (the reference with the
model, parameters and optimizer state in bfloat16), ``half_batch`` (each
worker's loss from half its rows) and ``no_exchange`` (nothing crosses
between the workers). Each line is one variant's numbers against the
reference, as ``run.py`` compares them.
Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from benchmarks.chip import reference as R  # noqa: E402
from benchmarks.chip import run as RUN  # noqa: E402


def program_readings(cell: dict, seed: int) -> dict:
    c = RUN.Cell(cell, seed)
    c.setup()
    out = c.readings
    c.free()
    gc.collect()
    return out


def readings(cell: dict, seed: int, variants) -> tuple:
    """{variant: numbers}, and the raw readings."""
    chips = cell["workload"]["chips"]
    m, opt, mix = (cell["config"]["model"], cell["config"]["optimizer"],
                   cell["mix"])
    devs = jax.devices()[:chips]
    out = {}
    if "program" in variants:
        out["program"] = program_readings(cell, seed)
    for v in variants:
        if v == "program":
            continue
        kw = ({"precision": "bfloat16"} if v == "control"
              else {"fault": v})
        out[v] = R.run(m, opt, mix, seed, chips, devices=devs, **kw)
    out["ref_float32"] = ref = R.run(m, opt, mix, seed, chips, devices=devs)
    return {v: R.compare(out[v], ref) for v in variants}, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["program", "control", "half_batch"])
    args = ap.parse_args(argv)
    cell = RUN.load_cell(args.workload)
    RUN.check_devices(cell["workload"]["chips"])
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".jax_cache"))
    for seed in args.seeds:
        gaps, raw = readings(cell, seed, args.variants)
        for v, g in gaps.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": v,
                              **{k: x["value"] for k, x in g.items()},
                              "at": {k: x["at"] for k, x in g.items()}}),
                  flush=True)
        print(json.dumps({"seed": seed, "raw": raw}), file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
