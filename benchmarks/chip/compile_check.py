#!/usr/bin/env python3
"""Compile each cell's train step for a described TPU v5e and print what the
compiler says of its memory. Needs no chip: JAX stays on the CPU and the
TPU compiler compiles for the chips of a described ``v5e:2x2`` host (the
first one for a one-chip cell).

  JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py [WORKLOAD ...]

The figures are the compiler's (``compiled.memory_analysis()``), not chip
measurements. A step that does not fit the chip fails here as it would
there.
"""
from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.chip import run as RUN  # noqa: E402

GIB = 2 ** 30


def compile_cell(name: str, topo) -> dict:
    from repro.train import Trainer, TrainerConfig
    cell = RUN.load_cell(name)
    chips = cell["workload"]["chips"]
    m, mix = cell["config"]["model"], cell["mix"]
    mesh = jax.sharding.Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                             ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    tr = Trainer(RUN.model_config(m),
                 RUN.optimizer_config(cell["config"]["optimizer"], mix),
                 mesh=mesh, trainer_cfg=TrainerConfig(worker_axes=("data",)))
    step, sh = tr.mesh_step_fn()
    params, state = jax.eval_shape(tr.mesh_init, jax.random.PRNGKey(0))
    attach = lambda t, s: jax.tree.map(
        lambda a, b: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=b), t, s)
    rows = mix["tokens_per_chip"] // mix["seq_len"] * chips
    bsh = NamedSharding(mesh, P("data"))
    batch = {k: jax.ShapeDtypeStruct((rows, mix["seq_len"]), jnp.int32,
                                     sharding=bsh)
             for k in ("tokens", "labels")}
    if not m.get("causal", True):
        batch["loss_mask"] = jax.ShapeDtypeStruct(
            (rows, mix["seq_len"]), jnp.float32, sharding=bsh)
    compiled = step.lower(attach(params, sh["params"]),
                          attach(state, sh["state"]), batch).compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    return {"workload": name, "chips": chips, "rows": rows,
            "argument_gib": ma.argument_size_in_bytes / GIB,
            "output_gib": ma.output_size_in_bytes / GIB,
            "alias_gib": ma.alias_size_in_bytes / GIB,
            "temp_gib": ma.temp_size_in_bytes / GIB,
            "total_gib": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                          + ma.output_size_in_bytes
                          - ma.alias_size_in_bytes) / GIB,
            "all_to_all": hlo.count(" all-to-all("),
            "all_gather": hlo.count(" all-gather("),
            "all_reduce": hlo.count(" all-reduce(")}


def main(argv=None) -> int:
    from jax.experimental import topologies
    names = list(argv if argv is not None else sys.argv[1:])
    if not names:
        man = json.loads((ROOT / "BENCHMARK.json").read_text())
        names = [w["name"] for w in man["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    # a one-chip cell compiles for the first chip of the described host
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
