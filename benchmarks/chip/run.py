#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

A run builds the trainer users run (``repro.train.Trainer`` on
``repro.launch.mesh.make_local_mesh``: data = the chips, model = 1), makes
weights and optimizer state on the chips from the seed
(``Trainer.mesh_init``), and steps it as ``repro.launch.train.run`` does:
``SyntheticLM.batch``, the step, ``block_until_ready``, then reading the
loss and the step kind. Set-up runs the mix's first steps, whose readings
the correctness check compares with ``reference.py``; the window then
measures for ``--seconds``. With ``--trace 1`` the profiler records the
mix's ``trace_steps`` window steps and the per-layer metrics come from
that trace. The last line of standard output is the result as JSON; the
numbers compared, each with its limit, are the last lines of standard
error and the result's last key.

The compile cache is the checkout's own ``.jax_cache/`` (the directory
``repro.launch.cache`` uses when ``JAX_COMPILATION_CACHE_DIR`` is unset),
and the whole run persists every program it compiles, however quick.
``SyntheticLM.batch`` traces its scan anew on every call; under JAX's
default threshold of one second that scan is written to the cache only
when one of its compiles happens to take longer, after which every step
loads it from disk. A long run of ``repro.launch.train`` reaches that
state at its first slow compile; the benchmark starts in it, so that
whether the window compiles the scan or loads it does not depend on the
host's load in an earlier run.

The run exits 3 and prints no result unless JAX's backend is a TPU with
exactly the chips the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    # run as a script: import this directory as benchmarks.chip, not as
    # top-level modules (``trace`` would shadow the standard library's)
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import flops as F  # noqa: E402
from benchmarks.chip import reference as R  # noqa: E402


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# the manifest and the files it names
# --------------------------------------------------------------------------

def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The workload's entry with its configuration, mix, per-layer metrics
    and limits, each read from the file its name gives."""
    man = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in man["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(w['name'] for w in man['workloads'])}")
    cfg = {c["name"]: c for c in man["configs"]}[wl["config"]]
    here = root / "benchmarks" / "chip"
    metrics = [m for m in man["per_layer"]
               if workload in m.get("workloads", [workload])]
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    return {
        "workload": wl,
        "config": json.loads((root / cfg["file"]).read_text()),
        "mix": json.loads((here / "mixes" / f"{wl['traffic']}.json")
                          .read_text()),
        "end_to_end": e2e,
        "per_layer": metrics,
        "limits": json.loads((here / "limits" / f"{workload}.json")
                             .read_text()),
        "peaks": json.loads((here / "peaks.json").read_text()),
    }


def metric_reader(name: str):
    return importlib.import_module(f"benchmarks.chip.metrics.{name}")


# --------------------------------------------------------------------------
# the system under test, built from the files
# --------------------------------------------------------------------------

def model_config(m: dict):
    from repro.models.config import ModelConfig
    kw = dict(m)
    for k in ("param_dtype", "compute_dtype"):
        if k in kw:
            kw[k] = jnp.dtype(kw[k])
    return ModelConfig(**kw)


def _schedule(spec: dict):
    from repro.core import schedules as S
    kw = {k: v for k, v in spec.items() if k != "kind"}
    return getattr(S, spec["kind"])(**kw)


def optimizer_config(opt: dict, mix: dict):
    from repro.core import OptimizerConfig
    kw = dict(opt)
    for k in ("comm_dtype", "state_dtype"):
        if k in kw:
            kw[k] = jnp.dtype(kw[k])
    return OptimizerConfig(lr=_schedule(mix["lr"]),
                           sync_policy=_schedule(mix["sync_policy"]),
                           var_policy=_schedule(mix["var_policy"]), **kw)


def check_devices(chips: int):
    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX's backend is {jax.default_backend()!r}, not tpu")
    if jax.device_count() != chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{jax.device_count()}")


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class Cell:
    """One cell's trainer, state and traffic, from set-up to the check."""

    def __init__(self, cell: dict, seed: int, wrap_step=None):
        from repro.data import DataConfig, SyntheticLM
        from repro.train import Trainer, TrainerConfig
        from repro.launch.mesh import make_local_mesh, worker_axes
        self.cell, self.seed = cell, seed
        self.m = cell["config"]["model"]
        self.opt = cell["config"]["optimizer"]
        self.mix = cell["mix"]
        self.chips = cell["workload"]["chips"]
        self.devices = jax.devices()[:self.chips]
        self.seq = self.mix["seq_len"]
        self.batch_rows = self.mix["tokens_per_chip"] // self.seq * self.chips
        self.mesh = make_local_mesh()
        self.tr = Trainer(model_config(self.m),
                          optimizer_config(self.opt, self.mix),
                          mesh=self.mesh,
                          trainer_cfg=TrainerConfig(
                              worker_axes=worker_axes(self.mesh)))
        self.key = jax.random.PRNGKey(seed)
        self.params, self.state = jax.jit(self.tr.mesh_init)(self.key)
        step, _ = self.tr.mesh_step_fn()
        self.step_fn = wrap_step(self, step) if wrap_step else step
        self.data = SyntheticLM(DataConfig(
            vocab=self.m["vocab"], seq_len=self.seq,
            global_batch=self.batch_rows, seed=seed % 2 ** 32,
            kind="lm" if self.m.get("causal", True) else "mlm"))
        self.paths = leaf_paths(self.params)
        self.t = 0
        self.readings = {"loss": []}

    # one step, as repro.launch.train.run takes it
    def step(self, spans=None):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = self.data.batch(self.t)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.state, met = self.step_fn(
                self.params, self.state, batch)
            jax.block_until_ready((self.params, self.state, met))
        with jax.profiler.TraceAnnotation("bench.read"):
            first = lambda x: np.asarray(x).reshape(-1)[0]
            synced = bool(first(met["synced"]))
            var_r = bool(first(met["var_round"]))
            loss = float(first(met["loss"]))
        t2 = time.perf_counter()
        if spans is not None:
            spans.append((t0, t1, t2))
        self.t += 1
        return loss, synced, var_r

    def grad_norms_from_state(self):
        """Per-leaf norm of the first gradient as the optimizer received it:
        after one variance round from v = 0, v = (1 - b2) gbar^2."""
        b2 = self.opt["beta2"]
        vs = self.state.slots["v"]
        sums = jax.jit(lambda v: [jnp.sum(x[0]) for x in v])(vs)
        return {p: math.sqrt(float(s) / (1 - b2))
                for p, s in zip(self.paths, sums)}

    def change_norms(self):
        """Per-leaf norm of worker 0's parameters minus the seed's."""
        m = self.m

        def f(params, key):
            x0 = R.init_params(m, key)
            return [jnp.sqrt(jnp.sum(jnp.square(x[0] - x0[p])))
                    for p, x in zip(self.paths, jax.tree.leaves(params))]

        out = jax.jit(f)(self.params, self.key)
        return {p: float(v) for p, v in zip(self.paths, out)}

    def setup(self):
        """The mix's first steps through the window's own call; the first
        three give the program's readings for the check."""
        kinds = []
        for i in range(self.mix["setup_steps"]):
            loss, synced, var_r = self.step()
            kinds.append(F.step_kind(synced, var_r))
            if i < 3:
                self.readings["loss"].append(loss)
            if i == 0:
                self.readings["grad_norm"] = self.grad_norms_from_state()
            if i == 2:
                self.readings["change_norm"] = self.change_norms()
        return kinds

    def free(self):
        for x in jax.tree.leaves((self.params, self.state)):
            x.delete()
        self.params = self.state = self.step_fn = None
        gc.collect()


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def _quantile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


@contextlib.contextmanager
def cache_small_programs():
    """Persist every program compiled inside, however quick its compile."""
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    for k in keys:
        jax.config.update(k, 0)
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, wrap_step=None, log=print):
    """One run of one cell. Returns the result object of the last line."""
    chips = cell["workload"]["chips"]
    if require_tpu:
        check_devices(chips)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    with cache_small_programs():
        return _run_cell(cell, seed, seconds, trace, wrap_step, log)


def _run_cell(cell, seed, seconds, trace, wrap_step, log):
    chips = cell["workload"]["chips"]

    c = Cell(cell, seed, wrap_step)
    setup_kinds = c.setup()
    log(f"setup steps: {len(setup_kinds)} {setup_kinds}, "
        f"{time.perf_counter() - T_START:.1f} s after start")

    spans, kinds, losses = [], [], []
    tracer = None
    if trace:
        tracer = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tracer)
        n_max = c.mix["trace_steps"]
    else:
        n_max = None
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    while True:
        loss, synced, var_r = c.step(spans)
        losses.append(loss)
        kinds.append(F.step_kind(synced, var_r))
        done = spans[-1][2] - t_window >= seconds
        if n_max is not None and len(kinds) >= n_max:
            done = True
        if done:
            break
    if tracer:
        jax.profiler.stop_trace()
    window_s = spans[-1][2] - t_window
    counts = {k: kinds.count(k) for k in sorted(set(kinds))}
    ends = [t_window] + [s[2] for s in spans]
    tokens = len(kinds) * c.batch_rows * c.seq
    h = len(kinds) // 2
    halves = [n * c.batch_rows * c.seq / (t1 - t0) for n, t0, t1 in
              ((h, ends[0], ends[h]), (len(kinds) - h, ends[h], ends[-1]))]
    log(f"window steps: {len(kinds)} {counts}; tokens/s in each half of "
        f"the window: {halves[0]:.1f} {halves[1]:.1f}")
    intervals = np.diff(ends)
    devices = c.devices
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": len(kinds),
              "failed": int(sum(not math.isfinite(x) for x in losses))}

    metrics = {}
    breakdown = None
    if not trace:
        vals = {"tokens_per_s": tokens / window_s,
                "step_ms_p90": 1e3 * _quantile(intervals, 90),
                "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        from benchmarks.chip import trace as TR
        # the optimized step program: its module name and each op's JAX
        # name stack, which the trace's op events do not carry
        compiled = c.step_fn.lower(c.params, c.state,
                                   c.data.batch(c.t)).compile()
        hlo = compiled.as_text()
        log(f"step program memory (the chip's compiler): "
            f"{compiled.memory_analysis()}")
        log(f"memory_stats: {devices[0].memory_stats()}")
        summ = TR.summarize(TR.find_xplane(tracer), len(kinds), chips,
                            hlo=hlo)
        log(f"trace: {summ['step_runs']:g} executions of "
            f"{summ['step_module']} in the window, per step (s) "
            f"{summ['per_step_s']}")
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        breakdown = summ["breakdown"]
        ctx = {"trace": summ, "spans": spans, "kinds": kinds,
               "window_s": window_s, "tokens": tokens, "chips": chips,
               "model": c.m, "mix": c.mix, "peaks": cell["peaks"],
               "device_kind": dev.device_kind, "memory_peak_bytes": peak,
               "n_params": F.param_count(c.m)}
        for m in cell["per_layer"]:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import shutil
        shutil.rmtree(tracer, ignore_errors=True)
    readings = c.readings
    c.free()
    del c
    gc.collect()

    # the check: the plain reference over the same first steps, after the
    # program's state is gone and the peak has been read
    t_ref = time.perf_counter()
    ref = R.run(cell["config"]["model"], cell["config"]["optimizer"],
                cell["mix"], seed, chips, devices=devices)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    gaps = R.compare(readings, ref)
    limits = cell["limits"]
    checks = {}
    for name, g in gaps.items():
        checks[name] = {"value": g["value"], "limit": limits[name],
                        "at": g["at"]}
    correct = (result["failed"] == 0
               and all(v["value"] <= v["limit"] for v in checks.values()))
    result["correct"] = bool(correct)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    err = lambda s: print(s, file=sys.stderr, flush=True)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       log=err)
    except NoChip as e:
        err(f"FAIL: {e}")
        return 3
    for name, c in res["checks"].items():
        err(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}, "
            f"worst at {c['at']})")
    err(f"correct: {res['correct']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
