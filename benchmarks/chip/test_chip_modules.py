"""A configuration brings its own reference and counts modules.

- A new configuration is new files and new entries only: a copy of the
  benchmark with a fixture configuration whose reference and counts
  modules delegate to ``reference.py``/``flops.py`` runs through
  ``run.run_cell``, and the harness reaches the model only through them.
- The readings of leaves without a worker axis: ``deepseek-smoke`` (MoE
  and MLA) over four workers holds its experts split over the workers
  (``dp=False``), and ``Cell`` reads each of those leaves whole.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

FIXTURE_REFERENCE = '''"""The fixture configuration's reference: ``reference.py``'s, with each
call recorded."""
from benchmarks.chip import reference as R
from benchmarks.chip.fixture_counts import CALLS

init_params_, run_, compare_ = R.init_params, R.run, R.compare


def init_params(m, key):
    CALLS.append("reference.init_params")
    return init_params_(m, key)


def run(m, opt, mix, seed, n_workers, devices=None):
    CALLS.append("reference.run")
    return run_(m, opt, mix, seed, n_workers, devices=devices)


def compare(prog, ref):
    CALLS.append("reference.compare")
    return compare_(prog, ref)
'''

FIXTURE_COUNTS = '''"""The fixture configuration's counts: ``flops.py``'s, with each call
recorded."""
from benchmarks.chip import flops as F

CALLS = []
param_count_ = F.param_count
flops_ = F.model_flops_per_token
rate_ = F.optimizer_bytes_per_param


def param_count(m):
    CALLS.append("counts.param_count")
    return param_count_(m)


def model_flops_per_token(m, seq_len):
    CALLS.append("counts.model_flops_per_token")
    return flops_(m, seq_len)


def optimizer_bytes(m, n_workers, kind):
    CALLS.append("counts.optimizer_bytes")
    return param_count_(m) * rate_(kind, n_workers)
'''

# Runs with the copy as the benchmark's checkout: ``benchmarks.chip`` is
# imported from it. The shared modules' functions are replaced by ones that
# record who called them; the fixture modules hold the originals.
RUN_FIXTURE = r"""
import inspect, json, pathlib, sys
root = pathlib.Path({root!r})
sys.path[:0] = [str(root), {src!r}]
import jax
from benchmarks.chip import flops as F, reference as R
from benchmarks.chip import fixture_counts, fixture_reference
from benchmarks.chip import run as RUN, trace as TR
assert pathlib.Path(RUN.__file__).parent == root / "benchmarks" / "chip"
direct = []

def tripwire(mod, name):
    f = getattr(mod, name)
    def g(*a, **k):
        caller = pathlib.Path(inspect.stack()[1].filename).name
        if caller not in ("reference.py", "flops.py", "fixture_counts.py",
                          "fixture_reference.py"):
            direct.append(f"{{mod.__name__}}.{{name}} from {{caller}}")
        return f(*a, **k)
    setattr(mod, name, g)

for name in ("init_params", "run", "compare"):
    tripwire(R, name)
for name in ("param_count", "model_flops_per_token", "optimizer_bytes"):
    tripwire(F, name)

# a CPU trace has no device plane: stand in for its summary, so that the
# counts' readers run
TR.summarize = lambda path, n, chips, hlo: {{
    "busy_s": 1.0, "window_s": 2.0, "step_module": "jit_body",
    "step_runs": float(n), "exchange_exposed_s": 0.0,
    "per_step_s": {{"fwd_bwd": 1e-3, "optimizer": 1e-3, "collective": 0.0,
                   "other": 0.0}},
    "breakdown": {{"device_ops": [], "idle_gaps": []}}}}
cell = RUN.load_cell("fixture.sync1", root=root)
cell["per_layer"] = [m for m in cell["per_layer"]
                     if m["name"] in ("mfu", "optimizer_roofline")]
kind = jax.devices()[0].device_kind
cell["peaks"]["devices"][kind] = cell["peaks"]["devices"]["TPU v5 lite"]
res = RUN.run_cell(cell, 2 ** 31 + 71, 0.5, True, require_tpu=False,
                   log=lambda s: None)
print(json.dumps({{"modules": [cell["reference"].__name__,
                               cell["counts"].__name__],
                  "calls": sorted(set(fixture_counts.CALLS)),
                  "direct": direct, "correct": res["correct"],
                  "checks": res["checks"],
                  "metrics": sorted(res["metrics"])}}))
"""


def _subprocess(code, devices=1, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices} "
               "--xla_cpu_enable_concurrency_optimized_scheduler=false")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=cwd)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _tree(root: pathlib.Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_config_needs_new_files_only(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    copied = _tree(tmp_path)
    man = json.loads(copied["BENCHMARK.json"])

    # the new configuration, as a model_config change would add it
    body = json.loads((chip / "configs" / "bert-large.json").read_text())
    body.update(name="fixture", reference="fixture_reference",
                counts="fixture_counts")
    body["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv=4,
                         head_dim=16, d_ff=128, vocab=500, max_seq=64,
                         remat=False)
    (chip / "configs" / "fixture.json").write_text(json.dumps(body))
    mix = json.loads((chip / "mixes" / "sync1-seq128.json").read_text())
    mix.update(seq_len=16, tokens_per_chip=64)
    (chip / "mixes" / "fixture-mix.json").write_text(json.dumps(mix))
    shutil.copy(chip / "limits" / "bert-large.sync1.json",
                chip / "limits" / "fixture.sync1.json")
    (chip / "fixture_reference.py").write_text(FIXTURE_REFERENCE)
    (chip / "fixture_counts.py").write_text(FIXTURE_COUNTS)
    new = dict(man)
    new["configs"] = man["configs"] + [dict(
        man["configs"][0], name="fixture",
        file="benchmarks/chip/configs/fixture.json")]
    new["workloads"] = man["workloads"] + [dict(
        man["workloads"][0], name="fixture.sync1", config="fixture",
        traffic="fixture-mix")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    got = _subprocess(RUN_FIXTURE.format(root=str(tmp_path),
                                         src=str(ROOT / "src")),
                      cwd=tmp_path)
    assert got["modules"] == ["benchmarks.chip.fixture_reference",
                              "benchmarks.chip.fixture_counts"]
    assert got["direct"] == [], got["direct"]
    assert got["calls"] == ["counts.model_flops_per_token",
                            "counts.optimizer_bytes",
                            "reference.compare", "reference.init_params",
                            "reference.run"]
    assert got["correct"], got["checks"]
    assert {"mfu", "optimizer_roofline"} <= set(got["metrics"])

    # every file that was copied is as it was, apart from the manifest,
    # which only gained entries
    after = _tree(tmp_path)
    for path, data in copied.items():
        if path != "BENCHMARK.json":
            assert after[path] == data, path
    now = json.loads(after["BENCHMARK.json"])
    for key, entries in man.items():
        if isinstance(entries, list) and key != "paths":
            assert now[key][:len(entries)] == entries, key
        else:
            assert now[key] == entries, key


MIXED_MASK = r"""
import dataclasses, json, sys, types
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp, numpy as np
from benchmarks.chip import run as RUN
from repro.configs.deepseek_v2_236b import SMOKE
from repro.models import transformer as T
from repro.models.layers import init_params

cell = RUN.load_cell("bert-large.sync1")
m = {{f.name: getattr(SMOKE, f.name) for f in dataclasses.fields(SMOKE)}}
m.update(param_dtype="float32", compute_dtype="float32")
cell["config"]["model"] = m
# a float32 wire, so that the exchanged first gradient is the mean itself
cell["config"]["optimizer"]["comm_dtype"] = "float32"
cell["mix"].update(seq_len=16, tokens_per_chip=64, setup_steps=3)
cell["workload"]["chips"] = 4

x0 = {{}}
cell["reference"] = types.SimpleNamespace(init_params=lambda m, key: x0)
c = RUN.Cell(cell, 2 ** 31 + 53)
# the trainer's own initial weights, drawn as mesh_init draws them
w0 = init_params(c.tr.template, c.key, dtype=jnp.float32)
x0.update(zip(c.paths, jax.tree.leaves(w0)))
batch = {{k: np.asarray(v) for k, v in c.data.batch(0).items()}}
c.setup()

# the first gradient, worker by worker with every expert held locally,
# then the mean over the workers
n, rows = 4, c.batch_rows // 4
cfg = RUN.model_config(m)
grad = jax.jit(jax.grad(lambda p, b: T.lm_loss(p, cfg, b)[0]))
gs = [jax.tree.leaves(grad(w0, {{k: v[i * rows:(i + 1) * rows]
                                for k, v in batch.items()}}))
      for i in range(n)]
want_g = {{p: float(np.linalg.norm(np.mean([np.asarray(g[j]) for g in gs],
                                          axis=0)))
          for j, p in enumerate(c.paths)}}
want_c, old_c = {{}}, {{}}
for p, x, dp in zip(c.paths, jax.tree.leaves(c.params), c.dp):
    x = np.asarray(x)
    if dp:
        assert x.shape[0] == n and all(np.array_equal(x[0], r) for r in x)
    mine = x[0] if dp else x
    want_c[p] = float(np.linalg.norm(mine - np.asarray(x0[p])))
    old_c[p] = float(np.linalg.norm(x[0] - np.asarray(x0[p])[0]))
print(json.dumps({{"dp": dict(zip(c.paths, c.dp)), "want_g": want_g,
                  "want_c": want_c, "old_c": old_c,
                  "got_g": c.readings["grad_norm"],
                  "got_c": c.readings["change_norm"]}}))
"""


def test_readings_of_leaves_without_a_worker_axis():
    got = _subprocess(MIXED_MASK.format(root=str(ROOT),
                                        src=str(ROOT / "src")), devices=4)
    experts = sorted(p for p, dp in got["dp"].items() if not dp)
    assert experts and len(experts) < len(got["dp"])
    for p in got["dp"]:
        w, r = got["want_g"][p], got["got_g"][p]
        assert abs(r - w) <= 1e-4 * w + 1e-9, (p, r, w)
        w, r = got["want_c"][p], got["got_c"][p]
        assert abs(r - w) <= 1e-5 * w + 1e-9, (p, r, w)
    # reading an expert leaf's first row, as every DP leaf is read, would
    # give another number
    for p in experts:
        assert abs(got["old_c"][p] - got["want_c"][p]) > \
            0.1 * got["want_c"][p], p
