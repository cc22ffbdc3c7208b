"""The check that decides ``correct``, driven through ``run.run_cell`` at a
size a CPU test holds: a sound run passes, and each fault of the timed
path that a training cell can have comes out not correct. Also pins the
reference's copies of the traffic and the weights to the program's."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks.chip import reference as R
from benchmarks.chip import run as RUN

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2 ** 31 + 29
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv=4, head_dim=16,
            d_ff=128, vocab=500, max_seq=64, remat=False)


def tiny_cell(workload: str, chips: int = 1) -> dict:
    """The workload's own files, its widths and lengths cut to a CPU's."""
    cell = RUN.load_cell(workload)
    cell["config"]["model"].update(TINY)
    cell["mix"].update(seq_len=16, tokens_per_chip=64)
    cell["workload"]["chips"] = chips
    return cell


def _run(cell, wrap_step=None):
    return RUN.run_cell(cell, SEED, 0.5, False, require_tpu=False,
                        wrap_step=wrap_step, log=lambda s: None)


@pytest.mark.parametrize("mlm", [True, False])
def test_reference_traffic_is_the_programs(mlm):
    from repro.data import DataConfig, SyntheticLM
    seed = SEED % 2 ** 32
    data = SyntheticLM(DataConfig(vocab=300, seq_len=12, global_batch=5,
                                  seed=seed, kind="mlm" if mlm else "lm"))
    table = R.bigram_table(300, seed)
    for step in (0, 7):
        ours = R.batch(table, seed, step, 5, 12, mlm)
        theirs = data.batch(step)
        assert set(ours) == set(theirs)
        for k in ours:
            np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]))


def test_reference_weights_are_the_programs():
    cell = tiny_cell("bert-large.sync1")
    c = RUN.Cell(cell, SEED)
    ours = jax.jit(lambda k: R.init_params(cell["config"]["model"], k))(
        c.key)
    assert sorted(ours) == sorted(c.paths)
    for p, x in zip(c.paths, jax.tree.leaves(c.params)):
        np.testing.assert_allclose(np.asarray(x[0]), np.asarray(ours[p]),
                                   rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("workload", ["bert-large.sync1", "gpt2.sync16"])
def test_sound_run_is_correct(workload):
    res = _run(tiny_cell(workload))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss", "grad_norm", "change_norm"}
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("workload", ["bert-large.sync1", "gpt2.sync16"])
def test_control_reads_far_above_the_program(workload):
    """The control: the reference in bfloat16 (model, parameters and
    optimizer state) put in the program's place. At the cells' own size on
    the chip its worst leaf's change reads 2.06 to 3.03 against the
    program's 3.5e-4 to 2.9e-3 and fails the cell's limit (PERF.md); at a
    CPU's size the gap shrinks with the model, so here it is held to read
    far above the program's, on the same numbers."""
    cell = tiny_cell(workload)
    m, opt, mix = (cell["config"]["model"], cell["config"]["optimizer"],
                   cell["mix"])
    c = RUN.Cell(cell, SEED)
    c.setup()
    prog = c.readings
    c.free()
    ref = R.run(m, opt, mix, SEED, 1)
    ours = R.compare(prog, ref)
    ctl = R.compare(R.run(m, opt, mix, SEED, 1, precision="bfloat16"), ref)
    for k in ("loss", "change_norm"):
        assert ctl[k]["value"] > 30 * ours[k]["value"], (k, ctl, ours)


def test_window_compiles_as_the_cli_does():
    """Set-up, window and reference persist every compile, so that the
    input pipeline's per-step scan is always loaded from the cache (the
    state a long ``repro.launch.train`` run reaches at its first compile
    over JAX's one-second threshold), and the defaults come back after."""
    key = "jax_persistent_cache_min_compile_time_secs"
    default = getattr(jax.config, key)
    seen = []

    def watch(cell, step):
        def f(params, state, batch):
            seen.append(getattr(jax.config, key))
            return step(params, state, batch)
        return f

    seen_ref = []
    run_ref = R.run

    def ref(*a, **k):
        seen_ref.append(getattr(jax.config, key))
        return run_ref(*a, **k)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(R, "run", ref)
        res = _run(tiny_cell("bert-large.sync1"), wrap_step=watch)
    assert res["correct"], res["checks"]
    assert len(seen) > 6 and set(seen) == {0} and default > 0
    assert seen_ref == [0]
    assert getattr(jax.config, key) == default


def test_run_wants_exactly_the_cells_chips(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    with pytest.raises(RUN.NoChip, match="asks for 1 chips"):
        RUN.check_devices(1)
    RUN.check_devices(4)


def _frozen(cell, step):
    """Fault: the step returns its parameters and state unchanged."""
    cell.tr.tc = dataclasses.replace(cell.tr.tc, donate=False)
    keep, _ = cell.tr.mesh_step_fn()

    def f(params, state, batch):
        _, _, met = keep(params, state, batch)
        return params, state, met
    return f


def _half_batch(cell, step):
    """Fault: half of the batch left out, the mean taken over the rest."""
    def f(params, state, batch):
        return step(params, state,
                    {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return f


@pytest.mark.parametrize("workload", ["bert-large.sync1", "gpt2.sync16"])
@pytest.mark.parametrize("fault", [_frozen, _half_batch])
def test_fault_is_not_correct(workload, fault):
    res = _run(tiny_cell(workload), wrap_step=fault)
    assert not res["correct"], res["checks"]


NO_EXCHANGE = r"""
import json, sys
import jax.numpy as jnp
sys.path[:0] = [{root!r}, {src!r}]
from repro.core import comm as C
# fault: the exchange between chips left out; every worker keeps its own
# payload and "gathers" copies of its own result
C.Comm.all_to_all = lambda self, x, split_axis=0, concat_axis=0: x
C.Comm.all_gather = lambda self, x, axis=0, tiled=True: jnp.concatenate(
    [x] * 4, axis=axis)
from benchmarks.chip import test_chip_correct as T, run as RUN
res = RUN.run_cell(T.tiny_cell("bert-large.sync1", 4), T.SEED, 0.5,
                   False, require_tpu=False, log=lambda s: None)
print(json.dumps(res["checks"]))
print(json.dumps(res["correct"]))
"""

SOUND_DP4 = NO_EXCHANGE.split("# fault")[0] + r"""
from benchmarks.chip import test_chip_correct as T, run as RUN
res = RUN.run_cell(T.tiny_cell("bert-large.sync1", 4), T.SEED, 0.5,
                   False, require_tpu=False, log=lambda s: None)
print(json.dumps(res["checks"]))
print(json.dumps(res["correct"]))
"""


@pytest.mark.parametrize("code,want", [(SOUND_DP4, True),
                                       (NO_EXCHANGE, False)],
                         ids=["sound", "no_exchange"])
def test_four_workers(code, want):
    """The harness over a data=4 mesh (4 host devices), with bert-large's
    mix on each worker: a sound run passes the check and one whose
    exchange between the workers is left out does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               "--xla_cpu_enable_concurrency_optimized_scheduler=false")
    out = subprocess.run(
        [sys.executable, "-c", code.format(root=str(ROOT),
                                           src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) is want, lines[-2]
