"""The training CLI's code path (``repro.launch.train.run``) in every mode,
and its compile-cache rule.

``--mode mesh`` builds its mesh from the devices present; on the one CPU
device of a test process that is a one-worker mesh, which must train the
same trajectory as a one-worker sim run.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import train as T

ARGS = ["--arch", "bert-base", "--smoke", "--optimizer", "zero_one_adam",
        "--steps", "6", "--batch", "4", "--seq", "16", "--lr", "1e-3",
        "--lr-warmup", "2", "--sync-warmup", "2", "--double-every", "2",
        "--kappa", "2", "--log-every", "100"]


@pytest.mark.parametrize("pallas", [False, True])
def test_mesh_mode_trains_like_sim(pallas):
    extra = ["--use-pallas"] if pallas else []
    mesh = T.run(T.parse_args(ARGS + ["--mode", "mesh"] + extra))
    sim = T.run(T.parse_args(ARGS + ["--mode", "sim", "--workers", "1"]
                             + extra))
    assert mesh["trainer"].mesh is not None
    assert np.isfinite(mesh["loss"]).all()
    # the schedule holds a variance round, a 1-bit sync and a local step
    kinds = {(s, v) for s, v in zip(mesh["synced"], mesh["var_round"])}
    assert {(True, True), (True, False), (False, False)} <= kinds, kinds
    assert mesh["synced"] == sim["synced"]
    assert mesh["var_round"] == sim["var_round"]
    np.testing.assert_allclose(mesh["loss"], sim["loss"], rtol=1e-5)


def test_single_mode_starts_at_uniform_loss():
    rec = T.run(T.parse_args(ARGS + ["--mode", "single", "--steps", "1"]))
    # random weights predict near-uniformly over the smoke vocab
    assert abs(rec["loss"][0] - np.log(512)) < 1.0, rec["loss"]


def test_on_step_sees_each_step():
    seen = []
    rec = T.run(T.parse_args(ARGS + ["--mode", "sim", "--workers", "2",
                                     "--steps", "3"]),
                on_step=lambda step, params, state: seen.append(
                    (step, int(np.asarray(state.step).reshape(-1)[0]),
                     [np.asarray(x) for x in jax.tree.leaves(params)])))
    assert [s for s, _, _ in seen] == [0, 1, 2]
    assert [t for _, t, _ in seen] == [1, 2, 3]
    for a, b in zip(seen[-1][2], jax.tree.leaves(rec["params"])):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert any(not np.array_equal(a, b)
               for a, b in zip(seen[0][2], seen[-1][2]))


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/cache-dir"])
def test_compile_cache_dir(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = ("import jax; from repro.launch.cache import "
            "enable_compile_cache as e; "
            "print(e()); print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    used, configured = r.stdout.split()[-2:]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = env_dir or os.path.join(root, ".jax_cache")
    assert used == configured == want
