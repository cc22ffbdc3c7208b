"""Cross-regime parity: a full ZeroOneAdam trainer step executed under the
mesh regime (partial-manual ``shard_map`` over the worker axes of a
multi-device debug mesh) must match the sim regime (single-device vmap) to
<= 1e-6 per leaf, for flat and hierarchical topologies, with and without
the Pallas kernels and the fused buckets.

This is the end-to-end guarantee behind every sim-mode convergence result:
whatever the tests prove under vmap is what the partitioned multi-device
program computes. Runs in a subprocess so the forced host device count
never leaks into other tests (same pattern as test_dryrun_small).

The mesh of the main combos is data-parallel only (``model=1``), the
layout ``launch/train.py --mode mesh`` builds: a size-1 model axis plans
the same state and wire layouts as sim, so every combo compares leaf for
leaf. Expert-parallel leaves are stacked per worker in sim and sharded on
their expert axis in mesh; the harness converts between the two.

A model axis of size > 1 plans tensor-parallel-local layouts: each model
shard flattens and chunks its own slice, where sim chunks the whole leaf.
The 1-bit exchange then compresses different chunks with different scales,
so its result differs from sim's by design. ``test_tp_mesh_matches_sim_
without_sync`` pins everything else of the TP mesh step against sim: the
sharded forward/backward, the local half-steps on TP-local views (jnp and
Pallas) and the full-precision variance exchange, over steps whose
momentum sync never fires. The compressed exchange under TP has no
numeric reference yet.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    # XLA:CPU gives every collective of the program the same channel id,
    # and its concurrency-optimized schedule lets two exchange units'
    # collectives run at once, which then join each other's rendezvous
    # (aborts under load); the plain schedule keeps them in order
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               "--xla_cpu_enable_concurrency_optimized_"
                               "scheduler=false")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get
    from repro.core import Hierarchy, OptimizerConfig, schedules as S
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_debug_mesh
    from repro.train import Trainer, TrainerConfig

    def opt_cfg(h, pallas, name, bucket_mb=None):
        return OptimizerConfig(
            name=name,
            lr=S.LinearWarmupExpDecay(peak_lr=2e-3, warmup_steps=10,
                                      decay=0.97, decay_period=20),
            var_policy=S.AdaptiveFreezePolicy(kappa=2),
            sync_policy=S.LrProportionalSyncPolicy(
                warmup_steps=10, double_every=20, max_interval=4),
            hierarchy=h, use_pallas=pallas, bucket_mb=bucket_mb,
            comm_dtype=jnp.float32)   # exact wire: parity at 1e-6

    import sys
    arch = sys.argv[3] if len(sys.argv) > 3 else "gpt2"
    mb_arg = float(sys.argv[4]) if len(sys.argv) > 4 else 0.25
    cfg = get(arch).smoke
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                  global_batch=8, seed=3))
    mesh = make_debug_mesh(pod=2, data=2, model=1)
    W = ("pod", "data")

    def fdiff(a, b):
        out = 0.0
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            if np.issubdtype(x.dtype, np.floating):
                out = max(out, float(np.abs(x.astype(np.float64)
                                            - y.astype(np.float64)).max()))
        return out

    parts = sys.argv[1].split("-")
    topology, kernels = parts[0], parts[1]
    bucketed = "bucketed" in parts[2:]
    opt_name = sys.argv[2] if len(sys.argv) > 2 else "zero_one_adam"
    COMBOS = [(sys.argv[1],
               Hierarchy(inner=2) if topology == "hier" else None,
               kernels == "pallas")]
    for tag, h, pallas in COMBOS:
        oc = opt_cfg(h, pallas, opt_name,
                     bucket_mb=mb_arg if bucketed else None)
        tr_sim = Trainer(cfg, oc, n_workers=4)
        p, s = tr_sim.sim_init(jax.random.PRNGKey(0))
        tr_mesh = Trainer(cfg, oc, mesh=mesh,
                          trainer_cfg=TrainerConfig(worker_axes=W,
                                                    donate=False))
        fn_sim = tr_sim.sim_step_fn()
        fn_mesh, _ = tr_mesh.mesh_step_fn()
        # sim stacks every leaf per worker; mesh keeps per-worker leaves
        # stacked, shards expert-parallel leaves on their expert axis and
        # drops the worker axis of shared scalars
        def to_mesh(x, spec):
            ent = tuple(spec)
            if ent and ent[0] == W:
                return x
            for ax, e in enumerate(ent):
                names = e if isinstance(e, tuple) else (e,)
                if e is not None and set(names) & set(W):
                    y = jnp.moveaxis(x, 0, ax)
                    return y.reshape(y.shape[:ax] + (-1,) + y.shape[ax + 2:])
            return x[0]
        pf = tr_mesh._params_full_specs_tree()
        sf, _ = tr_mesh.tree_specs.state_specs()
        conv_p = lambda t: jax.tree.map(to_mesh, t, pf)
        conv_s = lambda t: jax.tree.map(to_mesh, t, sf)
        p_mesh, s_mesh = tr_mesh.mesh_init(jax.random.PRNGKey(0))
        assert fdiff(conv_p(p), p_mesh) == 0.0, tag
        assert fdiff(conv_s(s), s_mesh) == 0.0, tag
        p_sim, s_sim = p, s
        # 2 steps cover every branch: warmup syncs fire each step, the
        # variance refresh at step 0, local-only updates in between
        for step in range(2):
            b = data.batch(step)
            p_sim, s_sim, met_s = fn_sim(p_sim, s_sim, b)
            p_mesh, s_mesh, met_m = fn_mesh(p_mesh, s_mesh, b)
        s_sim = conv_s(s_sim)
        dp = fdiff(conv_p(p_sim), p_mesh)
        dm = fdiff(s_sim.m, s_mesh.m)
        dv = fdiff(s_sim.v, s_mesh.v)
        dw = fdiff(s_sim.err_w, s_mesh.err_w)
        dl = abs(float(np.asarray(met_s["loss"]).reshape(-1)[0])
                 - float(np.asarray(met_m["loss"]).reshape(-1)[0]))
        worst = max(dp, dm, dv, dw, dl)
        assert worst <= 1e-6, (tag, dp, dm, dv, dw, dl)
        print(f"PARITY_OK {tag} params={dp:.2e} m={dm:.2e} v={dv:.2e} "
              f"err_w={dw:.2e} loss={dl:.2e}")
""")


TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               "--xla_cpu_enable_concurrency_optimized_"
                               "scheduler=false")
    import dataclasses, sys
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get
    from repro.core import OptimizerConfig, schedules as S
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_debug_mesh
    from repro.train import Trainer, TrainerConfig

    @dataclasses.dataclass(frozen=True)
    class NoSync:
        # the momentum sync never fires: every step is local, plus the
        # variance refresh, so no step depends on how a leaf is chunked
        def init(self):
            return ()

        def step(self, state, t):
            return jnp.asarray(False), state, jnp.asarray(1, jnp.int32)

    pallas = sys.argv[1] == "pallas"
    cfg = get("gpt2").smoke
    oc = OptimizerConfig(
        name="zero_one_adam",
        lr=S.LinearWarmupExpDecay(peak_lr=2e-3, warmup_steps=2, decay=0.97,
                                  decay_period=20),
        var_policy=S.EveryStepVariancePolicy(), sync_policy=NoSync(),
        use_pallas=pallas, comm_dtype=jnp.float32)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                  global_batch=8, seed=3))
    tr_sim = Trainer(cfg, oc, n_workers=2)
    tr_mesh = Trainer(cfg, oc, mesh=make_debug_mesh(data=2, model=2),
                      trainer_cfg=TrainerConfig(worker_axes=("data",),
                                                donate=False))
    # the TP mesh really plans other layouts than sim
    n_tp = sum(a.chunk_shape != b.chunk_shape
               for a, b in zip(tr_sim.opt.layouts, tr_mesh.opt.layouts))
    assert n_tp > 0

    def fdiff(a, b):
        out = 0.0
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
            assert x.shape == y.shape, (x.shape, y.shape)
            out = max(out, float(np.abs(x - y).max()))
        return out

    key = jax.random.PRNGKey(0)
    p_sim, s_sim = tr_sim.sim_init(key)
    p_mesh, s_mesh = tr_mesh.mesh_init(key)
    assert fdiff(p_sim, p_mesh) == 0.0
    fn_sim = tr_sim.sim_step_fn()
    fn_mesh, _ = tr_mesh.mesh_step_fn()
    first = lambda met, k: float(np.asarray(met[k]).reshape(-1)[0])
    for step in range(3):
        b = data.batch(step)
        p_sim, s_sim, met_s = fn_sim(p_sim, s_sim, b)
        p_mesh, s_mesh, met_m = fn_mesh(p_mesh, s_mesh, b)
        assert first(met_m, "var_round") and not first(met_m, "synced")
        dl = abs(first(met_s, "loss") - first(met_m, "loss"))
        assert dl <= 1e-6 * abs(first(met_s, "loss")), (step, dl)
    # parameters stay per worker (no sync); both regimes stack them
    dp = fdiff(p_sim, p_mesh)
    assert dp <= 1e-6, dp
    print(f"TP_PARITY_OK {n_tp} TP-local layouts params={dp:.2e}")
""")


def _run_combo(combo, opt_name, arch="gpt2", bucket_mb=0.25):
    r = subprocess.run([sys.executable, "-c", SCRIPT, combo, opt_name,
                        arch, str(bucket_mb)],
                       capture_output=True, text=True, timeout=1200,
                       env={**os.environ, "PYTHONPATH": "src"})
    out = r.stdout
    assert r.returncode == 0, out[-2000:] + r.stderr[-3000:]
    assert out.count("PARITY_OK") == 1, out[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
@pytest.mark.parametrize("combo", ["flat-jnp", "hier-jnp",
                                   "flat-pallas", "hier-pallas"])
def test_mesh_matches_sim_zero_one_adam(combo):
    _run_combo(combo, "zero_one_adam")


@pytest.mark.slow
def test_mesh_matches_sim_zero_one_lamb():
    """0/1-LAMB carries per-leaf trust scalars (state kind "leaf_scalar");
    this pins their mesh-regime sharding/stacking against sim."""
    _run_combo("flat-jnp", "zero_one_lamb")


@pytest.mark.slow
def test_mesh_matches_sim_bucketed_hier_pallas():
    """Bucketed exchange x hierarchy x pallas: the bucket-shaped state
    kinds (bucket_view/bucket_chunk) must shard/stack identically in the
    mesh regime — this is the combination that exercises every new layer
    of the bucketing path at once."""
    _run_combo("hier-pallas-bucketed", "zero_one_adam")


@pytest.mark.slow
def test_mesh_matches_sim_deepseek_pallas_bucketed():
    """deepseek-smoke (MoE + MLA, a first-class fused workload): the
    Pallas-dispatched bucketed exchange (--use-pallas --bucket-mb 4) must
    lower identically under the model-sharded debug mesh and the sim
    regime — the TP leaves' views and the fused buckets take the exact
    same kernel-vs-jnp dispatch decisions in both."""
    _run_combo("flat-pallas-bucketed", "zero_one_adam",
               arch="deepseek-v2-236b", bucket_mb=4.0)


@pytest.mark.slow
@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
def test_tp_mesh_matches_sim_without_sync(kernels):
    """A (data=2, model=2) mesh step against 2-worker sim over 3 steps of
    local half-steps and full-precision variance refreshes: the TP-local
    layouts, the sharded model and the variance exchange agree with sim
    to <= 1e-6 on every parameter."""
    r = subprocess.run([sys.executable, "-c", TP_SCRIPT, kernels],
                       capture_output=True, text=True, timeout=1200,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert r.stdout.count("TP_PARITY_OK") == 1, r.stdout[-2000:]
