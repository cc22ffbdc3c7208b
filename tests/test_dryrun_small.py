"""Multi-device lowering proof in CI: a reduced mesh dry-run in a
subprocess so the forced device count never leaks into other tests.
Covers: train step (shard_map, compressed optimizer), serve decode, and a
multi-pod (3-axis) variant — the same machinery launch/dryrun.py runs at
(2,16,16) scale.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.dryrun import default_opt_cfg, collective_bytes
    from repro.train import Trainer, TrainerConfig
    from repro.serve import Server
    from repro.configs import get

    # ---- train step on (data=2, model=4) + multi-pod (2,2,4) ----
    for mesh, W in ((make_debug_mesh(data=2, model=4), ("data",)),
                    (make_debug_mesh(pod=2, data=2, model=4),
                     ("pod", "data"))):
        cfg = dataclasses.replace(get("chatglm3-6b").smoke,
                                  param_dtype=jnp.bfloat16,
                                  compute_dtype=jnp.bfloat16)
        tr = Trainer(cfg, default_opt_cfg(), mesh=mesh,
                     trainer_cfg=TrainerConfig(micro_batches=2,
                                               worker_axes=W))
        fn, _ = tr.mesh_step_fn()
        params, state, batch = tr.abstract_inputs(8, 16)
        co = fn.lower(params, state, batch).compile()
        cb, cc = collective_bytes(co.as_text())
        assert cb["all-to-all"] > 0 or cb["all-gather"] > 0, cb
        print("TRAIN_OK", mesh.shape, sum(cb.values()))

    # ---- MoE train (EP dispatch) ----
    mesh = make_debug_mesh(data=4, model=2)
    cfgm = dataclasses.replace(get("llama4-scout-17b-a16e").smoke,
                               param_dtype=jnp.bfloat16,
                               compute_dtype=jnp.bfloat16)
    tr = Trainer(cfgm, default_opt_cfg(), mesh=mesh,
                 trainer_cfg=TrainerConfig(worker_axes=("data",)))
    assert tr.ep_degree == 4, tr.ep_degree
    fn, _ = tr.mesh_step_fn()
    params, state, batch = tr.abstract_inputs(8, 16)
    fn.lower(params, state, batch).compile()
    print("MOE_TRAIN_OK")

    # ---- serve decode (auto path) ----
    mesh = make_debug_mesh(data=2, model=4)
    cfg = dataclasses.replace(get("gemma3-12b").smoke,
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    srv = Server(cfg, mesh=mesh, worker_axes=("data",), batch=4, max_seq=64)
    co = srv.decode_fn().lower(
        srv.abstract_params(), srv.abstract_cache(),
        jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    print("SERVE_OK")
""")


SYNTH_HLO = textwrap.dedent("""\
    HloModule synth

    %body.1 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
      %arg.1 = (s32[], f32[4]) parameter(0)
      ROOT %tup.1 = (s32[], f32[4]) tuple(%arg.1)
    }

    %cond.1 (arg.2: (s32[], f32[4])) -> pred[] {
      %arg.2 = (s32[], f32[4]) parameter(0)
      %gte.0 = s32[] get-tuple-element(%arg.2), index=0
      %big.0 = s32[] constant(32768)
      %noise.0 = pred[] compare(%gte.0, %big.0), direction=NE
      %bound.0 = s32[] constant(4)
      ROOT %cmp.0 = pred[] compare(%gte.0, %bound.0), direction=LT
    }

    %body.2 (arg.3: (s32[], f32[4])) -> (s32[], f32[4]) {
      %arg.3 = (s32[], f32[4]) parameter(0)
      ROOT %tup.2 = (s32[], f32[4]) tuple(%arg.3)
    }

    %cond.2 (arg.4: (s32[], f32[4])) -> pred[] {
      %arg.4 = (s32[], f32[4]) parameter(0)
      %gte.1 = s32[] get-tuple-element(%arg.4), index=0
      %bound.1 = s32[] constant(5)
      ROOT %cmp.1 = pred[] compare(%gte.1, %bound.1), direction=LE
    }

    %body.3 (arg.5: (s32[], f32[4])) -> (s32[], f32[4]) {
      %arg.5 = (s32[], f32[4]) parameter(0)
      ROOT %tup.3 = (s32[], f32[4]) tuple(%arg.5)
    }

    %cond.3 (arg.6: (s32[], f32[4])) -> pred[] {
      %arg.6 = (s32[], f32[4]) parameter(0)
      %odd.0 = s32[] constant(7)
      ROOT %root.3 = pred[] custom-call(%arg.6), custom_call_target="opaque"
    }

    ENTRY %main.1 (p.0: (s32[], f32[4])) -> (s32[], f32[4]) {
      %p.0 = (s32[], f32[4]) parameter(0)
      %w.1 = (s32[], f32[4]) while(%p.0), condition=%cond.1, body=%body.1
      %w.2 = (s32[], f32[4]) while(%w.1), condition=%cond.2, body=%body.2
      ROOT %w.3 = (s32[], f32[4]) while(%w.2), condition=%cond.3, body=%body.3
    }
""")


def test_loop_multiplier_reads_compare_bound():
    """The trip count comes from the loop-bound compare, not the largest
    integer constant in the condition block: a microbatch scan whose cond
    also materializes an unrelated schedule literal (constant(32768))
    must scale its body 4x, not 32768x. LE bounds add one; conditions
    with no parseable compare fall back to the legacy heuristic."""
    from repro.launch.dryrun import _computation_blocks, _loop_multipliers
    blocks = _computation_blocks(SYNTH_HLO)
    assert {"body.1", "cond.1", "body.2", "cond.2", "body.3", "cond.3",
            "main.1"} <= set(blocks)
    mult = _loop_multipliers(SYNTH_HLO, blocks)
    assert mult["body.1"] == 4       # direction=LT -> the bound itself
    assert mult["body.2"] == 6       # direction=LE -> bound + 1
    assert mult["body.3"] == 7       # no compare -> legacy max heuristic


@pytest.mark.slow
def test_reduced_mesh_dryrun():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "TRAIN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
    assert "MOE_TRAIN_OK" in r.stdout, r.stderr[-3000:]
    assert "SERVE_OK" in r.stdout, r.stderr[-3000:]
