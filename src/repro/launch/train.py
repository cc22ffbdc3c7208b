"""Training driver CLI.

Modes: ``--mode single`` (one worker), ``--mode sim --workers N`` (N
simulated paper-workers via vmap on one device — the real 0/1 Adam
communication semantics at algorithm level), and ``--mode mesh`` (one
paper-worker per device present: a ``data = jax.device_count()``,
``model = 1`` mesh, the exchange over the chips' interconnect).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gpt2 --smoke \\
      --optimizer zero_one_adam --steps 50 --batch 8 --seq 64 --mode sim \\
      --workers 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import io as ckpt_io
from repro.configs import get
from repro.core import (CODEC_NAMES, Hierarchy, OptimizerConfig,
                        REGISTRY_NAMES, comm_accounting, schedules as S)
from repro.data import DataConfig, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.train import Trainer, TrainerConfig


def build_opt_cfg(args) -> OptimizerConfig:
    lr = S.LinearWarmupExpDecay(peak_lr=args.lr,
                                warmup_steps=args.lr_warmup,
                                decay=0.99, decay_period=max(args.steps // 20,
                                                             1))
    return OptimizerConfig(
        name=args.optimizer, lr=lr,
        var_policy=S.AdaptiveFreezePolicy(kappa=args.kappa),
        sync_policy=S.LrProportionalSyncPolicy(
            warmup_steps=args.sync_warmup, double_every=args.double_every,
            max_interval=args.max_interval),
        onebit_warmup=args.onebit_warmup,
        scale_mode=args.scale_mode,
        codec=args.codec, codec_arg=args.codec_arg,
        use_pallas=args.use_pallas,
        hierarchy=(Hierarchy(inner=args.hierarchy)
                   if args.hierarchy else None),
        bucket_mb=args.bucket_mb)


def _parse_resizes(specs):
    events = []
    for s in specs:
        try:
            step, m = s.split(":")
            step, m = int(step), int(m)
        except ValueError:
            raise SystemExit(f"--resize expects STEP:M, got {s!r}")
        events.append((step, m))
    return sorted(events)


def _run_elastic(args, cfg, opt_cfg, acct):
    """Sim-mode run with in-run DP resizes via repro.elastic.FleetSim."""
    from repro.elastic import FleetSim, ResizeEvent
    from repro.train import TrainerConfig as TC
    events = [ResizeEvent(step=s, workers=m)
              for s, m in _parse_resizes(args.resize)]
    fleet = FleetSim(cfg, opt_cfg, args.workers,
                     trainer_cfg=TC(micro_batches=args.micro_batches),
                     seed=args.seed)
    t0 = time.time()
    res = fleet.run(args.steps, global_batch=args.batch, seq=args.seq,
                    events=events)
    for t, loss in enumerate(res["losses"]):
        if t % args.log_every == 0 or t == args.steps - 1:
            print(f"step {t:5d} loss {loss:.4f} [{time.time()-t0:.1f}s]")
    print(f"DONE: {args.steps} steps with {len(res['resizes'])} "
          f"resize(s) ({time.time()-t0:.1f}s)")
    for r in res["resizes"]:
        print(f"  resize @ step {r['step']}: {r['n_from']} -> {r['n_to']} "
              f"workers ({r['carried_entities']} EF entities carried, "
              f"{r['dead_entities']} folded, fold={r['ef_fold']}) in "
              f"{r['reshard_ms']:.1f}ms")
    if args.save:
        n_final = res["trainer"].n_workers
        ckpt_io.save(args.save,
                     {"params": res["params"], "state": res["state"]},
                     step=args.steps,
                     meta={"arch": cfg.name, "n_workers": n_final,
                           "resizes": [
                               {k: r[k] for k in ("step", "n_from", "n_to")}
                               for r in res["resizes"]]})
        print(f"saved checkpoint to {args.save} (width {n_final})")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--optimizer", default="zero_one_adam",
                    choices=list(REGISTRY_NAMES))
    ap.add_argument("--mode", default="single",
                    choices=["single", "sim", "mesh"])
    ap.add_argument("--workers", type=int, default=4,
                    help="simulated workers (sim mode); mesh mode uses "
                         "one worker per device")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--lr-warmup", type=int, default=20)
    ap.add_argument("--kappa", type=int, default=4)
    ap.add_argument("--sync-warmup", type=int, default=20)
    ap.add_argument("--double-every", type=int, default=50)
    ap.add_argument("--max-interval", type=int, default=16)
    ap.add_argument("--onebit-warmup", type=int, default=20)
    ap.add_argument("--scale-mode", default="tensor",
                    choices=["tensor", "chunk", "row"])
    ap.add_argument("--codec", default="sign1bit",
                    choices=list(CODEC_NAMES),
                    help="wire format of the compressed EF exchange "
                         "(repro.core.codecs); sign1bit is the paper's")
    ap.add_argument("--codec-arg", type=float, default=None,
                    help="parameter for parameterized codecs "
                         "(topk: density, default 0.01)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the optimizer hot path through the fused "
                         "Pallas kernels (interpreted off-TPU)")
    ap.add_argument("--hierarchy", type=int, default=0, metavar="INNER",
                    help="workers per pod for the two-level AllReduce: "
                         "reduce uncompressed inside pods ('data' axis), "
                         "1-bit-compress only across pods ('pod' axis). "
                         "0 = flat single-level exchange")
    ap.add_argument("--bucket-mb", type=float, default=None, metavar="MB",
                    help="fuse the per-leaf compressed exchange into flat "
                         "buckets of MB MiB of f32 elements each "
                         "(repro.core.bucketing): one codec encode + one "
                         "collective pair per bucket instead of per leaf. "
                         "Default: per-leaf exchange")
    ap.add_argument("--micro-batches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--save", default=None, help="checkpoint path (.npz)")
    ap.add_argument("--resize", action="append", default=None,
                    metavar="STEP:M",
                    help="sim mode only: resize the fleet to M workers "
                         "before running STEP (repeatable). Routes the run "
                         "through repro.elastic.FleetSim — EF state and "
                         "anchors are resharded, not reset; the resize is "
                         "recorded in the run summary")
    return ap.parse_args(argv)


def run(args, opt_cfg=None, on_step=None):
    """Train as the CLI does. Returns the per-step record — ``loss``,
    ``synced``, ``var_round`` and ``step_s`` (wall seconds of each step,
    the first one including compilation) — with the final ``params``,
    ``state`` and the ``trainer``. ``opt_cfg`` overrides the optimizer
    config ``args`` would build; ``on_step(step, params, state)``, if
    given, is called after each step."""
    spec = get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    opt_cfg = opt_cfg or build_opt_cfg(args)
    key = jax.random.PRNGKey(args.seed)
    tcfg = dict(micro_batches=args.micro_batches)

    if args.mode == "mesh":
        from repro.launch.mesh import make_local_mesh, worker_axes
        mesh = make_local_mesh()
        tr = Trainer(cfg, opt_cfg, mesh=mesh, trainer_cfg=TrainerConfig(
            worker_axes=worker_axes(mesh), **tcfg))
    else:
        tr = Trainer(cfg, opt_cfg,
                     n_workers=args.workers if args.mode == "sim" else 1,
                     trainer_cfg=TrainerConfig(**tcfg))
    n = tr.n_workers
    acct = comm_accounting(tr.opt)
    print(f"arch={cfg.name} params(dp)={acct['dp_params']/1e6:.2f}M "
          f"codec={acct['codec']} "
          f"bits/param/sync={acct['bits_per_param_sync']:.3f} "
          f"workers={n} mode={args.mode} optimizer={args.optimizer}")
    if args.bucket_mb:
        print(f"bucketed exchange: {int(acct['exchange_units'])} buckets "
              f"({args.bucket_mb}MiB budget) over "
              f"{int(acct['dp_leaves'])} DP leaves -> "
              f"{int(acct['collectives_per_sync'])} collective phases/sync")
    if acct["n_inner"] > 1:
        print(f"hierarchy: {int(acct['n_outer'])} pods x "
              f"{int(acct['n_inner'])} workers/pod; sync bytes/worker "
              f"intra={acct['compressed_bytes_per_sync_inner']/2**20:.2f}MiB "
              f"inter={acct['compressed_bytes_per_sync_outer']/2**20:.2f}MiB")

    if args.resize:
        if args.mode != "sim":
            raise SystemExit("--resize needs --mode sim (the elastic "
                             "resharding path runs over the sim trainer)")
        return _run_elastic(args, cfg, opt_cfg, acct)

    if args.mode == "mesh":
        params, state = tr.mesh_init(key)
        step_fn, _ = tr.mesh_step_fn()
    elif args.mode == "sim":
        params, state = tr.sim_init(key)
        step_fn = tr.sim_step_fn()
    else:
        params, state = tr.single_init(key)
        step_fn = tr.single_step_fn()

    # bidirectional encoders train masked-LM, as BERT does
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed,
                                  kind="lm" if cfg.causal else "mlm"))

    first = lambda x: np.asarray(x).reshape(-1)[0]
    rec = {"loss": [], "synced": [], "var_round": [], "step_s": []}
    t0 = time.time()
    comp_bytes = 0.0
    rounds = 0
    for step in range(args.steps):
        batch = data.batch(step)
        if cfg.enc_layers:
            batch["frames"] = jnp.zeros((args.batch, cfg.enc_frames,
                                         cfg.d_model))
        if cfg.vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model))
        ts = time.perf_counter()
        params, state, met = step_fn(params, state, batch)
        jax.block_until_ready((params, state, met))
        rec["step_s"].append(time.perf_counter() - ts)
        synced = bool(first(met["synced"]))
        var_r = bool(first(met["var_round"]))
        loss = float(first(met["loss"]))
        rec["loss"].append(loss)
        rec["synced"].append(synced)
        rec["var_round"].append(var_r)
        if on_step is not None:
            on_step(step, params, state)
        if synced:
            comp_bytes += acct["compressed_bytes_per_sync"]
            rounds += 1
        if var_r:
            comp_bytes += acct["fullprec_bytes_per_round"]
            rounds += 1
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"lr {float(first(met['lr'])):.2e} "
                  f"sync={synced} var={var_r} "
                  f"[{time.time()-t0:.1f}s]")

    bits_pp = 8 * comp_bytes / max(acct["dp_params"], 1) / max(args.steps, 1)
    print(f"DONE: {args.steps} steps, {rounds} comm rounds, "
          f"avg {bits_pp:.3f} bits/param/step "
          f"({time.time()-t0:.1f}s)")
    if args.save:
        ckpt_io.save(args.save, {"params": params, "state": state},
                     step=args.steps,
                     meta={"arch": cfg.name, "n_workers": n})
        print(f"saved checkpoint to {args.save}")
    rec.update(params=params, state=state, trainer=tr)
    return rec


def main(argv=None):
    enable_compile_cache()
    run(parse_args(argv))


if __name__ == "__main__":
    main()
