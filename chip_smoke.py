#!/usr/bin/env python3
"""Smoke run of 0/1 Adam data-parallel training on TPU chips.

Drives ``repro.launch.train`` (the training CLI's own code path) with
BERT-base at its published width (12L, d=768, 12H, vocab 30522; masked-LM
loss), random weights from a seed, at the paper's phase-1 sequence length
128 and global batch 32, for 10 steps whose schedule holds a
full-precision variance round, 1-bit momentum syncs and local-only steps.

  python3 chip_smoke.py               one chip: every Pallas call against
                                      the jnp path on each comm layout,
                                      then sim mode with 2 workers,
                                      unfused and Pallas paths, compared
  python3 chip_smoke.py --four-chips  four chips: mesh mode (data=4,
                                      model=1) at full width, then the
                                      mesh-vs-sim parity reference at
                                      smoke width; nothing else

Everything runs in this one process (a chip belongs to one process). The
script exits non-zero, printing no result, unless JAX's backend is the
TPU. Times and memory printed on the way are smoke output, not benchmark
numbers. The last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import statistics
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
STEPS = 10
SCHEDULE = ["--optimizer", "zero_one_adam", "--steps", str(STEPS),
            "--seq", "128", "--batch", "32", "--lr", "1e-4",
            "--lr-warmup", "2", "--sync-warmup", "2", "--double-every", "2",
            "--max-interval", "4", "--kappa", "2", "--seed", "0",
            "--log-every", "1"]
# Unfused vs Pallas on one chip: the same f32 math in different reduction
# orders (Mosaic row sums vs XLA reductions), so scales differ in the last
# ulps, and a near-zero element can flip its sign bit, moving that one
# element by 2 x scale x lr. Over 10 steps at lr <= 1e-4 that moves the
# loss by far less than this relative bound (one v5e: 1.745e-5).
LOSS_RTOL = 1e-3
# The same two runs' parameters, leaf by leaf: an element mismatches when
# it differs by more than 1% of its leaf's largest update. After the
# first step, which already runs every kernel (a 1-bit sync and a
# variance round), at most MISMATCH_SHARE of the elements may (one v5e:
# 0, the largest difference 9.1e-5 x the update); a wrong byte or scale
# in the packed exchange flips about half the signs (~0.5).
PARAM_UPDATE_RTOL = 1e-2
MISMATCH_SHARE = 1e-3
# After all 10 steps the two runs have drifted apart through sign flips:
# from the second step on, a near-tie compressed element flips on one
# path, error feedback carries the flip into the next exchange, and the
# share of mismatching elements grows, then levels off (one v5e, BERT-base:
# 0, 2.4e-3 and 6.2e-3 after 1, 3 and 10 steps, spread over every leaf).
# The unfused path does the same against itself when every sign-1-bit
# scale is moved by 2e-7 relative, the size of the Mosaic-vs-XLA
# difference (one v5e: 0, 1.7e-3 and 5.3e-3). END_SHARE sits 8x above
# that and 10x below the ~0.5 of a wrong byte layout.
END_SHARE = 5e-2
# The Pallas path vs the jnp path on every comm-view layout of the run:
# packed bytes and decompressed values must be identical (sign tests and
# +-scale products are exact); per-row scales are f32 sums of one row in
# another order (random-walk error ~ sqrt(cols) x 6e-8; one v5e, the
# BERT-base frames: at most 4.41e-7), and error-feedback residuals
# inherit that error through the scale.
KERNEL_RTOL = 2e-5
# Mesh vs sim at smoke width with an f32 wire and "highest" matmuls: the
# two regimes run the same per-worker program, so they differ by
# reduction-order ulps (~1e-7 relative) and, now and then, by the sign of
# a near-zero compressed element. Such a flip moves its own element by
# about twice its leaf's largest value and the scale of its chunk by
# ~1e-4 (at smoke width on forced CPU devices: one flip by step 4, 5e-5
# of the param elements and 3e-3 of the m elements beyond 1e-5 x leaf
# max by step 9, nearly all of them through chunk scales). An element
# mismatches when it differs by more than PARITY_RTOL x its leaf's
# largest magnitude, well above the ulp growth and ten times a flip's
# chunk-scale shift (the same CPU run: 2.4e-6 to 3.6e-6 of the elements
# mismatch); the share bound above then admits flips and nothing that
# moves a chunk, a row or a leaf.
PARITY_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def train(T, argv, **overrides):
    args = T.parse_args(argv)
    print(f"--- train {' '.join(argv)}", flush=True)
    rec = T.run(args, **overrides)
    losses = rec["loss"]
    check(len(losses) == STEPS, f"expected {STEPS} steps, got {len(losses)}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    var = rec["var_round"]
    sync = [s and not v for s, v in zip(rec["synced"], var)]
    local = [not s and not v for s, v in zip(rec["synced"], var)]
    check(any(var) and any(sync) and any(local),
          f"schedule lacks a variance round, a 1-bit sync or a local-only "
          f"step: var={var} synced={rec['synced']}")
    print(f"smoke: compile+first step {rec['step_s'][0]:.2f}s, median "
          f"step {statistics.median(rec['step_s'][1:]):.4f}s "
          f"(smoke output, not a benchmark)", flush=True)
    return rec


def peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def mismatch(ref, got, scales, rtol):
    """(share of elements with |got - ref| > rtol x their leaf's scale,
    max over leaves of max|got - ref| / scale)."""
    bad = total = 0
    worst = 0.0
    for x, y, scale in zip(ref, got, scales):
        if x is None:
            continue
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        check(x.shape == y.shape, f"shape mismatch {x.shape} vs {y.shape}")
        d = np.abs(x - y)
        scale = scale or 1.0
        bad += int((d > rtol * scale).sum())
        total += d.size
        worst = max(worst, float(d.max()) / scale)
    return bad / max(total, 1), worst


def max_abs(leaves):
    return [None if x is None else float(np.abs(np.asarray(x)).max())
            for x in leaves]


def path_check(jax, T, argv):
    """Every Pallas call of the exchange and the local step, through the
    dispatch entry points the step uses, on every distinct comm-view
    layout of the trainer that ``argv`` builds, against the jnp path of
    the unfused run. Returns the failed comparisons."""
    import jax.numpy as jnp
    from repro.configs import get
    from repro.core import compressor as C
    from repro.core.codecs import _server_compress
    from repro.kernels import dispatch as K
    from repro.train import Trainer, TrainerConfig
    args = T.parse_args(argv)
    spec = get(args.arch)
    tr = Trainer(spec.smoke if args.smoke else spec.config,
                 T.build_opt_cfg(args), n_workers=args.workers,
                 trainer_cfg=TrainerConfig())
    mode = tr.opt.ar_cfg.scale_mode
    layouts = sorted({lo for lo, dp in zip(tr.opt.layouts, tr.opt.dp_mask)
                      if dp}, key=lambda lo: lo.view_shape)
    del tr
    eq = lambda a, b: bool(jnp.array_equal(a, b))
    rel = lambda a, b: float(jnp.abs(a - b).max()
                             / jnp.maximum(jnp.abs(b).max(), 1e-30))
    worst = {}
    bad = []
    for i, lo in enumerate(layouts):
        ks = jax.random.split(jax.random.PRNGKey(i), 6)
        mask = C.pad_mask(lo)
        z, err, g, m, u = (jax.random.normal(k, lo.view_shape, jnp.float32)
                           for k in ks[:5])
        if mask is not None:
            z, err = z * mask, err * mask
        v = jnp.abs(jax.random.normal(ks[5], lo.view_shape)) + 1e-3
        pk, sk, ek = K.ef_compress_view(z, err, lo, mode)
        pr, sr, er = C.ef_compress(z + err, lo, mode, mask)
        res = {"worker packed": eq(pk, pr), "worker scales": rel(sk, sr),
               "worker err": rel(ek, er),
               "decompress": eq(K.decompress_view(pr, sr, lo),
                                C.decompress(pr, sr, lo.pack_count))}
        for w in sorted({0, lo.n - 1}):   # the server chunk of worker w
            sm = None if mask is None else mask[w][None]
            pk, sk, ek = K.server_compress_view(z[w][None], err[w][None],
                                                lo, mode, w)
            pr, sr, er = _server_compress((z[w] + err[w])[None], lo, mode,
                                          sm)
            res.update({f"server {w} packed": eq(pk, pr),
                        f"server {w} scales": rel(sk, sr),
                        f"server {w} err": rel(ek, er)})
        lr, b1 = jnp.float32(1e-4), 0.9
        mh = b1 * m + (1 - b1) * g
        outs = K.fused_local_step_view(g, m, u, v, lr, b1, 1e-8, lo)
        refs = (mh, u + lr * mh, lr * mh / jnp.sqrt(v + 1e-8))
        res["fused local step"] = max(rel(a, b) for a, b in zip(outs, refs))
        for k, val in res.items():
            if isinstance(val, bool):
                worst[k] = worst.get(k, True) and val
                if not val:
                    bad.append(f"{k} on {lo.view_shape}")
            else:
                worst[k] = max(worst.get(k, 0.0), val)
                if not val <= KERNEL_RTOL:
                    bad.append(f"{k} on {lo.view_shape}: {val:.3e}")
    fmt = lambda v: (("identical" if v else "DIFFERENT")
                     if isinstance(v, bool) else f"{v:.3e}")
    print(f"Pallas vs jnp path on {len(layouts)} comm-view layouts "
          f"({', '.join(str(lo.view_shape) for lo in layouts)}), worst: "
          + ", ".join(f"{k} {fmt(v)}" for k, v in worst.items())
          + f" (tolerance: exact / {KERNEL_RTOL:g} relative)", flush=True)
    return bad


def host_leaves(jax, tree):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def phase_a(jax, T):
    """One chip: the Pallas path vs the jnp path on every layout, then sim
    mode, 2 workers, unfused vs Pallas."""
    from repro.models.layers import init_params
    base = ["--arch", "bert-base", "--mode", "sim", "--workers", "2"]
    bad = path_check(jax, T, base + SCHEDULE)
    check(not bad, f"the Pallas path disagrees with the jnp path: {bad}")
    first = {}

    def keep_first(step, params, state):
        if step == 0:
            first["ref"] = host_leaves(jax, params)

    ref = train(T, base + SCHEDULE, on_step=keep_first)
    ref_losses, ref_flags = ref["loss"], (ref["synced"], ref["var_round"])
    tr = ref["trainer"]
    # host copies: the second run needs the chip's memory to itself
    ref_params = host_leaves(jax, ref["params"])
    init = host_leaves(jax, init_params(tr.template, jax.random.PRNGKey(0),
                                        dtype=tr.model_cfg.param_dtype))
    del ref, tr
    # sim params carry a leading worker axis; the init broadcasts over it
    upd_first = [float(np.abs(r - i).max())
                 for r, i in zip(first["ref"], init)]
    upd = [float(np.abs(r - i).max()) for r, i in zip(ref_params, init)]

    def cmp_first(step, params, state):
        if step == 0:
            first["got"] = mismatch(first.pop("ref"),
                                    jax.tree.leaves(params), upd_first,
                                    PARAM_UPDATE_RTOL)

    got = train(T, base + SCHEDULE + ["--use-pallas"], on_step=cmp_first)
    check((got["synced"], got["var_round"]) == ref_flags,
          "the two paths ran different schedules")
    worst = max(abs(a - b) / abs(a) for a, b in zip(ref_losses, got["loss"]))
    share1, dmax1 = first["got"]
    share, dmax = mismatch(ref_params, jax.tree.leaves(got["params"]),
                           upd, PARAM_UPDATE_RTOL)
    print(f"unfused vs pallas: max relative loss diff {worst:.3e} "
          f"(tolerance {LOSS_RTOL:g}); params, share of elements off by "
          f"> {PARAM_UPDATE_RTOL:g} x their leaf's largest update: after "
          f"step 0 {share1:.3e} (tolerance {MISMATCH_SHARE:g}; largest "
          f"difference {dmax1:.3e} x that update), after step {STEPS - 1} "
          f"{share:.3e} (tolerance {END_SHARE:g}; largest {dmax:.3e})")
    check(worst <= LOSS_RTOL, "unfused and Pallas losses disagree")
    check(min(upd_first) > 0, "the reference run left a leaf unchanged")
    check(share1 <= MISMATCH_SHARE,
          "unfused and Pallas parameters disagree after the first step")
    check(share <= END_SHARE,
          f"unfused and Pallas parameters disagree after step {STEPS - 1}")
    print(f"smoke: peak_bytes_in_use {peak_bytes(jax)}", flush=True)
    return 1


def phase_b(jax, T):
    """Four chips: mesh mode at full width, then mesh vs sim parity."""
    import jax.numpy as jnp
    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    train(T, ["--arch", "bert-base", "--mode", "mesh", "--use-pallas"]
          + SCHEDULE)
    print(f"smoke: peak_bytes_in_use {peak_bytes(jax)}", flush=True)

    smoke = ["--arch", "bert-base", "--smoke", "--use-pallas"] + SCHEDULE
    opt_cfg = dataclasses.replace(T.build_opt_cfg(T.parse_args(smoke)),
                                  comm_dtype=jnp.float32)
    sim_params, per_step = [], []

    def keep(step, params, state):
        sim_params.append(host_leaves(jax, params))

    def compare(step, params, state):
        ref = sim_params[step]
        per_step.append(mismatch(ref, jax.tree.leaves(params), max_abs(ref),
                                 PARITY_RTOL)[0])

    with jax.default_matmul_precision("highest"):
        sim = train(T, smoke + ["--mode", "sim", "--workers", "4"],
                    opt_cfg=opt_cfg, on_step=keep)
        mesh = train(T, smoke + ["--mode", "mesh"], opt_cfg=opt_cfg,
                     on_step=compare)
    pairs = {
        "param": (jax.tree.leaves(sim["params"]),
                  jax.tree.leaves(mesh["params"])),
        "m": (sim["state"].slots["m"], mesh["state"].slots["m"]),
        "v": (sim["state"].slots["v"], mesh["state"].slots["v"]),
        "err_w": (sim["state"].err_w, mesh["state"].err_w),
    }
    diffs = {k: mismatch(r, g, max_abs(r), PARITY_RTOL)
             for k, (r, g) in pairs.items()}
    print("mesh vs sim (smoke width, f32 wire, highest precision): "
          + "; ".join(f"{k} share off by > {PARITY_RTOL:g} x leaf max "
                      f"{sh:.3e}, largest |d|/leaf max {mx:.3e}"
                      for k, (sh, mx) in diffs.items())
          + f" (tolerance: share {MISMATCH_SHARE:g}); param share after "
          "each step: " + ", ".join(f"{sh:.3e}" for sh in per_step))
    check(max(sh for sh, _ in diffs.values()) <= MISMATCH_SHARE
          and max(per_step) <= MISMATCH_SHARE, "mesh and sim disagree")
    return 4


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="mesh mode over 4 chips and its sim reference "
                         "only")
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        print(f"FAIL: JAX backend is {jax.default_backend()!r}, not a TPU",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"FAIL: no src/repro next to {__file__}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import train as T
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        count = phase_b(jax, T) if args.four_chips else phase_a(jax, T)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
