import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede every other import (jax locks the device
# count at first initialization).

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles, and extract the roofline inputs.

For each combination this lowers + compiles the real jitted program
(train_step under partial-manual shard_map, or the serving prefill/decode
step), prints ``memory_analysis()`` / ``cost_analysis()``, parses the
optimized HLO for collective traffic, and (optionally) appends a JSON
record consumed by benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch chatglm3-6b --shape train_4k
  python -m repro.launch.dryrun --all --json results/dryrun.jsonl
  python -m repro.launch.dryrun --arch gemma3-12b --shape long_500k --multi-pod
"""
import argparse
import dataclasses
import gc
import json
import re
import sys
import time
from typing import Optional

import jax.numpy as jnp

from repro.configs import ALL_SHAPES, ASSIGNED, get
from repro.core import (CODEC_NAMES, OptimizerConfig, REGISTRY_NAMES,
                        schedules as S)
from repro.launch import shapes as SH
from repro.launch.mesh import make_mesh, make_production_mesh, worker_axes
from repro.serve import Server
from repro.train import Trainer, TrainerConfig

BYTES = {"f32": 4, "bf16": 2, "f16": 2, "u8": 1, "s8": 1, "u32": 4,
         "s32": 4, "f64": 8, "s64": 8, "u64": 8, "pred": 1, "s16": 2,
         "u16": 2}

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def _computation_blocks(hlo_text: str):
    """Split an HLO module into named computation blocks."""
    blocks = {}
    cur, buf = None, []
    hdr = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(")
    for line in hlo_text.splitlines():
        m = hdr.match(line)
        if m and line.rstrip().endswith("{") and "->" in line:
            if cur is not None:
                blocks[cur] = buf
            cur, buf = m.group(1), []
            continue
        if line.startswith("}"):
            if cur is not None:
                blocks[cur] = buf
            cur, buf = None, []
            continue
        if cur is not None:
            buf.append(line)
    return blocks


def _loop_multipliers(hlo_text: str, blocks):
    """body-computation -> trip count (XLA cost analysis counts while-loop
    bodies once; scans over layers/microbatches must be scaled).

    The trip count is read from the condition computation's *actual* loop
    bound: the integer constant feeding a ``compare`` with
    ``direction=LT`` (trip = bound, the standard counting-up ``lax.scan``
    lowering) or ``LE`` (trip = bound + 1). An unrelated large integer
    constant in the condition block — a threshold, a packed literal —
    must NOT be mistaken for the bound; the old max-over-all-constants
    heuristic did exactly that (e.g. a ``constant(32768)`` sync-schedule
    literal scaling a 4-iteration microbatch scan 32768x). When no
    compare/constant pair parses, fall back to that heuristic rather
    than silently under-counting."""
    mult = {}
    cond_body = []
    for line in hlo_text.splitlines():
        m = re.search(r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*"
                      r"body=%?([\w\.\-]+)", line)
        if m:
            cond_body.append((m.group(1), m.group(2)))
    const_re = re.compile(r"%?([\w\.\-]+)\s*=\s*[su]\d+\[\]\s*"
                          r"constant\((\d+)\)")
    cmp_re = re.compile(r"compare\(([^)]*)\).*?direction=(LT|LE)")
    for cond, body in cond_body:
        lines = blocks.get(cond, [])
        consts = {}
        for line in lines:
            for name, val in const_re.findall(line):
                consts[name] = int(val)
        trip = None
        for line in lines:
            m = cmp_re.search(line)
            if not m:
                continue
            operands = re.findall(r"%?([\w\.\-]+)", m.group(1))
            bound = next((consts[n] for n in operands if n in consts),
                         None)
            if bound is None:
                continue
            trip = bound + 1 if m.group(2) == "LE" else bound
            break
        if trip is None:   # unrecognized condition shape: legacy heuristic
            trip = 1
            for line in lines:
                for c in re.findall(r"constant\((\d+)\)", line):
                    trip = max(trip, int(c))
        mult[body] = max(1, trip)
    return mult


def _block_parents(hlo_text: str, blocks):
    """computation -> list of computations that call it (while/call/cond)."""
    parents = {}
    ref_re = re.compile(
        r"(?:body=|condition=|to_apply=|calls=|branch_computations=\{|"
        r"true_computation=|false_computation=)%?([\w\.\-]+)")
    extra_re = re.compile(r"branch_computations=\{([^}]*)\}")
    for name, lines in blocks.items():
        for line in lines:
            for ref in ref_re.findall(line):
                parents.setdefault(ref, []).append(name)
            for grp in extra_re.findall(line):
                for ref in re.findall(r"%?([\w\.\-]+)", grp):
                    parents.setdefault(ref, []).append(name)
    return parents


def collective_bytes(hlo_text: str):
    """Per-device collective traffic from optimized (SPMD-partitioned) HLO.

    Shapes in the partitioned module are per-device. Ring all-reduce moves
    ~2x the payload; the other collectives ~1x of the result shape.
    Ops inside while-loop bodies (lax.scan over layers / microbatches) are
    scaled by the loop trip count — XLA's own cost analysis counts loop
    bodies once, which would understate scanned-model traffic ~L-fold.
    """
    blocks = _computation_blocks(hlo_text)
    loop_mult = _loop_multipliers(hlo_text, blocks)
    parents = _block_parents(hlo_text, blocks)

    def total_mult(comp, depth=0):
        if depth > 8:
            return 1
        m = loop_mult.get(comp, 1)
        ps = parents.get(comp, [])
        if not ps:
            return m
        return m * max(total_mult(p, depth + 1) for p in ps)

    out = {k: 0.0 for k in _COLL}
    counts = {k: 0 for k in _COLL}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    op_re = re.compile(
        r"=\s+(\(?[\w\[\],\s{}/#]*?\)?)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(-start|-done)?\(")
    for comp, lines in blocks.items():
        scale = total_mult(comp)
        for line in lines:
            m = op_re.search(line)
            if not m:
                continue
            op = m.group(2)
            if m.group(3) == "-done":
                continue  # counted at -start
            nbytes = 0.0
            for dt, dims in shape_re.findall(m.group(1)):
                if dt not in BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * BYTES[dt]
            mult = 2.0 if op == "all-reduce" else 1.0
            out[op] += nbytes * mult * scale
            counts[op] += scale
    return out, counts


def _parse_replica_groups(line: str):
    """Replica groups of one collective line: list of id-lists, or None.

    Handles both the explicit ``{{0,1},{2,3}}`` form and the iota form
    ``[g,s]<=[t0,..]T(perm)`` (decoded numerically).
    """
    m = re.search(r"replica_groups=\{\{([\d,{}\s]*)\}\}", line)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in m.group(1).split("},{")]
    m = re.search(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    if m:
        import numpy as np
        try:
            dims = [int(x) for x in m.group(1).split(",")]
            tdims = [int(x) for x in m.group(2).split(",")]
            ids = np.arange(int(np.prod(tdims))).reshape(tdims)
            if m.group(3):
                ids = ids.transpose([int(x) for x in m.group(3).split(",")])
            return ids.reshape(dims).tolist()
        except ValueError:   # unexpected form -> caller's unattributed bucket
            return None
    # collective-permute carries source_target_pairs instead; each (src,
    # tgt) pair is its own two-device "group" for pod-crossing purposes
    m = re.search(r"source_target_pairs=\{\{([\d,{}\s]*)\}\}", line)
    if m:
        return [[int(x) for x in pair.split(",") if x.strip()]
                for pair in m.group(1).split("},{")]
    return None


def collective_group_bytes(hlo_text: str, pod_span: Optional[int] = None):
    """Collective traffic bucketed by replica-group size, plus the
    intra/inter-pod split when ``pod_span`` (devices per pod) is given.

    This is what makes the hierarchical AllReduce's promise checkable in
    the lowered HLO: the inner (intra-pod) collectives appear as groups
    whose device ids stay inside one ``pod_span`` block, the outer 1-bit
    exchange as (small) groups that cross blocks.
    """
    blocks = _computation_blocks(hlo_text)
    loop_mult = _loop_multipliers(hlo_text, blocks)
    parents = _block_parents(hlo_text, blocks)

    def total_mult(comp, depth=0):
        if depth > 8:
            return 1
        m = loop_mult.get(comp, 1)
        ps = parents.get(comp, [])
        if not ps:
            return m
        return m * max(total_mult(p, depth + 1) for p in ps)

    by_group = {}
    intra = inter = unattributed = 0.0
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    op_re = re.compile(
        r"=\s+(\(?[\w\[\],\s{}/#]*?\)?)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(-start|-done)?\(")
    for comp, lines in blocks.items():
        scale = total_mult(comp)
        for line in lines:
            m = op_re.search(line)
            if not m or m.group(3) == "-done":
                continue
            op = m.group(2)
            nbytes = 0.0
            for dt, dims in shape_re.findall(m.group(1)):
                if dt not in BYTES:
                    continue
                nelt = 1
                for d in dims.split(","):
                    if d:
                        nelt *= int(d)
                nbytes += nelt * BYTES[dt]
            nbytes *= (2.0 if op == "all-reduce" else 1.0) * scale
            groups = _parse_replica_groups(line)
            gsize = len(groups[0]) if groups else 0
            key = f"{op}|g{gsize}"
            by_group[key] = by_group.get(key, 0.0) + nbytes
            if pod_span:
                if groups:
                    crosses = any(len({i // pod_span for i in g}) > 1
                                  for g in groups)
                    if crosses:
                        inter += nbytes
                    else:
                        intra += nbytes
                else:
                    # global groups ("{}") or an unparsed form: keep it out
                    # of both pod buckets but visible, so the split never
                    # silently under-counts the collective term
                    unattributed += nbytes
    out = {"by_group_size": by_group}
    if pod_span:
        out["intrapod_bytes"] = intra
        out["interpod_bytes"] = inter
        out["unattributed_bytes"] = unattributed
    return out


def default_opt_cfg(optimizer: str = "zero_one_adam", scale_mode="tensor",
                    hierarchy_inner: int = 0, codec: str = "sign1bit",
                    codec_arg=None, bucket_mb=None):
    from repro.core import Hierarchy
    return OptimizerConfig(
        name=optimizer,
        codec=codec, codec_arg=codec_arg, bucket_mb=bucket_mb,
        lr=S.LinearWarmupExpDecay(peak_lr=4e-4, warmup_steps=12500),
        var_policy=S.AdaptiveFreezePolicy(kappa=16),
        sync_policy=S.LrProportionalSyncPolicy(
            warmup_steps=12500, double_every=32768, max_interval=16),
        onebit_warmup=16000,
        scale_mode=scale_mode,
        state_dtype=jnp.bfloat16,   # production state dtype (fp16 in paper)
        comm_dtype=jnp.bfloat16,
        hierarchy=(Hierarchy(inner=hierarchy_inner) if hierarchy_inner
                   else None),
    )


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            optimizer: str = "zero_one_adam", scale_mode: str = "tensor",
            micro_override=None, window_cache: bool = False,
            mesh_shape=None, verbose: bool = True,
            hierarchy: bool = False, codec: str = "sign1bit",
            codec_arg=None, bucket_mb=None, audit: bool = False,
            resize_to=None):
    spec = get(arch)
    shape = SH.SHAPES[shape_name]
    if shape_name not in spec.shapes:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "note": spec.skip_notes}
    if mesh_shape is not None:  # perf-iteration override (same chip count)
        dp, tp = mesh_shape
        mesh = make_mesh((dp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    W = worker_axes(mesh)
    cfg = dataclasses.replace(spec.config, param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16,
                              window_cache=window_cache)
    t0 = time.time()
    n_buckets = n_dp_leaves = audit_rec = elastic_rec = None

    if shape.kind == "train":
        n_workers = 1
        for a in W:
            n_workers *= mesh.shape[a]
        b_local = shape.global_batch // n_workers
        micro = micro_override or max(1, b_local // 2)
        inner = 0
        if hierarchy:
            if "pod" not in mesh.axis_names:
                raise ValueError("--hierarchy needs the multi-pod mesh")
            inner = mesh.shape["data"]
        tr = Trainer(cfg, default_opt_cfg(optimizer, scale_mode,
                                          hierarchy_inner=inner,
                                          codec=codec,
                                          codec_arg=codec_arg,
                                          bucket_mb=bucket_mb), mesh=mesh,
                     trainer_cfg=TrainerConfig(micro_batches=micro,
                                               worker_axes=W))
        n_buckets = (len(tr.opt.bucket_plan.buckets)
                     if getattr(tr.opt, "bucket_plan", None) is not None
                     else None)
        n_dp_leaves = sum(1 for dp in tr.opt.dp_mask if dp)
        if resize_to:
            # static pre/post-resize layout geometry: rebind the optimizer
            # at the target width and record the remap plan — no arrays,
            # no compile, just the two LeafLayout/bucket geometries
            from repro.elastic import reshard_report, resize_opt
            dst_opt = resize_opt(tr.opt, resize_to,
                                 model_axis_sizes=tr.model_sizes)
            elastic_rec = reshard_report(tr.opt, dst_opt)
        if audit:
            from repro.analysis import audit_trainer
            audit_rec = audit_trainer(tr, seq=shape.seq).to_dict()
        fn, _ = tr.mesh_step_fn()
        params, state, batch = tr.abstract_inputs(
            shape.global_batch, shape.seq,
            extra_fn=lambda B, s, c: SH.batch_extras(c, B, s))
        lowered = fn.lower(params, state, batch)
    else:
        srv = Server(cfg, mesh=mesh, worker_axes=W,
                     batch=shape.global_batch, max_seq=shape.seq)
        params = srv.abstract_params()
        cache = srv.abstract_cache()
        if shape.kind == "prefill":
            batch = SH.prefill_input_specs(cfg, shape)
            lowered = srv.prefill_fn().lower(params, batch, cache)
        else:
            d = SH.decode_input_specs(cfg, shape)
            if cfg.enc_layers:
                lowered = srv.decode_fn().lower(
                    params, cache, d["tokens"], d["pos"], d["enc_out"])
            else:
                lowered = srv.decode_fn().lower(
                    params, cache, d["tokens"], d["pos"])

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):   # older jax: one properties-dict per device
        cost = cost[0] if cost else {}
    hlo_text = compiled.as_text()
    coll, coll_counts = collective_bytes(hlo_text)
    pod_span = (mesh.devices.size // mesh.shape["pod"]
                if "pod" in mesh.axis_names else None)
    grp = collective_group_bytes(hlo_text, pod_span)

    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": (f"{mesh_shape[0]}x{mesh_shape[1]}" if mesh_shape
                 else ("2x16x16" if multi_pod else "16x16")),
        "optimizer": optimizer if shape.kind == "train" else None,
        "scale_mode": scale_mode if shape.kind == "train" else None,
        "codec": codec if shape.kind == "train" else None,
        "hierarchy": bool(hierarchy) if shape.kind == "train" else None,
        "bucket_mb": bucket_mb if shape.kind == "train" else None,
        "n_buckets": n_buckets,
        "n_dp_leaves": n_dp_leaves,
        "audit": audit_rec,
        "elastic": elastic_rec,
        "micro": micro_override, "window_cache": window_cache,
        "kind": shape.kind,
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_counts": coll_counts,
        "collective_by_group": grp["by_group_size"],
        "intrapod_bytes": grp.get("intrapod_bytes"),
        "interpod_bytes": grp.get("interpod_bytes"),
        "unattributed_collective_bytes": grp.get("unattributed_bytes"),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params": get(arch).config.param_count(),
        "active_params": get(arch).config.active_param_count(),
    }
    if verbose:
        print(f"== {arch} x {shape_name} [{rec['mesh']}] "
              f"{'opt=' + optimizer if shape.kind == 'train' else shape.kind}")
        print(f"   memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB (per device)")
        print(f"   cost_analysis: flops={rec['flops_per_device']:.3e} "
              f"bytes={rec['bytes_per_device']:.3e} (per device)")
        tot_coll = sum(coll.values())
        print(f"   collectives: {tot_coll/2**20:.1f}MiB/device "
              f"{ {k: round(v/2**20, 2) for k, v in coll.items() if v} }")
        if pod_span:
            print(f"   pod split: intra={grp['intrapod_bytes']/2**20:.1f}MiB "
                  f"inter={grp['interpod_bytes']/2**20:.1f}MiB/device")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
    del lowered, compiled
    gc.collect()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimizer", default="zero_one_adam",
                    choices=list(REGISTRY_NAMES))
    ap.add_argument("--scale-mode", default="tensor",
                    choices=["tensor", "chunk", "row"])
    ap.add_argument("--codec", default="sign1bit",
                    choices=list(CODEC_NAMES),
                    help="wire format of the compressed EF exchange; "
                         "non-sign1bit codecs lower through the jnp path")
    ap.add_argument("--codec-arg", type=float, default=None,
                    help="parameter for parameterized codecs (topk density)")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="fuse the per-leaf exchange into flat buckets of "
                         "this many MiB each; the bucket count lands in "
                         "the JSON record (n_buckets)")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--hierarchy", action="store_true",
                    help="two-level AllReduce: uncompressed intra-pod "
                         "('data'), 1-bit inter-pod ('pod'); needs "
                         "--multi-pod")
    ap.add_argument("--window-cache", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="DPxTP override, e.g. 32x8 (perf iterations)")
    ap.add_argument("--json", default=None,
                    help="append JSONL records here")
    ap.add_argument("--audit", action="store_true",
                    help="run the IR communication audit on train shapes; "
                         "any violation fails the run (non-zero exit) and "
                         "prints the first offending collective")
    ap.add_argument("--resize-to", type=int, default=None, metavar="M",
                    help="record the elastic pre/post-resize layout "
                         "geometry for a DP resize to M workers "
                         "(repro.elastic.reshard_report) in the JSON "
                         "record — static, no second compile")
    args = ap.parse_args()

    combos = []
    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(ALL_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    ok = skipped = failed = 0
    for a, s, mp in combos:
        try:
            ms = (tuple(int(x) for x in args.mesh_shape.split("x"))
                  if args.mesh_shape else None)
            rec = run_one(a, s, multi_pod=mp, optimizer=args.optimizer,
                          scale_mode=args.scale_mode,
                          micro_override=args.micro,
                          window_cache=args.window_cache,
                          mesh_shape=ms, hierarchy=args.hierarchy,
                          codec=args.codec, codec_arg=args.codec_arg,
                          bucket_mb=args.bucket_mb, audit=args.audit,
                          resize_to=args.resize_to)
        except Exception as e:  # noqa: BLE001 — report, keep going
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "failed", "error": f"{type(e).__name__}: {e}"}
            print(f"== {a} x {s} FAILED: {rec['error'][:500]}")
        if rec["status"] == "ok" and rec.get("audit") \
                and not rec["audit"]["ok"]:
            # audit violations fail the run loudly, not just in the JSON
            from repro.launch.audit import first_violation
            rec["status"] = "audit-failed"
            print(f"== {a} x {s} AUDIT FAILED: "
                  f"{first_violation(rec['audit'])}")
        if rec["status"] == "ok":
            ok += 1
        elif rec["status"] == "skipped":
            skipped += 1
            print(f"== {a} x {s} skipped ({rec['note'][:60]}...)")
        else:
            failed += 1
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")
        gc.collect()
    print(f"\nDRY-RUN SUMMARY: ok={ok} skipped={skipped} failed={failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
