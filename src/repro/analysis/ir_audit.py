"""IR-level communication audit of the lowered train step.

The repo's headline numbers — 1-bit inter-pod volume, the bucketed
collective count, hierarchy routing — are declared analytically
(``comm_accounting``, ``codec.wire_bytes``). This module verifies them
against what actually lowers: the per-worker step is traced through
``shard_map`` over an **abstract mesh** (no devices needed — works on a
1-CPU container for any worker count), and every collective equation of
the jaxpr is extracted and checked against the declared contract:

1. **Schedule** — the collectives of each control-flow region (cond
   branches fork regions) must match, in count and order, exactly one of
   the declared manifests (:func:`bucketing.expected_sync_schedule` /
   ``expected_fullprec_schedule``), with op kind, axes, operand dtype and
   shape all equal. Anything else must be an *allowed* extra (scalar
   control/metric reductions, expert-parallel dispatch); in particular a
   full-precision collective smuggled across the inter-pod axes outside
   the declared T_v/mean rounds is a violation.
2. **Wire bytes** — each unit's declared payload bytes must match
   ``codec.wire_bytes(layout, mode)`` (padding is already inside the
   layout's chunk quantum; a one-f32-per-chunk tolerance absorbs scale
   broadcast degeneracies).
3. **Dtype discipline** — no float64 anywhere in the traced step, and no
   weak-type or f64 leaf in the optimizer-state outputs.

Entry point: :func:`audit_trainer`. The building blocks
(:func:`trace_collectives`, :func:`build_manifests`,
:func:`concretize_manifest`, :func:`check_schedule`) are public so tests
can seed violations into any single stage.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bucketing as BK
from repro.core.comm import Comm

# collective primitives, normalized ("psum2" is how psum binds on newer
# tracers; "all_reduce"/"reduce_scatter" appear via shard_map rewrites)
_COLLECTIVE_PRIMS = {
    "psum": "psum", "psum2": "psum", "pmax": "pmax", "pmin": "pmin",
    "ppermute": "ppermute", "pbroadcast": "pbroadcast",
    "all_to_all": "all_to_all", "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter", "all_reduce": "all_reduce",
    "pgather": "pgather",
}

# reductions of at most this many elements are treated as control/metric
# scalars (loss pmean, policy flags, trust-ratio norms) and allowed
# anywhere
_SMALL_ELEMS = 64


@dataclasses.dataclass(frozen=True)
class TracedCollective:
    """One collective equation extracted from the lowered step."""

    op: str                    # normalized primitive name
    axes: Tuple[str, ...]      # mesh/vmap axis names it runs over
    dtype: str                 # operand dtype
    shape: Tuple[int, ...]     # operand shape (largest operand)
    elems: int                 # total operand elements (all operands)
    nbytes: int                # total operand bytes (all operands)
    region: str                # control-flow region ("top", "cond@i/b1", ..)
    order: int                 # global emission order within the walk
    in_loop: bool              # inside scan/while (repeated per iteration)
    weak_type: bool

    def describe(self) -> str:
        return (f"{self.op} over {self.axes} {self.dtype}{self.shape} "
                f"(eqn #{self.order} in {self.region})")


@dataclasses.dataclass(frozen=True)
class Violation:
    code: str      # "schedule" | "undeclared-collective" | "interpod-bytes"
    #              # | "payload-dtype" | "wire-bytes" | "f64" | "weak-type"
    message: str

    def to_dict(self):
        return {"code": self.code, "message": self.message}


@dataclasses.dataclass
class AuditReport:
    ok: bool
    violations: List[Violation]
    collectives: List[TracedCollective]
    summary: Dict[str, Any]

    def to_dict(self):
        return {
            "ok": self.ok,
            "violations": [v.to_dict() for v in self.violations],
            "n_collectives": len(self.collectives),
            "summary": self.summary,
        }

    def to_json(self, **kw):
        return json.dumps(self.to_dict(), **kw)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def _abstract_mesh(axes, sizes):
    """Device-free mesh for the ``shard_map`` trace (``jax.make_jaxpr``
    only — it never reaches the compiler)."""
    return jax.sharding.AbstractMesh(
        tuple(sizes), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def worker_axes_sizes(trainer) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """The worker axis names/sizes the per-worker step runs under — the
    same selection ``sim_step_fn`` / the mesh paths make."""
    if trainer.mesh is not None:
        W = tuple(trainer.tc.worker_axes)
        return W, tuple(trainer.mesh.shape[a] for a in W)
    h = trainer.hierarchy
    if h is not None:
        return tuple(h.axes), (trainer.n_workers // h.inner, h.inner)
    return ("workers",), (trainer.n_workers,)


def _abstract_batch(trainer, batch: int, seq: int):
    cfg = trainer.model_cfg
    b = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.enc_layers:
        b["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.enc_frames, cfg.d_model), jnp.bfloat16)
    if cfg.vision_tokens:
        b["vision_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if not cfg.causal:
        b["loss_mask"] = jax.ShapeDtypeStruct((batch, seq), jnp.float32)
    return b


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for idx, item in enumerate(items):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "outvars"):
                yield idx, inner


def _eqn_axes(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if isinstance(ax, (tuple, list)):
        return tuple(a for a in ax if isinstance(a, str))
    return (ax,) if isinstance(ax, str) else ()


def _walk_jaxpr(jaxpr, region, in_loop, out, counter, f64_hits):
    for eqn in jaxpr.eqns:
        counter[0] += 1
        name = eqn.primitive.name
        avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)
                 if hasattr(v, "aval") and hasattr(v.aval, "dtype")]
        for a in avals:
            if str(a.dtype) == "float64":
                f64_hits.append(
                    f"{name} (eqn #{counter[0]} in {region}): "
                    f"float64 aval {a.shape}")
        if name in _COLLECTIVE_PRIMS:
            op_avals = [v.aval for v in eqn.invars
                        if hasattr(v, "aval") and hasattr(v.aval, "shape")]
            if op_avals:
                big = max(op_avals, key=lambda a: a.size)
                out.append(TracedCollective(
                    op=_COLLECTIVE_PRIMS[name],
                    axes=_eqn_axes(eqn),
                    dtype=str(big.dtype),
                    shape=tuple(big.shape),
                    elems=int(sum(a.size for a in op_avals)),
                    nbytes=int(sum(a.size * a.dtype.itemsize
                                   for a in op_avals)),
                    region=region,
                    order=counter[0],
                    in_loop=in_loop,
                    weak_type=bool(getattr(big, "weak_type", False)),
                ))
        fork = name == "cond"
        loop = in_loop or name in ("scan", "while")
        eqn_id = counter[0]
        for idx, sub in _sub_jaxprs(eqn):
            sub_region = (f"{region}/cond@{eqn_id}.b{idx}" if fork
                          else region)
            _walk_jaxpr(sub, sub_region, loop, out, counter, f64_hits)


@dataclasses.dataclass
class Trace:
    collectives: List[TracedCollective]
    f64_hits: List[str]
    state_avals: List[Tuple[str, Any]]   # (path, aval) of state outputs
    axes: Tuple[str, ...]
    sizes: Tuple[int, ...]
    jaxpr: Any


def trace_collectives(trainer, *, seq: int = 16,
                      batch_per_worker: Optional[int] = None,
                      wrap_step=None) -> Trace:
    """Trace the trainer's per-worker step under ``shard_map`` over an
    abstract mesh of its worker axes; return every collective eqn plus the
    dtype bookkeeping. ``wrap_step`` (tests) wraps the per-worker fn to
    seed violations."""
    axes, sizes = worker_axes_sizes(trainer)
    b = batch_per_worker or trainer.tc.micro_batches
    if b % trainer.tc.micro_batches:
        raise ValueError(f"batch_per_worker={b} must be divisible by "
                         f"micro_batches={trainer.tc.micro_batches}")
    # The per-worker step is traced the way the mesh path nests it: the
    # outer region is manual over the WORKER axes only, so the forward
    # sees model-GLOBAL leaves; the optimizer's own nested shard_map
    # (``Trainer._per_worker_step``) then enters the manual-'model'
    # domain with TP-local shapes, and its model-axis psums trace there.
    params_inner = jax.tree.unflatten(
        trainer.treedef, list(jax.tree.leaves(trainer.inner_abstract)))
    params_i = jax.tree.unflatten(
        trainer.treedef, list(jax.tree.leaves(trainer.local_abstract)))
    state_i = jax.eval_shape(trainer.opt.init, params_inner)
    model_sizes = dict(getattr(trainer, "model_sizes", {}) or {})
    if model_sizes:
        # worker-local / model-global state, as the outer region holds it
        ms = trainer.tree_specs.state_model_specs()

        def grow(x, s):
            if not hasattr(x, "shape"):
                return x
            shape = trainer._grow_model(
                x.shape, tuple(s) if s is not None else None)
            return jax.ShapeDtypeStruct(shape, x.dtype)

        state_i = jax.tree.map(grow, state_i, ms)
    batch_i = _abstract_batch(trainer, b, seq)

    comm = Comm(axes)
    one = trainer._one_worker_fn(comm)
    if wrap_step is not None:
        one = wrap_step(one)

    P = jax.sharding.PartitionSpec
    # bind TP model axes too (if any) — auto in the outer region
    mesh_axes, mesh_sizes = list(axes), list(sizes)
    for a, s in model_sizes.items():
        mesh_axes.append(a)
        mesh_sizes.append(s)
    mesh = _abstract_mesh(tuple(mesh_axes), tuple(mesh_sizes))
    f = jax.shard_map(one, in_specs=P(), out_specs=P(),
                      axis_names=set(axes), mesh=mesh, check_vma=False)
    closed, out_shape = jax.make_jaxpr(f, return_shape=True)(
        params_i, state_i, batch_i)

    collectives: List[TracedCollective] = []
    f64_hits: List[str] = []
    _walk_jaxpr(closed.jaxpr, "top", False, collectives, [0], f64_hits)

    # optimizer-state output avals, named by tree path
    _, state_out, _ = out_shape
    n_params = len(jax.tree.leaves(out_shape[0]))
    flat_state, _ = jax.tree_util.tree_flatten_with_path(state_out)
    out_avals = closed.out_avals
    state_avals = []
    for k, (path, _) in enumerate(flat_state):
        state_avals.append((jax.tree_util.keystr(path),
                            out_avals[n_params + k]))
    return Trace(collectives, f64_hits, state_avals, axes, sizes,
                 closed)


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

def build_manifests(opt) -> Tuple[List[BK.ExpectedCollective],
                                  List[BK.ExpectedCollective]]:
    """(sync manifest, fullprec manifest) declared by a composed
    optimizer's config — empty where the style never emits that round.
    The mean style syncs full-precision every step (no compressed round);
    the accumulate style only builds the T_v branch when the base tracks a
    variance; the gradient style traces both branches of its cond."""
    cfg = opt.cfg
    pack_order = getattr(cfg, "pack_order", "flat")
    sync = ([] if cfg.style == "mean"
            else BK.expected_sync_schedule(opt.plan, opt.ar_cfg,
                                           opt.bucket_plan, pack_order))
    has_fp = (cfg.style == "mean" or cfg.style == "gradient"
              or (cfg.style == "accumulate" and opt.base.has_variance))
    fullprec = (BK.expected_fullprec_schedule(opt.plan, opt.ar_cfg,
                                              opt.bucket_plan, pack_order)
                if has_fp else [])
    return sync, fullprec


@dataclasses.dataclass(frozen=True)
class ConcreteCollective:
    """A manifest entry resolved onto the trainer's worker axes, one eqn
    per entry (multi-axis all_gathers decompose into per-axis eqns with
    growing leading dim, matching ``Comm.all_gather``)."""

    op: str
    axes: Tuple[str, ...]
    dtype: str
    shape: Tuple[int, ...]
    source: BK.ExpectedCollective

    def describe(self) -> str:
        s = self.source
        return (f"{self.op} over {self.axes} {self.dtype}{self.shape} "
                f"[{s.round} {s.phase}, {s.unit_label}, leaf '{s.leaf}']")


def _level_axes(trainer) -> Dict[str, Tuple[str, ...]]:
    axes, _ = worker_axes_sizes(trainer)
    h = trainer.hierarchy
    levels = {"flat": axes}
    if h is not None:
        levels["outer"] = tuple(h.outer_axes)
        levels["inner"] = tuple(h.inner_axes)
    return levels


def concretize_manifest(entries, trainer) -> List[ConcreteCollective]:
    levels = _level_axes(trainer)
    axes, sizes = worker_axes_sizes(trainer)
    size_of = dict(zip(axes, sizes))
    out: List[ConcreteCollective] = []
    for e in entries:
        lv = levels.get(e.level)
        if lv is None:
            raise ValueError(f"manifest entry at level {e.level!r} but the "
                             f"trainer has levels {sorted(levels)}")
        if e.op == "all_to_all" or len(lv) == 1:
            out.append(ConcreteCollective(e.op, lv, e.dtype, e.shape, e))
            continue
        # multi-axis all_gather: one eqn per axis, innermost first
        shape = tuple(e.shape)
        for a in reversed(lv):
            out.append(ConcreteCollective("all_gather", (a,), e.dtype,
                                          shape, e))
            shape = (shape[0] * size_of[a],) + shape[1:]
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _allowance(c: TracedCollective, trainer) -> Optional[str]:
    """Why an off-manifest collective is acceptable, or None."""
    if c.op in ("psum", "pmax", "pmin", "pbroadcast") \
            and c.elems <= _SMALL_ELEMS:
        return "control/metric scalar"
    # EP token routing lives inside the decoder layer scan; the optimizer
    # exchange issues from per-unit cond regions outside any loop. The
    # in_loop discriminator keeps this allowance from swallowing the whole
    # exchange when the EP suffix covers every worker axis (deepseek /
    # llama4 smokes: n_experts % n_workers == 0 -> ep_axes == worker axes).
    ep = set(trainer.ep_axes)
    if ep and set(c.axes) <= ep and c.in_loop:
        return "expert-parallel dispatch"
    if (trainer.ep_degree > 1 and c.op == "psum"
            and set(c.axes) <= set(trainer._residual_axes())):
        return "EP residual-axis gradient mean"
    model = set(getattr(trainer, "model_axes", ()) or ())
    if model and set(c.axes) <= model:
        return "tensor-parallel reduction"
    return None


def _entry_eq(got: TracedCollective, exp: ConcreteCollective) -> bool:
    return (got.op == exp.op and tuple(got.axes) == tuple(exp.axes)
            and got.dtype == exp.dtype
            and tuple(got.shape) == tuple(exp.shape))


def _match_prefix(seq: List[TracedCollective],
                  rest: List[ConcreteCollective]
                  ) -> Optional[Tuple[int, str, bool]]:
    """None if ``seq`` equals the next ``len(seq)`` entries of ``rest``;
    else ``(prefix_len, message, dtype_only)`` locating the first
    divergence, ``dtype_only`` True when the operand dtype is the sole
    mismatch (a codec payload-dtype lie rather than a reordered/extra
    collective)."""
    for k, got in enumerate(seq):
        if k >= len(rest):
            return (k, f"{len(seq)} collectives but only {k} left in the "
                       f"declared schedule; first extra: {got.describe()}",
                    False)
        exp = rest[k]
        problems = []
        if got.op != exp.op:
            problems.append(f"op {got.op} != {exp.op}")
        if tuple(got.axes) != tuple(exp.axes):
            problems.append(f"axes {got.axes} != {exp.axes}")
        if got.dtype != exp.dtype:
            problems.append(f"dtype {got.dtype} != declared {exp.dtype}")
        if tuple(got.shape) != tuple(exp.shape):
            problems.append(f"shape {got.shape} != {exp.shape}")
        if problems:
            dtype_only = (len(problems) == 1
                          and problems[0].startswith("dtype"))
            return (k, f"position {k}: expected {exp.describe()}, found "
                       f"{got.describe()} ({'; '.join(problems)})",
                    dtype_only)
    return None


def _dtype_bits(dtype: str) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize) * 8


def check_schedule(trace: Trace, sync: List[ConcreteCollective],
                   fullprec: List[ConcreteCollective],
                   trainer) -> List[Violation]:
    """Match the control-flow regions' collectives against the declared
    manifests, in issue order.

    The per-unit exchange forks one cond region per unit, so each
    manifest is no longer carried by a single region: the regions, taken
    in trace order, must consume the sync and fullprec manifests as
    ordered contiguous prefixes — every payload region is one unit's sync
    block, one unit's fullprec block, or (mean style) a run of fullprec
    units, and both manifests must be fully consumed. Matching uses
    backtracking over (region, sync position, fullprec position): with
    byte-identical sync and fullprec blocks (identity codec), a greedy
    sync-first choice could mis-claim a fullprec region and cascade into
    false violations. Any other payload-sized collective is a violation —
    with a dedicated code when it crosses the inter-pod axes at full
    precision."""
    out: List[Violation] = []
    regions: Dict[str, List[TracedCollective]] = {}
    for c in trace.collectives:
        regions.setdefault(c.region, []).append(c)
    for r in regions:
        regions[r].sort(key=lambda c: c.order)

    h = trainer.hierarchy
    outer = set(h.outer_axes) if h is not None else set()

    def flag_undeclared(c: TracedCollective, context: str):
        if outer and (set(c.axes) & outer) \
                and _dtype_bits(c.dtype) * c.elems > 8 * c.elems \
                and c.elems > _SMALL_ELEMS:
            out.append(Violation(
                "interpod-bytes",
                f"undeclared full-precision collective crosses the "
                f"inter-pod axes {sorted(outer)}: {c.describe()} "
                f"({context})"))
        else:
            out.append(Violation(
                "undeclared-collective",
                f"collective not in any declared schedule: "
                f"{c.describe()} ({context})"))

    # Payload sequences per region, in trace (= issue) order. Manifests
    # contain only all_to_all / all_gather — any other payload-sized op is
    # undeclared by construction (the smuggled-psum case) and must not
    # poison the sequence match.
    ordered: List[Tuple[str, List[TracedCollective]]] = []
    for region, seq in regions.items():
        payload = [c for c in seq if _allowance(c, trainer) is None]
        for c in payload:
            if c.op not in ("all_to_all", "all_gather"):
                flag_undeclared(c, f"region {region}")
        payload = [c for c in payload
                   if c.op in ("all_to_all", "all_gather")]
        if payload:
            ordered.append((region, payload))
    ordered.sort(key=lambda rp: rp[1][0].order)

    # --- backtracking assignment -------------------------------------
    seqs = [p for _, p in ordered]
    memo: Dict[Tuple[int, int, int], bool] = {}

    def assign(ri: int, s: int, f: int) -> bool:
        if ri == len(seqs):
            return s == len(sync) and f == len(fullprec)
        key = (ri, s, f)
        if key in memo:
            return memo[key]
        seq = seqs[ri]
        k = len(seq)
        ok = False
        if (s + k <= len(sync)
                and all(_entry_eq(c, e)
                        for c, e in zip(seq, sync[s:s + k]))):
            ok = assign(ri + 1, s + k, f)
        if (not ok and f + k <= len(fullprec)
                and all(_entry_eq(c, e)
                        for c, e in zip(seq, fullprec[f:f + k]))):
            ok = assign(ri + 1, s, f + k)
        memo[key] = ok
        return ok

    if assign(0, 0, 0):
        return out

    # --- diagnostics: greedy replay locating the first divergence -----
    s = f = 0
    diagnosed = False
    for region, payload in ordered:
        k = len(payload)
        res_s = (_match_prefix(payload, sync[s:])
                 if sync else (0, "no sync schedule declared", False))
        res_f = (_match_prefix(payload, fullprec[f:])
                 if fullprec else (0, "no fullprec schedule declared",
                                   False))
        if res_s is None:
            s += k
            continue
        if res_f is None:
            f += k
            continue
        if not sync and not fullprec:
            for c in payload:
                flag_undeclared(c, f"region {region}")
            continue
        # report against the closest manifest (longest matching prefix);
        # a dtype-only divergence gets its own code so the seeded codec
        # fixture is distinguishable from a reordering
        (plen, msg, dtype_only), name = max(
            ((res_s, "sync"), (res_f, "fullprec")),
            key=lambda t: t[0][0])
        out.append(Violation(
            "payload-dtype" if dtype_only else "schedule",
            f"region {region} does not match the declared {name} "
            f"schedule: {msg}"))
        diagnosed = True
        # consume the better prefix so later regions diagnose against
        # sensible offsets
        if name == "sync":
            s += min(k, len(sync) - s)
        else:
            f += min(k, len(fullprec) - f)
    for name, manifest, pos in (("sync", sync, s),
                                ("fullprec", fullprec, f)):
        if pos < len(manifest) and not diagnosed:
            out.append(Violation(
                "schedule",
                f"no region matches the declared {name} schedule "
                f"({len(manifest) - pos} collectives unconsumed, first: "
                f"{manifest[pos].describe()})"))
    return out


def check_wire_bytes(opt, tol_per_chunk: int = 4) -> List[Violation]:
    """Declared payload bytes vs ``codec.wire_bytes(layout, mode)`` per
    exchange unit and phase, within ``tol_per_chunk`` bytes per chunk."""
    out: List[Violation] = []
    ar_cfg = opt.ar_cfg
    codec = ar_cfg.codec
    hier = ar_cfg.hierarchy is not None
    pack_order = getattr(opt.cfg, "pack_order", "flat")
    sync = BK.expected_sync_schedule(opt.plan, ar_cfg, opt.bucket_plan,
                                     pack_order) \
        if opt.cfg.style != "mean" else []
    if not sync:
        return out
    units = BK.exchange_units(opt.plan, opt.bucket_plan, pack_order)
    for u, (lo, _, label) in enumerate(units):
        wire = codec.wire_bytes(lo, ar_cfg.scale_mode)
        for phase, lead in (("scatter", lo.n_outer if hier else lo.n),
                            ("gather", 1)):
            got = sum(e.nbytes for e in sync
                      if e.unit == u and e.phase == phase)
            want = lead * wire[phase]
            if abs(got - want) > tol_per_chunk * lead:
                out.append(Violation(
                    "wire-bytes",
                    f"{label} {phase} payload is {got} bytes but "
                    f"codec.wire_bytes declares {want} "
                    f"({lead} chunks x {wire[phase]} B; codec "
                    f"{codec.name}, mode {ar_cfg.scale_mode})"))
    return out


def check_dtypes(trace: Trace) -> List[Violation]:
    out = [Violation("f64", f"float64 promotion in the traced step: {m}")
           for m in trace.f64_hits[:8]]
    for path, aval in trace.state_avals:
        if str(aval.dtype) == "float64":
            out.append(Violation(
                "f64", f"optimizer state leaf {path} is float64"))
        if getattr(aval, "weak_type", False):
            out.append(Violation(
                "weak-type",
                f"optimizer state leaf {path} has a weak type "
                f"({aval.dtype}) — a python-scalar promotion leaked into "
                f"carried state"))
    for c in trace.collectives:
        if c.weak_type:
            out.append(Violation(
                "weak-type",
                f"collective operand is weakly typed: {c.describe()}"))
    return out


# ---------------------------------------------------------------------------
# top-level entry
# ---------------------------------------------------------------------------

def audit_trainer(trainer, *, seq: int = 16,
                  batch_per_worker: Optional[int] = None,
                  wrap_step=None) -> AuditReport:
    """Run the full IR audit on a built Trainer (sim or mesh mode)."""
    opt = trainer.opt
    if not hasattr(opt, "ar_cfg") or not hasattr(opt, "plan"):
        raise TypeError(
            f"audit_trainer needs a composed optimizer with a declared "
            f"plan/ar_cfg; got {type(opt).__name__}")
    trace = trace_collectives(trainer, seq=seq,
                              batch_per_worker=batch_per_worker,
                              wrap_step=wrap_step)
    sync_m, fp_m = build_manifests(opt)
    sync_c = concretize_manifest(sync_m, trainer)
    fp_c = concretize_manifest(fp_m, trainer)
    violations = (check_schedule(trace, sync_c, fp_c, trainer)
                  + check_wire_bytes(opt)
                  + check_dtypes(trace))
    axes, sizes = worker_axes_sizes(trainer)
    summary = {
        "arch": trainer.model_cfg.name,
        "axes": dict(zip(axes, sizes)),
        "n_workers": trainer.n_workers,
        "hierarchy_inner": (trainer.hierarchy.inner
                            if trainer.hierarchy else 0),
        "codec": opt.ar_cfg.codec.name,
        "style": opt.cfg.style,
        "bucketed": opt.bucket_plan is not None,
        "pack_order": getattr(opt.cfg, "pack_order", "flat"),
        "exchange_units": len(BK.exchange_units(
            opt.plan, opt.bucket_plan,
            getattr(opt.cfg, "pack_order", "flat"))),
        "collectives_traced": len(trace.collectives),
        "sync_collectives_declared": len(sync_c),
        "fullprec_collectives_declared": len(fp_c),
        "sync_payload_bytes": int(sum(e.nbytes for e in sync_m)),
        "fullprec_payload_bytes": int(sum(e.nbytes for e in fp_m)),
        "interpod_sync_bytes": int(sum(e.nbytes for e in sync_m
                                       if e.inter_pod)),
    }
    return AuditReport(ok=not violations, violations=violations,
                       collectives=trace.collectives, summary=summary)
