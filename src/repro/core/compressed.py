"""``compressed_dp``: compressed data-parallel sync as a composable transform.

The paper's 0/1 Adam recipe — stale-state linearization + error-feedback
1-bit sync + local steps — is not Adam-specific. This module factors the
recipe into a combinator over *base steps* (:mod:`repro.core.base_steps`):

    opt = compressed_dp(adam_base(), lr=..., sync_policy=..., var_policy=...)(
        param_shapes, specs=specs, dp_mask=dp_mask, n_workers=n)
    state = opt.init(params)
    params, state, metrics = opt.step(comm, params, grads, state)

Every bound optimizer implements the same **GradientTransform protocol**
(``init`` / ``step`` written per worker, exactly like the legacy classes),
so trainers, checkpointing, and the benchmarks are base-agnostic.

Three sync styles, all owning the same layouts / EF state / hierarchy:

* ``"accumulate"`` — paper Algorithm 1 generalized: local linearized
  half-steps accumulate ``u``; on T_u steps ``u`` is 1-bit AllReduced
  (Algorithm 2) and parameters re-anchor; on T_v steps the variance is
  refreshed from a full-precision gradient mean. With ``adam_base`` this is
  bitwise-identical to the legacy ``ZeroOneAdam`` (asserted in
  tests/test_composed_equivalence.py); with ``lamb_base`` / ``momentum_sgd_base``
  it yields 0/1-LAMB and 0/1-SGD.
* ``"gradient"`` — the 1-bit Adam two-stage schedule (Algorithm 4):
  full-precision gradient AllReduce while ``var_policy`` fires (the warmup
  stage), EF-1-bit gradient AllReduce with frozen variance afterwards.
  Bitwise-identical to the legacy ``OneBitAdam`` with
  ``var_policy=FixedWarmupPolicy(onebit_warmup)`` at ``weight_decay=0``
  (the legacy class never applied decay; this style does).
* ``"mean"`` — the uncompressed baseline: full-precision gradient mean every
  step, variance every step. ``compressed_dp(adam_base(), style="mean")``
  is distributed Adam; with the other bases, distributed LAMB /
  momentum-SGD.

State is carried per leaf in comm-view shape for DP leaves (natural shape
for ``dp_mask=False`` leaves, which take plain local base steps). The
``slots`` dict holds whatever the base declares ("m", optionally "v",
optionally per-leaf "trust" scalars), so one state type serves every base.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import bucketing as BK
from repro.core import codecs as CODECS
from repro.core import compressor as C
from repro.core import leafwise
from repro.core import onebit_allreduce as AR
from repro.core import schedules as S
from repro.core.comm import Comm, Hierarchy

STYLES = ("accumulate", "gradient", "mean")


class CompressedDPState(NamedTuple):
    step: jnp.ndarray
    gamma_acc: jnp.ndarray    # sum of gamma since the last sync (accumulate)
    sync_pstate: tuple        # T_u policy carried state (accumulate)
    var_pstate: tuple         # T_v policy carried state
    slots: Dict[str, list]    # base slots: "m" (+"v", +"trust"), per leaf
    u: list                   # accumulated update views (accumulate style)
    err_w: list               # worker-side EF (layout.ef_worker_shape)
    err_s: list               # server-side EF (chunk shape)
    anchor: list              # x_{t'} copies (accumulate + store_anchor)

    # Convenience accessors so slot-based state reads like the legacy one.
    @property
    def m(self):
        return self.slots["m"]

    @property
    def v(self):
        return self.slots.get("v")


@dataclasses.dataclass(frozen=True)
class StateKind:
    """Tag describing one optimizer-state leaf, for generic sharding-spec /
    abstract-shape derivation (see train/sharding.py).

    tags: ``scalar`` (replicated scalar), ``view`` (comm view for DP leaves,
    natural for non-DP), ``chunk`` (server chunk, DP only), ``natural``
    (param-shaped, DP only — anchors), ``leaf_scalar`` (per-worker scalar,
    DP only — trust ratios). ``leaf`` indexes the flat param leaf.

    With a bucketed exchange (``bucket_mb`` set) the EF/anchor state lives
    per *bucket* instead of per leaf: ``bucket_view`` / ``bucket_chunk``
    mirror ``view`` / ``chunk`` with ``leaf`` indexing
    ``opt.bucket_plan.buckets`` (always DP — buckets only cover DP
    leaves)."""

    tag: str
    leaf: Optional[int] = None

    @property
    def bucketed(self) -> bool:
        return self.tag in ("bucket_view", "bucket_chunk")


_SCALAR = StateKind("scalar")


class _ExchangeUnit(NamedTuple):
    """One unit of the per-unit issue schedule: a bucket, or a single DP
    leaf when bucketing is off. Each unit's exchange (T_u sync, 1-bit
    gradient, and full-precision T_v alike) is issued under its own
    ``lax.cond`` whose operands are only the unit's member leaves and its
    EF/anchor state — so the collective depends on nothing but those
    leaves' gradients, and XLA's latency-hiding scheduler can start it
    while the rest of the backward/accumulation compute is still running.

    ``state_idx`` indexes the per-leaf EF/anchor lists when bucketing is
    off (flat leaf index) and ``bucket_plan.buckets`` otherwise;
    ``members`` are flat leaf indices in unit-buffer order."""

    state_idx: int
    members: tuple
    layout: Any
    vspec: Any
    bucket: Any               # bucketing.Bucket | None (per-leaf unit)


@dataclasses.dataclass(frozen=True)
class CompressedDP:
    """Unbound transform: a base step plus the distributed-sync policy.

    Calling it on a parameter tree returns the bound
    :class:`ComposedOptimizer` (the GradientTransform). Field defaults are
    the paper's production values, mirroring ``OptimizerConfig``.
    """

    base: Any
    style: str = "accumulate"
    lr: Callable = S.ConstantLr(1e-3)
    sync_policy: Any = S.LrProportionalSyncPolicy(
        warmup_steps=12500, double_every=32768, max_interval=16)
    var_policy: Any = S.AdaptiveFreezePolicy(kappa=16)
    weight_decay: float = 0.0
    scale_mode: C.ScaleMode = "tensor"
    quantize: bool = True               # deprecated: False -> codec="identity"
    codec: Any = "sign1bit"             # wire format of the EF exchange —
                                        # a registry name (codecs.CODEC_NAMES)
                                        # or a Codec instance
    codec_arg: Optional[float] = None   # parameter for parameterized codecs
                                        # (topk density)
    store_anchor: bool = True
    comm_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    use_pallas: bool = False
    hierarchy: Optional[Hierarchy] = None
    bucket_mb: Optional[float] = None   # fuse the per-leaf exchange into
                                        # fixed-budget flat buckets (MiB of
                                        # f32 elements per bucket; see
                                        # repro.core.bucketing). None keeps
                                        # the historical per-leaf exchange.
    pack_order: str = "flat"            # exchange-unit packing/issue order
                                        # (bucketing.PACK_ORDERS):
                                        # "reverse_backward" issues units in
                                        # reverse flat-leaf order ≈ backward
                                        # readiness order, so early units'
                                        # exchanges overlap the tail of the
                                        # backward pass.

    def __post_init__(self):
        if self.style not in STYLES:
            raise ValueError(f"style={self.style!r}; choose from {STYLES}")
        if self.bucket_mb is not None and self.bucket_mb <= 0:
            raise ValueError(
                f"bucket_mb must be positive (MiB per fused bucket), got "
                f"{self.bucket_mb!r}")
        if self.pack_order not in BK.PACK_ORDERS:
            raise ValueError(
                f"pack_order must be one of {BK.PACK_ORDERS}, got "
                f"{self.pack_order!r}")
        C.validate_scale_mode(self.scale_mode)
        codec = self.codec
        if not self.quantize:
            warnings.warn(
                "quantize=False is deprecated; use codec=\"identity\" "
                "instead (the exact-mean exchange is now the identity "
                "codec — see repro.core.codecs)", DeprecationWarning,
                stacklevel=3)
        # precedence (shared with OneBitConfig via
        # codecs.resolve_with_quantize, so the legacy and composed paths
        # can never disagree): the deprecated knob forces identity unless
        # a NON-default codec is set — an explicit "sign1bit", name or
        # instance, is indistinguishable from the default and is
        # rewritten; any other explicit codec wins.
        codec = CODECS.resolve_with_quantize(codec, self.quantize)
        # resolve once, at config-build time: a bad codec name / codec_arg
        # fails here with the registry listed, not deep inside the exchange
        object.__setattr__(self, "codec",
                           CODECS.make_codec(codec, self.codec_arg))
        if (self.style == "accumulate" and self.base.needs_anchor
                and not self.store_anchor):
            raise ValueError(
                f"{type(self.base).__name__} refreshes slots at syncs and "
                f"therefore requires store_anchor=True in the accumulate "
                f"style (the anchor recovery path assumes a fixed "
                f"preconditioner between syncs)")
        if self.style == "accumulate" and self.weight_decay:
            raise ValueError(
                "weight_decay is not supported in the accumulate style: a "
                "decay term makes the local step affine in x, breaking the "
                "u-linearization that lets syncs exchange the accumulated "
                "buffer (x_{t+1/2} = x_{t'} - precond(u) no longer holds). "
                "Use decoupled decay outside the optimizer, or the "
                "gradient/mean styles.")

    def __call__(self, param_shapes, *, specs=None, dp_mask=None,
                 n_workers: int, model_axis_sizes=None):
        return ComposedOptimizer(self, param_shapes, specs, dp_mask,
                                 n_workers, model_axis_sizes)


def compressed_dp(base, **kwargs) -> CompressedDP:
    """Compose a base step with the compressed-DP sync machinery."""
    return CompressedDP(base=base, **kwargs)


class ComposedOptimizer:
    """``compressed_dp(...)`` bound to a parameter tree (GradientTransform)."""

    def __init__(self, cfg: CompressedDP, param_shapes, specs, dp_mask,
                 n_workers, model_axis_sizes=None):
        self.cfg = cfg
        self.base = cfg.base
        plan = leafwise.make_plan(param_shapes, specs, dp_mask, n_workers,
                                  model_axis_sizes, cfg.hierarchy)
        self.plan = plan
        self.n = plan.n
        self.hierarchy = plan.hierarchy
        self.model_axes = plan.model_axes
        self.treedef = plan.treedef
        self.specs = plan.specs
        self.dp_mask = plan.dp_mask
        self.layouts = plan.layouts
        self.vspecs = plan.vspecs
        self.ar_cfg = leafwise.make_ar_cfg(
            plan, scale_mode=cfg.scale_mode, quantize=cfg.quantize,
            codec=cfg.codec, use_pallas=cfg.use_pallas,
            comm_dtype=cfg.comm_dtype)
        self.codec = self.ar_cfg.codec
        # Bucketed exchange: EF state / anchors / codec payloads /
        # collectives operate per bucket (repro.core.bucketing) instead of
        # per leaf. None keeps the historical per-leaf exchange.
        self.bucket_plan = (BK.make_bucket_plan(plan, cfg.bucket_mb,
                                                self.vspecs, cfg.pack_order)
                            if cfg.bucket_mb is not None else None)
        if self.bucket_plan is not None:
            self.units = tuple(
                _ExchangeUnit(bi, b.members, b.layout, b.vspec, b)
                for bi, b in enumerate(self.bucket_plan.buckets))
        else:
            idx = [i for i, dp in enumerate(plan.dp_mask) if dp]
            if cfg.pack_order == "reverse_backward":
                idx = idx[::-1]
            self.units = tuple(
                _ExchangeUnit(i, (i,), plan.layouts[i], plan.vspecs[i],
                              None)
                for i in idx)
        self._slot_specs = self.base.slot_specs()
        self._use_sync_policy = cfg.style == "accumulate"
        self._use_var_policy = (cfg.style in ("accumulate", "gradient")
                                and self.base.has_variance)
        self._has_u = cfg.style == "accumulate"
        self._has_ef = cfg.style in ("accumulate", "gradient")
        self._has_anchor = self._has_u and cfg.store_anchor

    def flat(self, tree):
        return self.treedef.flatten_up_to(tree)

    def exchange_units(self):
        """``(layout, vspec, label)`` per exchange unit, in issue order —
        the single source the audit / accounting layers use so the
        declared schedule can never drift from the step's issue loop."""
        return BK.exchange_units(self.plan, self.bucket_plan,
                                 self.cfg.pack_order)

    # ------------------------------------------------------------------ #
    # state
    # ------------------------------------------------------------------ #
    def init(self, params) -> CompressedDPState:
        cfg = self.cfg
        sd = cfg.state_dtype
        los, dps = self.layouts, self.dp_mask
        ps = self.flat(params)

        def slot(skind, init_val, p, lo, dp):
            if skind == "scalar":
                return (jnp.full((), init_val, jnp.float32) if dp else None)
            return jnp.full(lo.view_shape if dp else p.shape, init_val, sd)

        slots = {name: [slot(sk, iv, p, lo, dp)
                        for p, lo, dp in zip(ps, los, dps)]
                 for name, (sk, iv) in self._slot_specs.items()}
        bp = self.bucket_plan
        if bp is None:
            err_w = [jnp.zeros(lo.ef_worker_shape, sd)
                     if (dp and self._has_ef) else None
                     for lo, dp in zip(los, dps)]
            err_s = [jnp.zeros(lo.chunk_shape, sd)
                     if (dp and self._has_ef) else None
                     for lo, dp in zip(los, dps)]
            anchor = [(p * 1.0).astype(p.dtype)
                      if (dp and self._has_anchor) else None
                      for p, dp in zip(ps, dps)]
        else:
            # per-bucket EF / anchors: the bucket buffer is what the codec
            # compresses, so its error state (and the re-anchored params)
            # live in bucket shape
            err_w = [jnp.zeros(b.layout.ef_worker_shape, sd)
                     if self._has_ef else None for b in bp.buckets]
            err_s = [jnp.zeros(b.layout.chunk_shape, sd)
                     if self._has_ef else None for b in bp.buckets]
            anchor = [self._gather_bucket(
                          b, [(ps[i] * 1.0).astype(ps[i].dtype)
                              for i in b.members])
                      if self._has_anchor else None for b in bp.buckets]
        return CompressedDPState(
            step=jnp.zeros((), jnp.int32),
            gamma_acc=jnp.zeros((), jnp.float32),
            sync_pstate=(cfg.sync_policy.init()
                         if self._use_sync_policy else ()),
            var_pstate=(cfg.var_policy.init()
                        if self._use_var_policy else ()),
            slots=slots,
            u=[jnp.zeros(lo.view_shape, sd) if (dp and self._has_u) else None
               for lo, dp in zip(los, dps)],
            err_w=err_w,
            err_s=err_s,
            anchor=anchor,
        )

    def _gather_bucket(self, bucket, leaves_nat):
        """Natural member leaves -> bucket buffer (via their comm views)."""
        views = [C.to_view(x, self.layouts[i])
                 for x, i in zip(leaves_nat, bucket.members)]
        return BK.gather_views(bucket, views)

    def state_kinds(self) -> CompressedDPState:
        """Pytree mirroring the state treedef with :class:`StateKind`
        leaves (same ``None`` placements as :meth:`init`)."""
        cfg = self.cfg
        dps = self.dp_mask
        slots = {}
        for name, (sk, _) in self._slot_specs.items():
            if sk == "scalar":
                slots[name] = [StateKind("leaf_scalar", i) if dp else None
                               for i, dp in enumerate(dps)]
            else:
                slots[name] = [StateKind("view", i)
                               for i in range(len(dps))]
        bp = self.bucket_plan
        if bp is None:
            err_w = [StateKind("view", i) if (dp and self._has_ef) else None
                     for i, dp in enumerate(dps)]
            err_s = [StateKind("chunk", i) if (dp and self._has_ef) else None
                     for i, dp in enumerate(dps)]
            anchor = [StateKind("natural", i)
                      if (dp and self._has_anchor) else None
                      for i, dp in enumerate(dps)]
        else:
            err_w = [StateKind("bucket_view", bi) if self._has_ef else None
                     for bi in range(len(bp.buckets))]
            err_s = [StateKind("bucket_chunk", bi) if self._has_ef else None
                     for bi in range(len(bp.buckets))]
            anchor = [StateKind("bucket_view", bi)
                      if self._has_anchor else None
                      for bi in range(len(bp.buckets))]
        return CompressedDPState(
            step=_SCALAR, gamma_acc=_SCALAR,
            sync_pstate=tuple(_SCALAR for _ in (
                cfg.sync_policy.init() if self._use_sync_policy else ())),
            var_pstate=tuple(_SCALAR for _ in (
                cfg.var_policy.init() if self._use_var_policy else ())),
            slots=slots,
            u=[StateKind("view", i) if (dp and self._has_u) else None
               for i, dp in enumerate(dps)],
            err_w=err_w,
            err_s=err_s,
            anchor=anchor,
        )

    def _slots32(self, slots, i):
        return {name: (slots[name][i].astype(jnp.float32)
                       if slots[name][i] is not None else None)
                for name in slots}

    def _unit_gather(self, unit, views):
        """Member comm views -> the unit's exchange buffer."""
        if unit.bucket is None:
            (v,) = views
            return v
        return BK.gather_views(unit.bucket, views)

    def _unit_scatter(self, unit, buf):
        """Unit exchange buffer -> member comm views (inverse of
        :meth:`_unit_gather` on the true elements)."""
        if unit.bucket is None:
            return [buf]
        return BK.scatter_views(unit.bucket, buf,
                                [self.layouts[i] for i in unit.members])

    def _fullprec_unit(self, comm, unit, bufs):
        """Full-precision mean of ONE exchange unit's member view buffers
        (the T_v / mean-round transport). Elementwise, so fusing members
        into a bucket is value-preserving per element."""
        with jax.named_scope(telemetry.OPT_EXCHANGE):
            z = self._unit_gather(unit, bufs)
            o = AR.fullprec_allreduce_view(
                comm, z, self.cfg.comm_dtype, vspec=unit.vspec,
                hierarchy=self.hierarchy, layout=unit.layout)
            return self._unit_scatter(unit, o)

    def _fullprec_dp(self, comm, bufs_dp):
        """Full-precision mean of the DP leaves' view buffers, one
        collective pair per exchange unit (leaf, or bucket when bucketing
        is on), issued in unit order."""
        dp_idx = [i for i, dp in enumerate(self.dp_mask) if dp]
        dp_pos = {i: k for k, i in enumerate(dp_idx)}
        out = [None] * len(bufs_dp)
        for unit in self.units:
            res = self._fullprec_unit(
                comm, unit, [bufs_dp[dp_pos[i]] for i in unit.members])
            for i, v in zip(unit.members, res):
                out[dp_pos[i]] = v
        return out

    # ------------------------------------------------------------------ #
    # step
    # ------------------------------------------------------------------ #
    def step(self, comm: Comm, params, grads, state: CompressedDPState,
             worker_index=None):
        # every op of the step is the local step's unless the sync, the
        # variance round or the exchange inside it names its own scope
        with jax.named_scope(telemetry.OPT_LOCAL_STEP):
            if self.cfg.style == "accumulate":
                return self._step_accumulate(comm, params, grads, state,
                                             worker_index)
            return self._step_sync(comm, params, grads, state, worker_index)

    # --- accumulate: paper Algorithm 1, generalized over bases ---------- #
    def _step_accumulate(self, comm, params, grads, state, worker_index):
        cfg, base = self.cfg, self.base
        t = state.step
        lr = cfg.lr(t).astype(jnp.float32)

        do_sync, sync_ps, interval = cfg.sync_policy.step(state.sync_pstate,
                                                          t)
        if self._use_var_policy:
            do_var, var_ps = cfg.var_policy.step(state.var_pstate, t,
                                                 interval)
        else:
            do_var, var_ps = jnp.asarray(False), state.var_pstate

        los, dps = self.layouts, self.dp_mask
        xs, gs = self.flat(params), self.flat(grads)
        gv = [C.constrain(C.to_view(g.astype(jnp.float32), lo), vs) if dp
              else g.astype(jnp.float32)
              for g, lo, dp, vs in zip(gs, los, dps, self.vspecs)]
        gamma_total = state.gamma_acc + lr     # sum of gamma over [t', t]

        # --- local half-step for every leaf ----------------------------
        # DP leaves with use_pallas route the elementwise chain through the
        # fused kernel (keyed on the base kind); the unfused jnp chain is
        # f32-identical.
        if cfg.use_pallas:
            from repro.kernels import dispatch as K
        x_half, m_half, u_half = [], [], []
        for i, (x, g, lo, dp, vs) in enumerate(zip(xs, gv, los, dps,
                                                   self.vspecs)):
            s32 = self._slots32(state.slots, i)
            m32 = s32["m"]
            u = state.u[i]
            if dp and cfg.use_pallas and K.kernel_safe(
                    vs, lo, self.ar_cfg.model_axes):
                mh, u_new, delta = K.fused_local_step_view(
                    g, m32, u.astype(jnp.float32), s32.get("v"), lr,
                    base.beta1, getattr(base, "eps", 0.0), lo,
                    kind=base.kind, vspec=vs)
                if base.has_trust:
                    delta = s32["trust"] * delta
                delta_nat = C.from_view(delta, lo)
            else:
                mh = base.beta1 * m32 + (1 - base.beta1) * g
                if not dp and base.has_trust:
                    # non-DP leaves never sync: plain local base step with a
                    # per-step trust ratio (ordinary LAMB behaviour)
                    upd = base.precond_raw(mh, s32)
                    trust = base.trust_ratio(x.astype(jnp.float32), upd,
                                             self.model_axes)
                    delta = lr * trust * upd
                else:
                    delta = base.precond(lr * mh, s32)
                delta_nat = C.from_view(delta, lo) if dp else delta
                u_new = (u.astype(jnp.float32) + lr * mh) if dp else None
            x_half.append((x.astype(jnp.float32) - delta_nat).astype(x.dtype))
            m_half.append(mh)
            u_half.append(u_new)

        use_anchor = cfg.store_anchor
        sync_names = tuple(base.sync_slot_names)

        def post_sync_leaf(i, ubar, anc32, xh_i, uh_i):
            """Per-leaf post-exchange update shared by per-leaf and
            bucketed units: momentum refresh, slot refresh, the
            re-anchored (or corrected) parameter, u reset. Returns
            ``(nx, nm, nu, extras)``."""
            lo = self.layouts[i]
            nm = ubar / gamma_total
            s32 = self._slots32(state.slots, i)
            s32 = {**s32, **base.refresh_sync_slots(
                s32, anc32, ubar, gamma_total, lo, self.model_axes)}
            if use_anchor:
                # x_{t+1} = x_{t'} - precond(ubar): bitwise identical on
                # all workers (ubar, the anchor, and the slots are
                # replicated).
                nx = (anc32
                      - C.from_view(base.precond(ubar, s32), lo)
                      ).astype(xh_i.dtype)
            else:
                corr = base.precond(uh_i - ubar, s32)
                nx = (xh_i.astype(jnp.float32)
                      + C.from_view(corr, lo)).astype(xh_i.dtype)
            nu = jnp.zeros_like(uh_i)
            return nx, nm, nu, tuple(s32[name] for name in sync_names)

        # --- T_u: ONE Algorithm-2 exchange per unit, each under its own
        # cond whose operands are only that unit's member leaves + its
        # EF/anchor state. The exchange's collectives therefore depend on
        # nothing but those leaves' accumulated gradients, so with the
        # peeled last microbatch (train/step.py) XLA can issue unit k's
        # collective while later units' member gradients are still being
        # computed. Per-unit math is identical to the old monolithic
        # branch — bitwise, pinned by the golden-trajectory suite.
        def unit_sync_cond(unit):
            si = unit.state_idx
            op = (tuple(x_half[i] for i in unit.members),
                  tuple(m_half[i] for i in unit.members),
                  tuple(u_half[i] for i in unit.members),
                  state.err_w[si], state.err_s[si], state.anchor[si],
                  tuple(tuple(state.slots[name][i].astype(jnp.float32)
                              for name in sync_names)
                        for i in unit.members))

            @jax.named_scope(telemetry.OPT_SYNC_UPDATE)
            def sync_b(op):
                xh_m, mh_m, uh_m, ew, es, anc, _ = op
                z = self._unit_gather(unit, list(uh_m))
                ubar_u, ef = AR.onebit_allreduce_view(
                    comm, z, AR.EFState(ew, es), unit.layout, self.ar_cfg,
                    vspec=unit.vspec, worker_index=worker_index)
                ubars = self._unit_scatter(unit,
                                           ubar_u.astype(jnp.float32))
                if not use_anchor:
                    anc32s = [None] * len(unit.members)
                elif unit.bucket is None:
                    anc32s = [anc.astype(jnp.float32)]
                else:
                    anc32s = [C.from_view(av.astype(jnp.float32),
                                          self.layouts[i])
                              for av, i in zip(self._unit_scatter(unit,
                                                                  anc),
                                               unit.members)]
                nx_m, nm_m, nu_m, nex_m = [], [], [], []
                for k, i in enumerate(unit.members):
                    nx, nm, nu, nex = post_sync_leaf(
                        i, ubars[k].astype(jnp.float32), anc32s[k],
                        xh_m[k], uh_m[k])
                    nx_m.append(nx)
                    nm_m.append(nm)
                    nu_m.append(nu)
                    nex_m.append(nex)
                if not use_anchor:
                    na = anc
                elif unit.bucket is None:
                    na = nx_m[0]
                else:
                    na = self._unit_gather(
                        unit, [C.to_view(nx, self.layouts[i])
                               for nx, i in zip(nx_m, unit.members)]
                        ).astype(anc.dtype)
                return (tuple(nx_m), tuple(nm_m), tuple(nu_m),
                        ef.err_worker, ef.err_server, na, tuple(nex_m))

            def keep_b(op):
                return op

            return jax.lax.cond(do_sync, sync_b, keep_b, op)

        new_x, new_m = list(x_half), list(m_half)
        new_u = list(u_half)
        new_ew, new_es = list(state.err_w), list(state.err_s)
        new_anchor = list(state.anchor)
        new_sync_slots = {name: list(state.slots[name])
                          for name in sync_names}
        for unit in self.units:
            nx_m, nm_m, nu_m, nw, ns, na, nex_m = unit_sync_cond(unit)
            for k, i in enumerate(unit.members):
                new_x[i], new_m[i], new_u[i] = nx_m[k], nm_m[k], nu_m[k]
                for j, name in enumerate(sync_names):
                    new_sync_slots[name][i] = nex_m[k][j]
            new_ew[unit.state_idx] = nw
            new_es[unit.state_idx] = ns
            new_anchor[unit.state_idx] = na

        # --- T_v: full-precision variance refresh, also per unit -------
        if base.has_variance:
            def unit_var_cond(unit):
                @jax.named_scope(telemetry.OPT_VAR_ROUND)
                def var_b(vs_m):
                    gbars = self._fullprec_unit(
                        comm, unit, [gv[i] for i in unit.members])
                    return tuple(
                        base.update_variance(v.astype(jnp.float32), gb)
                        for v, gb in zip(vs_m, gbars))

                def keep_b(vs_m):
                    return tuple(v.astype(jnp.float32) for v in vs_m)

                return jax.lax.cond(
                    do_var, var_b, keep_b,
                    tuple(state.slots["v"][i] for i in unit.members))

            new_v = list(state.slots["v"])
            for unit in self.units:
                nv_m = unit_var_cond(unit)
                for k, i in enumerate(unit.members):
                    new_v[i] = nv_m[k].astype(state.slots["v"][i].dtype)
            # non-DP leaves: plain local base step (v every step)
            for i, dp in enumerate(dps):
                if dp:
                    continue
                v32 = state.slots["v"][i].astype(jnp.float32)
                new_v[i] = base.update_variance(v32, gv[i]).astype(
                    state.slots["v"][i].dtype)
        else:
            new_v = None

        new_gamma = jnp.where(do_sync, 0.0, gamma_total)
        sd = cfg.state_dtype
        new_slots = dict(state.slots)
        new_slots["m"] = [m.astype(sd) for m in new_m]
        if new_v is not None:
            new_slots["v"] = new_v
        for name in sync_names:
            new_slots[name] = new_sync_slots[name]
        new_state = CompressedDPState(
            step=t + 1,
            gamma_acc=new_gamma,
            sync_pstate=sync_ps,
            var_pstate=var_ps,
            slots=new_slots,
            u=[u.astype(sd) if u is not None else None for u in new_u],
            err_w=[w.astype(sd) if w is not None else None for w in new_ew],
            err_s=[s.astype(sd) if s is not None else None for s in new_es],
            anchor=new_anchor,
        )
        metrics = {"lr": lr, "synced": do_sync, "var_round": do_var,
                   "interval": interval}
        return jax.tree.unflatten(self.treedef, new_x), new_state, metrics

    # --- gradient / mean: sync the gradient itself every step ----------- #
    def _step_sync(self, comm, params, grads, state, worker_index):
        cfg, base = self.cfg, self.base
        t = state.step
        lr = cfg.lr(t).astype(jnp.float32)

        los, dps = self.layouts, self.dp_mask
        xs, gs = self.flat(params), self.flat(grads)
        gv = [C.constrain(C.to_view(g.astype(jnp.float32), lo), vs) if dp
              else g.astype(jnp.float32)
              for g, lo, dp, vs in zip(gs, los, dps, self.vspecs)]
        dp_idx = [i for i, dp in enumerate(dps) if dp]

        if cfg.style == "gradient":
            if self._use_var_policy:
                do_var, var_ps = cfg.var_policy.step(
                    state.var_pstate, t, jnp.ones((), jnp.int32))
            else:
                do_var, var_ps = jnp.asarray(False), state.var_pstate

            # One cond per exchange unit (see _step_accumulate): the
            # warmup round's full-precision exchange and the 1-bit round
            # both issue unit-by-unit, each depending only on that unit's
            # member gradients.
            def unit_grad_cond(unit):
                si = unit.state_idx
                op = (tuple(gv[i] for i in unit.members),
                      state.err_w[si], state.err_s[si])

                def full_b(op):
                    gs_m, ew, es = op
                    outs = self._fullprec_unit(comm, unit, list(gs_m))
                    return (tuple(o.astype(jnp.float32) for o in outs),
                            ew, es)

                @jax.named_scope(telemetry.OPT_SYNC_UPDATE)
                def onebit_b(op):
                    gs_m, ew, es = op
                    z = self._unit_gather(unit, list(gs_m))
                    o, ef = AR.onebit_allreduce_view(
                        comm, z, AR.EFState(ew, es), unit.layout,
                        self.ar_cfg, vspec=unit.vspec,
                        worker_index=worker_index)
                    outs = self._unit_scatter(unit, o)
                    return (tuple(v.astype(jnp.float32) for v in outs),
                            ef.err_worker, ef.err_server)

                return jax.lax.cond(do_var, full_b, onebit_b, op)

            gbar = list(gv)
            new_ew, new_es = list(state.err_w), list(state.err_s)
            for unit in self.units:
                outs_m, nw, ns = unit_grad_cond(unit)
                for k, i in enumerate(unit.members):
                    gbar[i] = outs_m[k]
                new_ew[unit.state_idx] = nw
                new_es[unit.state_idx] = ns
        else:  # mean: uncompressed baseline, no EF state at all
            do_var = jnp.asarray(base.has_variance)
            var_ps = state.var_pstate
            agg_dp = self._fullprec_dp(comm, [gv[i] for i in dp_idx])
            new_ew, new_es = list(state.err_w), list(state.err_s)
            gbar = list(gv)
            for k, i in enumerate(dp_idx):
                gbar[i] = agg_dp[k]

        wd = cfg.weight_decay
        new_x = []
        new_slots = {name: list(vals) for name, vals in state.slots.items()}
        for i, (x, g, lo, dp) in enumerate(zip(xs, gbar, los, dps)):
            s32 = self._slots32(state.slots, i)
            m32 = s32["m"]
            nm = base.beta1 * m32 + (1 - base.beta1) * g
            if base.has_variance:
                v32 = s32["v"]
                if dp and cfg.style == "gradient":
                    nv = jnp.where(do_var, base.update_variance(v32, g), v32)
                else:  # mean style / local leaves: v every step
                    nv = base.update_variance(v32, g)
                new_slots["v"][i] = nv.astype(state.slots["v"][i].dtype)
            x32 = x.astype(jnp.float32)
            if base.has_trust:
                # LAMB: trust ratio from the *unscaled* update so the lr
                # schedule keeps control of the step size
                upd = base.precond_raw(nm, s32)
                upd = C.from_view(upd, lo) if dp else upd
                if wd:
                    upd = upd + wd * x32
                trust = base.trust_ratio(x32, upd, self.model_axes)
                delta = lr * trust * upd
            else:
                delta = base.precond(lr * nm, s32)
                delta = C.from_view(delta, lo) if dp else delta
                if wd:
                    delta = delta + lr * wd * x32
            new_x.append((x32 - delta).astype(x.dtype))
            new_slots["m"][i] = nm.astype(state.slots["m"][i].dtype)

        metrics = {"lr": lr, "synced": jnp.asarray(True),
                   "var_round": do_var,
                   "interval": jnp.ones((), jnp.int32)}
        new_state = CompressedDPState(
            step=t + 1, gamma_acc=state.gamma_acc,
            sync_pstate=state.sync_pstate, var_pstate=var_ps,
            slots=new_slots, u=list(state.u), err_w=new_ew, err_s=new_es,
            anchor=list(state.anchor))
        return jax.tree.unflatten(self.treedef, new_x), new_state, metrics
