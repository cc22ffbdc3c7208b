"""Trainer: builds the distributed train step for any registered arch.

Three execution modes share one per-worker step function:

  * ``mesh``   — partial-manual ``jax.shard_map``: manual over the worker
    axes (the paper's communication pattern, hand-written collectives),
    GSPMD-auto over 'model' (tensor parallelism via sharding constraints).
    This is the multi-chip path (``launch/train.py --mode mesh``) and the
    dry-run's.
  * ``sim``    — ``jax.vmap(axis_name=...)`` materializes n workers on one
    device; identical collectives run through the vmap axis. Used by the
    convergence tests/benchmarks (paper Fig. 2) and the one-chip run.
  * ``single`` — one worker, NullComm. CPU smoke tests.

Parameters/optimizer state carry a leading worker axis for DP-replicated
leaves (each DP group's local-step replica); expert-parallel leaves are
split across workers on their expert axis (see train/sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api as opt_api
from repro.core.comm import (Comm, NullComm, mesh_comm, norm_hierarchy,
                             sim_comm)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import (dp_mask as tmpl_dp_mask, init_params,
                                 is_pd, param_specs)
from repro.train.sharding import TreeSpecs


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    micro_batches: int = 1
    worker_axes: Tuple[str, ...] = ("data",)
    donate: bool = True
    # Peel the final microbatch out of the accumulation scan so its
    # per-leaf gradient completions are visible dataflow: each exchange
    # unit's collectives (issued under their own per-unit cond in
    # repro.core.compressed) then depend only on that unit's member
    # leaves, and XLA's latency-hiding scheduler can overlap early units'
    # exchanges with the rest of the last backward. Bitwise-identical to
    # the full scan (same accumulation association order); False keeps
    # the sequential all-scanned path (used to regenerate goldens and by
    # the overlapped-vs-sequential parity tests).
    peel_last_microbatch: bool = True
    # Meshless tensor parallelism: model_shards > 1 binds a manual 'model'
    # axis of that size with no mesh attached, so the optimizer plans
    # TP-LOCAL force-flatten layouts (rest_factor = model_shards, sharded
    # fused buckets) exactly as the fully-manual mesh path would. Only the
    # abstract paths run in this regime — ``analysis.ir_audit`` traces the
    # per-worker step under an abstract mesh that binds 'model' — the
    # executable sim/single step functions refuse it (a vmap sim has no
    # 'model' axis for the exchange's psums to resolve against).
    model_shards: int = 0

    def __post_init__(self):
        if self.micro_batches < 1:
            raise ValueError(
                f"micro_batches must be >= 1, got "
                f"{self.micro_batches!r}")
        if self.model_shards < 0 or self.model_shards == 1:
            raise ValueError(
                f"model_shards must be 0 (off) or >= 2, got "
                f"{self.model_shards!r}")


def accumulate_grads(loss_fn, params, batch, micro_batches, *, peel=True):
    """Mean loss/gradients over ``micro_batches`` splits of the per-worker
    batch (leading axis). ``loss_fn(params, microbatch) -> (loss, aux)``.

    With ``peel=True`` the last microbatch runs unrolled after a scan over
    the first ``micro_batches - 1`` — the same sum in the same association
    order (bitwise-identical to the full scan), but the final backward's
    per-leaf gradients are individual equations instead of one opaque scan
    output, which is what lets the per-unit exchange issue early.
    """
    mb = micro_batches
    if mb <= 1:
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    for path, x in jax.tree_util.tree_flatten_with_path(batch)[0]:
        if x.shape[0] % mb:
            raise ValueError(
                f"per-worker batch leaf {jax.tree_util.keystr(path)} has "
                f"{x.shape[0]} rows, which is not divisible by "
                f"micro_batches={mb}; choose a global batch size divisible "
                f"by n_workers * micro_batches")

    def resh(x):
        return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

    mbs = jax.tree.map(resh, batch)

    def acc(carry, b_):
        gsum, lsum = carry
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b_)
        gsum = jax.tree.map(jnp.add, gsum, g)
        return (gsum, lsum + l), None

    g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    init = (g0, jnp.zeros(()))
    if peel:
        head = jax.tree.map(lambda x: x[:-1], mbs)
        last = jax.tree.map(lambda x: x[-1], mbs)
        carry, _ = jax.lax.scan(acc, init, head)
        (gsum, lsum), _ = acc(carry, last)
    else:
        (gsum, lsum), _ = jax.lax.scan(acc, init, mbs)
    grads = jax.tree.map(lambda g: g / mb, gsum)
    return lsum / mb, grads


class Trainer:
    """Holds the static plan: templates, specs, optimizer, jitted step."""

    def __init__(self, model_cfg: ModelConfig, opt_cfg, *, mesh=None,
                 n_workers: Optional[int] = None,
                 trainer_cfg: TrainerConfig = TrainerConfig()):
        self.model_cfg = model_cfg
        self.opt_cfg = opt_cfg
        self.mesh = mesh
        self.tc = trainer_cfg
        W = trainer_cfg.worker_axes
        if mesh is not None:
            n_workers = 1
            for a in W:
                n_workers = n_workers * mesh.shape[a]
        self.n_workers = n_workers or 1

        # Two-level (intra-pod x inter-pod) topology for the compressed
        # optimizer exchange. In mesh mode the hierarchy must name a split
        # of the worker axes; in sim mode both levels are materialized as
        # nested vmap axes carrying the same names. (opt_cfg is either an
        # OptimizerConfig or an unbound compressed_dp transform — both
        # always carry the hierarchy field.)
        self.hierarchy = norm_hierarchy(opt_cfg.hierarchy, self.n_workers)
        if self.hierarchy is not None:
            h = self.hierarchy
            if mesh is not None:
                if h.axes != tuple(W):
                    raise ValueError(
                        f"hierarchy axes {h.axes} must equal the worker "
                        f"axes {tuple(W)}")
                inner = 1
                for a in h.inner_axes:
                    inner *= mesh.shape[a]
                if inner != h.inner:
                    raise ValueError(
                        f"hierarchy.inner={h.inner} != mesh inner-axes "
                        f"product {inner}")
            elif len(h.outer_axes) != 1 or len(h.inner_axes) != 1:
                raise ValueError("sim mode materializes one vmap axis per "
                                 "hierarchy level (one outer + one inner "
                                 "axis name)")

        # Expert parallelism spans the largest suffix of the worker axes
        # whose size divides the expert count (llama4: 16 experts -> EP over
        # 'data' only on the 2x16x16 mesh, replicated over 'pod' with the
        # residual-axis gradient pmean in _ep_scale_grads).
        self.ep_axes, self.ep_degree = self._choose_ep(W)
        self.template = T.model_template(model_cfg,
                                         ep_workers=self.ep_degree)
        self.pd_leaves, self.treedef = jax.tree.flatten(
            self.template, is_leaf=is_pd)
        # The optimizer runs in the FULLY-manual domain: manual over the
        # worker axes (outer shard_map) AND over 'model' (nested shard_map in
        # _per_worker_step) — every op is chip-local except the worker-axis
        # collectives, so GSPMD never re-gathers the comm views.
        if mesh is not None and "model" in mesh.axis_names:
            self.model_axes = ("model",)
            self.model_sizes = {"model": mesh.shape["model"]}
        elif mesh is None and trainer_cfg.model_shards > 1:
            # meshless sim-TP (TrainerConfig.model_shards): same manual
            # 'model' planning domain as the fully-manual mesh path —
            # TP-local layouts, sharded fused buckets, model-axis psums —
            # resolved against the abstract mesh the auditor binds. Works
            # on any jax version because the abstract trace never reaches
            # the XLA partitioner.
            self.model_axes = ("model",)
            self.model_sizes = {"model": trainer_cfg.model_shards}
        else:
            self.model_axes, self.model_sizes = (), {}
        # per-worker local shapes: EP leaves divide their expert axis
        self.local_abstract = self._local_abstract()
        # worker+model local shapes (what the optimizer sees)
        self.inner_abstract = self._inner_abstract()
        specs_tree = param_specs(self.template)
        dpm_tree = tmpl_dp_mask(self.template)
        self.opt = opt_api.build_optimizer(
            opt_cfg, self.inner_abstract, specs=specs_tree,
            dp_mask=dpm_tree, n_workers=self.n_workers,
            model_axis_sizes=self.model_sizes)
        self.tree_specs = TreeSpecs(self.opt, self.pd_leaves, W,
                                    ep_axes=self.ep_axes)

    # ------------------------------------------------------------------ #
    def _choose_ep(self, W):
        """(ep_axes suffix, ep_degree): largest suffix of the worker axes
        whose total size divides the expert count."""
        if self.mesh is not None:
            names, sizes = list(W), [self.mesh.shape[a] for a in W]
        elif self.hierarchy is not None:  # sim: one vmap axis per level
            h = self.hierarchy
            names = list(h.axes)
            sizes = [self.n_workers // h.inner, h.inner]
        else:  # sim / single: one logical worker axis
            names, sizes = ["workers"], [self.n_workers]
        self._worker_axis_names = tuple(names)
        E = self.model_cfg.n_experts
        if not E:
            return (), 1
        for start in range(len(names) + 1):
            deg = 1
            for s in sizes[start:]:
                deg *= s
            if E % deg == 0:
                return tuple(names[start:]), deg
        return (), 1

    def _residual_axes(self):
        names = getattr(self, "_worker_axis_names", self.tc.worker_axes)
        return tuple(a for a in names if a not in self.ep_axes)

    def _local_abstract(self):
        n = self.ep_degree
        dt = self.model_cfg.param_dtype

        def f(pd):
            shape = list(pd.shape)
            if not pd.dp and pd.ep_axis is not None and n > 1:
                ax = pd.ep_axis
                assert shape[ax] % n == 0, (pd.shape, ax, n)
                shape[ax] = shape[ax] // n
            return jax.ShapeDtypeStruct(tuple(shape), dt)

        return jax.tree.map(f, self.template, is_leaf=is_pd)

    def _shrink_model(self, shape, spec):
        """Divide tensor-parallel-sharded dims by the model axis size."""
        if not self.model_sizes:
            return tuple(shape)
        entries = tuple(spec) if spec is not None else ()
        out = list(shape)
        for ax, e in enumerate(entries):
            if e is None or ax >= len(out):
                continue
            f = 1
            for name in (e if isinstance(e, tuple) else (e,)):
                f *= self.model_sizes.get(name, 1)
            assert out[ax] % f == 0, (shape, spec, f)
            out[ax] = out[ax] // f
        return tuple(out)

    def _grow_model(self, shape, entries):
        if not self.model_sizes or entries is None:
            return tuple(shape)
        out = list(shape)
        for ax, e in enumerate(tuple(entries)[:len(out)]):
            if e is None:
                continue
            f = 1
            for name in (e if isinstance(e, tuple) else (e,)):
                f *= self.model_sizes.get(name, 1)
            out[ax] = out[ax] * f
        return tuple(out)

    def _inner_abstract(self):
        ll, ldef = jax.tree.flatten(self.local_abstract)
        out = []
        for loc, pd in zip(ll, self.pd_leaves):
            shape = self._shrink_model(loc.shape, pd.spec)
            out.append(jax.ShapeDtypeStruct(shape, loc.dtype))
        return jax.tree.unflatten(ldef, out)

    def _ep_scale_grads(self, grads, comm):
        """EP-leaf grads arrive as sums over the EP axes (a2a transpose):
        pmean over the residual (replication) axes, then divide by the EP
        degree to match the mean-loss objective."""
        if self.n_workers == 1:
            return grads
        res = self._residual_axes()
        gl = self.treedef.flatten_up_to(grads)
        out = []
        for g, pd in zip(gl, self.pd_leaves):
            if pd.dp:
                out.append(g)
                continue
            if res and not isinstance(comm, NullComm) and comm.axes:
                g = jax.lax.pmean(g, res if len(res) > 1 else res[0])  # audit-ok: raw-collective
            out.append(g / self.ep_degree)
        return jax.tree.unflatten(self.treedef, out)

    # ------------------------------------------------------------------ #
    def _per_worker_step(self, comm: Comm, params_local, opt_state, batch,
                         ep_comm: Optional[Comm] = None):
        """params_local: DP leaves WITH leading worker dim of size 1."""
        p = self._squeeze(params_local)
        mb = self.tc.micro_batches
        if ep_comm is None:
            ep_comm = (Comm(self.ep_axes) if self.ep_axes
                       and not isinstance(comm, NullComm) else NullComm())

        def loss_fn(p_, b_):
            loss, met = T.lm_loss(p_, self.model_cfg, b_, comm=ep_comm)
            return loss, met

        loss, grads = accumulate_grads(
            loss_fn, p, batch, mb, peel=self.tc.peel_last_microbatch)

        grads = self._ep_scale_grads(grads, comm)
        widx = (comm.index() if not isinstance(comm, NullComm)
                else jnp.zeros((), jnp.int32))

        def opt_apply(p_, g_, s_, w_):
            return self.opt.step(comm, p_, g_, s_, worker_index=w_)

        if self.model_axes:
            pm = jax.tree.unflatten(self.treedef,
                                    self.tree_specs.params_model())
            sm = self.tree_specs.state_model_specs()
            # nested inside the worker-axes shard_map, so the mesh is the
            # ambient one: the physical mesh, or the abstract mesh that
            # ir_audit traces meshless TP under
            opt_apply = jax.shard_map(
                opt_apply, in_specs=(pm, pm, sm, P()),
                out_specs=(pm, sm, P()),
                axis_names=set(self.model_axes), check_vma=False)

        new_p, new_opt, met = opt_apply(p, grads, opt_state, widx)
        met["loss"] = comm.pmean(loss)
        return self._unsqueeze(new_p), new_opt, met

    def _squeeze(self, params):
        pl = self.treedef.flatten_up_to(params)
        out = [x[0] if pd.dp else x for x, pd in zip(pl, self.pd_leaves)]
        return jax.tree.unflatten(self.treedef, out)

    def _unsqueeze(self, params):
        pl = self.treedef.flatten_up_to(params)
        out = [x[None] if pd.dp else x for x, pd in zip(pl, self.pd_leaves)]
        return jax.tree.unflatten(self.treedef, out)

    def _is_per_worker_spec(self, s):
        ent = tuple(s)
        if not ent or ent[0] is None:
            return False
        first = ent[0] if isinstance(ent[0], tuple) else (ent[0],)
        return first == tuple(self.tc.worker_axes)

    def _squeeze_state(self, state, inner_specs):
        def f(x, s):
            return x[0] if self._is_per_worker_spec(s) else x
        return jax.tree.map(f, state, inner_specs)

    def _unsqueeze_state(self, state, inner_specs):
        def f(x, s):
            return x[None] if self._is_per_worker_spec(s) else x
        return jax.tree.map(f, state, inner_specs)

    # ------------------------------------------------------------------ #
    # mesh (production) mode
    # ------------------------------------------------------------------ #
    def mesh_step_fn(self):
        """jit(shard_map(step)) for the mesh, plus shardings."""
        assert self.mesh is not None
        W = self.tc.worker_axes
        comm = mesh_comm(W)
        pi = self._params_inner_specs_tree()
        _, si = self.tree_specs.state_specs()
        batch_i = P(W)
        batch_f = P(W)

        def body(params, opt_state, batch):
            opt_local = self._squeeze_state(opt_state, si)
            new_p, new_s, met = self._per_worker_step(
                comm, params, opt_local, batch)
            return new_p, self._unsqueeze_state(new_s, si), met

        shmapped = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(pi, si, batch_i),
            out_specs=(pi, si, P()),
            axis_names=set(W), check_vma=False)

        shardings = self._mesh_shardings()
        donate = (0, 1) if self.tc.donate else ()
        fn = jax.jit(
            shmapped,
            in_shardings=(shardings["params"], shardings["state"],
                          NamedSharding(self.mesh, batch_f)),
            out_shardings=(shardings["params"], shardings["state"], None),
            donate_argnums=donate)
        return fn, shardings

    def _mesh_shardings(self):
        sf, _ = self.tree_specs.state_specs()
        return {
            "params": self.tree_specs.shardings(
                self.mesh, self._params_full_specs_tree()),
            "state": self.tree_specs.shardings(self.mesh, sf),
        }

    def mesh_init(self, key):
        """(params, opt_state) laid out and placed for ``mesh_step_fn``:
        the same values ``sim_init`` makes, each worker's state built on
        its own devices from its local parameters."""
        assert self.mesh is not None
        W = self.tc.worker_axes
        n = self.n_workers
        pi = self._params_inner_specs_tree()
        _, si = self.tree_specs.state_specs()
        init = self.opt.init
        if self.model_axes:
            pm = jax.tree.unflatten(self.treedef,
                                    self.tree_specs.params_model())
            init = jax.shard_map(
                init, in_specs=(pm,),
                out_specs=self.tree_specs.state_model_specs(),
                axis_names=set(self.model_axes), check_vma=False)

        def body(params):
            return self._unsqueeze_state(init(self._squeeze(params)), si)

        def make(params):
            pl = self.treedef.flatten_up_to(params)
            pl = [jnp.broadcast_to(x[None], (n,) + x.shape) if pd.dp else x
                  for x, pd in zip(pl, self.pd_leaves)]
            params = jax.tree.unflatten(self.treedef, pl)
            state = jax.shard_map(body, mesh=self.mesh, in_specs=(pi,),
                                  out_specs=si, axis_names=set(W),
                                  check_vma=False)(params)
            return params, state

        sh = self._mesh_shardings()
        # drawn outside the jit, as sim_init does: XLA fuses the jitted
        # normal sampler differently and the draws would differ by an ulp
        params = init_params(self.template, key,
                             dtype=self.model_cfg.param_dtype)
        return jax.jit(make, out_shardings=(sh["params"], sh["state"]))(
            params)

    def _params_full_specs_tree(self):
        return jax.tree.unflatten(self.treedef,
                                  self.tree_specs.params_full())

    def _params_inner_specs_tree(self):
        return jax.tree.unflatten(self.treedef,
                                  self.tree_specs.params_inner())

    def abstract_inputs(self, global_batch: int, seq: int,
                        extra_fn=None):
        """ShapeDtypeStructs for (params, opt_state, batch) — the dry-run
        inputs. Nothing is allocated."""
        pl = []
        for pd, loc in zip(self.pd_leaves,
                           jax.tree.leaves(self.local_abstract)):
            if pd.dp:
                pl.append(jax.ShapeDtypeStruct(
                    (self.n_workers,) + loc.shape, loc.dtype))
            else:
                ax = pd.ep_axis or 0
                shape = list(loc.shape)
                shape[ax] = shape[ax] * self.ep_degree
                pl.append(jax.ShapeDtypeStruct(tuple(shape), loc.dtype))
        params = jax.tree.unflatten(self.treedef, pl)

        inner_params = jax.tree.unflatten(
            self.treedef, list(jax.tree.leaves(self.inner_abstract)))
        state_local = jax.eval_shape(self.opt.init, inner_params)
        state = self._stack_state_abstract(state_local)

        batch = {"tokens": jax.ShapeDtypeStruct((global_batch, seq),
                                                jnp.int32),
                 "labels": jax.ShapeDtypeStruct((global_batch, seq),
                                                jnp.int32)}
        if extra_fn is not None:
            batch.update(extra_fn(global_batch, seq, self.model_cfg))
        return params, state, batch

    def _stack_state_abstract(self, state_local):
        """Globalize abstract state: grow model-sharded dims back to global,
        add the worker axis to per-worker (DP) leaves, re-globalize the
        expert axis of EP leaves. Fully generic: driven by the optimizer's
        ``state_kinds()`` tags, so any composed optimizer (any base, any
        style) globalizes without per-class branching."""
        n = self.n_workers
        kinds = self.opt.state_kinds()
        model_specs = self.tree_specs.state_model_specs()

        def glob(x, k, ms):
            if k.tag == "scalar":
                return x
            shape = self._grow_model(x.shape, tuple(ms) if ms else None)
            if k.bucketed:
                # bucket-shaped state (EF / anchors): buckets only cover DP
                # leaves, so the state is always per-worker stacked
                return jax.ShapeDtypeStruct((n,) + shape, x.dtype)
            pd = self.pd_leaves[k.leaf]
            if pd.dp:
                return jax.ShapeDtypeStruct((n,) + shape, x.dtype)
            ax = pd.ep_axis or 0
            shape = list(shape)
            shape[ax] = shape[ax] * self.ep_degree
            return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

        return jax.tree.map(glob, state_local, kinds, model_specs)

    def _no_meshless_tp(self, mode: str) -> None:
        """The executable sim/single paths cannot honor meshless TP: their
        vmap/NullComm traces bind no 'model' axis, so the exchange's
        model-axis psums (and the TP-local state shapes) have nothing to
        resolve against. Only the abstract paths (ir_audit) run there."""
        if self.model_sizes and self.mesh is None:
            raise ValueError(
                f"TrainerConfig.model_shards="
                f"{self.model_sizes.get('model')} is abstract-trace-only "
                f"(analysis.ir_audit); the executable {mode} path has no "
                f"'model' axis to bind — use a mesh with a 'model' axis "
                f"instead")

    # ------------------------------------------------------------------ #
    # single-worker mode (CPU smoke)
    # ------------------------------------------------------------------ #
    def single_init(self, key):
        self._no_meshless_tp("single")
        params = init_params(self.template, key,
                             dtype=self.model_cfg.param_dtype)
        pl = self.treedef.flatten_up_to(params)
        pl = [x[None] if pd.dp else x for x, pd in zip(pl, self.pd_leaves)]
        params = jax.tree.unflatten(self.treedef, pl)
        state = self.opt.init(self._squeeze(params))
        return params, state

    def single_step_fn(self):
        self._no_meshless_tp("single")
        comm = NullComm()

        @jax.jit
        def fn(params, opt_state, batch):
            return self._per_worker_step(comm, params, opt_state, batch)

        return fn

    # ------------------------------------------------------------------ #
    # sim mode (n workers on one device via vmap)
    # ------------------------------------------------------------------ #
    def sim_init(self, key):
        self._no_meshless_tp("sim")
        n = self.n_workers
        params = init_params(self.template, key,
                             dtype=self.model_cfg.param_dtype)
        pl = self.treedef.flatten_up_to(params)
        out = []
        for x, pd in zip(pl, self.pd_leaves):
            if pd.dp:
                out.append(jnp.broadcast_to(x[None], (n,) + x.shape) + 0)
            else:  # split expert axis across simulated workers
                ax = pd.ep_axis or 0
                xs = jnp.moveaxis(
                    x.reshape(x.shape[:ax] + (n, x.shape[ax] // n)
                              + x.shape[ax + 1:]), ax, 0)
                out.append(xs)
        params = jax.tree.unflatten(self.treedef, out)
        # per-worker init (worker-dependent for EP slices / anchors)
        state = jax.vmap(lambda i: self.opt.init(
            jax.tree.map(lambda x: x[i], params)))(jnp.arange(n))
        return params, state

    def _sim_local(self, params, i):
        return jax.tree.map(lambda x: x[i], params)

    def _one_worker_fn(self, comm):
        """Per-worker step on worker-local trees (shared by sim's vmap and
        the hierarchical nested vmap)."""

        def one(params_i, state_i, batch_i):
            # params_i: DP leaves (shape local), EP leaves local slice
            pl = self.treedef.flatten_up_to(params_i)
            pl = [x[None] if pd.dp else x
                  for x, pd in zip(pl, self.pd_leaves)]
            p = jax.tree.unflatten(self.treedef, pl)
            new_p, new_s, met = self._per_worker_step(comm, p, state_i,
                                                      batch_i)
            npl = self.treedef.flatten_up_to(new_p)
            npl = [x[0] if pd.dp else x
                   for x, pd in zip(npl, self.pd_leaves)]
            return jax.tree.unflatten(self.treedef, npl), new_s, met

        return one

    def sim_step_fn(self):
        self._no_meshless_tp("sim")
        n = self.n_workers
        h = self.hierarchy
        if h is None:
            axes, sizes = ("workers",), (n,)
        else:
            # materialize both topology levels so Comm.split sees real axes
            axes = h.axes
            sizes = (n // h.inner, h.inner)
        comm = Comm(axes) if len(axes) > 1 else sim_comm(axes[0])
        one = self._one_worker_fn(comm)
        mapped = one
        for name in reversed(axes):
            mapped = jax.vmap(mapped, axis_name=name)

        def fn(params, state, batch):
            # batch: (GB, S) -> per-worker (*sizes, GB/n, S); the stacked
            # params/state keep their flat leading worker axis externally
            # (outer-major = the flattened collective order) and are only
            # reshaped around the nested vmap
            def resh_b(x):
                return x.reshape(sizes + (x.shape[0] // n,) + x.shape[1:])

            def lead(x):
                return x.reshape(sizes + x.shape[1:])

            def unlead(x):
                return x.reshape((n,) + x.shape[len(sizes):])

            b = jax.tree.map(resh_b, batch)
            if len(sizes) == 1:
                return mapped(params, state, b)
            p2 = jax.tree.map(lead, params)
            s2 = jax.tree.map(lead, state)
            new_p, new_s, met = mapped(p2, s2, b)
            return (jax.tree.map(unlead, new_p),
                    jax.tree.map(unlead, new_s),
                    jax.tree.map(unlead, met))

        return jax.jit(fn, donate_argnums=(0, 1) if self.tc.donate else ())
