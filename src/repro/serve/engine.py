"""Serving engine: prefill + single-token decode with sharded KV caches.

Non-MoE architectures serve under plain ``jit`` with GSPMD-auto sharding;
MoE architectures serve under the partial-manual ``shard_map`` so the
expert-parallel token exchange is the explicit a2a (same code path as
training). Cache sharding policy:

  * batch >= #workers: batch over the worker axes, sequence over 'model'
    (keeps the 32k x big-head caches on-chip);
  * batch == 1 (long_500k): sequence over ALL axes — decode of one token
    against a 512k-token cache is O(S) compute, sequence-sharded memory.

SSM/hybrid states shard their head axis over 'model'.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.comm import NullComm, mesh_comm
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import is_pd


def _div(n, k):
    return k > 0 and n % k == 0


class Server:
    def __init__(self, model_cfg: ModelConfig, *, mesh=None,
                 worker_axes: Tuple[str, ...] = ("data",),
                 batch: int = 1, max_seq: int = 2048,
                 cache_dtype=jnp.bfloat16):
        self.cfg = model_cfg
        self.mesh = mesh
        self.W = worker_axes
        self.batch = batch
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        self.n_workers = 1
        if mesh is not None:
            for a in worker_axes:
                self.n_workers *= mesh.shape[a]
        self.is_moe = model_cfg.n_experts > 0
        # expert parallelism over the largest worker-axis suffix dividing E
        self.ep_axes, self.ep_degree = (), 1
        if self.is_moe and mesh is not None:
            names = list(worker_axes)
            sizes = [mesh.shape[a] for a in names]
            for start in range(len(names) + 1):
                deg = 1
                for s in sizes[start:]:
                    deg *= s
                if model_cfg.n_experts % deg == 0:
                    self.ep_axes, self.ep_degree = tuple(names[start:]), deg
                    break
        self.template = T.model_template(model_cfg,
                                         ep_workers=self.ep_degree)

    # ------------------------------------------------------------------ #
    def param_shardings(self):
        """Serving holds ONE copy of the params: dense leaves replicated
        over the worker axes + TP over model; EP leaves expert-sharded."""
        mesh = self.mesh

        def f(pd):
            entries = tuple(pd.spec) if pd.spec else (None,) * len(pd.shape)
            if (not pd.dp and pd.ep_axis is not None and self.is_moe
                    and self.ep_axes):
                ax = pd.ep_axis
                entries = (entries[:ax] + (self.ep_axes,)
                           + entries[ax + 1:])
            return NamedSharding(mesh, P(*entries))

        return jax.tree.map(f, self.template, is_leaf=is_pd)

    def abstract_params(self, dtype=jnp.bfloat16):
        def f(pd):
            return jax.ShapeDtypeStruct(tuple(pd.shape), dtype)

        return jax.tree.map(f, self.template, is_leaf=is_pd)

    # ------------------------------------------------------------------ #
    def cache_shardings(self):
        cfg, mesh, W = self.cfg, self.mesh, self.W
        B = self.batch
        batch_ok = B % self.n_workers == 0 and B >= self.n_workers
        seq_axes = "model" if batch_ok else tuple(mesh.axis_names)

        def kv(ndim_prefix):
            # (L?, B, S, K, hd) — prefix covers the layer/app axis
            if batch_ok:
                return P(*([None] * ndim_prefix), W, seq_axes, None, None)
            return P(*([None] * ndim_prefix), None, seq_axes, None, None)

        if cfg.family in ("ssm", "hybrid"):
            hshard = "model" if _div(cfg.ssm_heads, 16) else None
            sh = {"ssm": {
                "h": P(None, W if batch_ok else None, hshard, None, None),
                "conv_x": P(None, W if batch_ok else None, None, "model"),
                "conv_B": P(None, W if batch_ok else None, None, None),
                "conv_C": P(None, W if batch_ok else None, None, None),
            }}
            if cfg.attn_every:
                sh["shared"] = {"k": kv(1), "v": kv(1)}
            return jax.tree.map(lambda s: NamedSharding(mesh, s), sh,
                                is_leaf=lambda x: isinstance(x, P))
        if cfg.attn_type == "mla":
            sh = {"ckv": P(None, W if batch_ok else None, seq_axes, None),
                  "kr": P(None, W if batch_ok else None, seq_axes, None)}
        elif cfg.window_cache and cfg.sliding_window and cfg.global_every:
            # ring buffers are small: batch-shard only; global stack as kv()
            lkv = P(None, W if batch_ok else None, None, None, None)
            sh = {"local": {"k": lkv, "v": lkv},
                  "global": {"k": kv(1), "v": kv(1)}}
        else:
            sh = {"k": kv(1), "v": kv(1)}
        return jax.tree.map(lambda s: NamedSharding(mesh, s), sh,
                            is_leaf=lambda x: isinstance(x, P))

    def abstract_cache(self):
        return jax.eval_shape(
            lambda: T.init_cache(self.cfg, self.batch, self.max_seq,
                                 self.cache_dtype))

    # ------------------------------------------------------------------ #
    def _comm(self):
        if self.mesh is None or not self.is_moe:
            return NullComm() if self.is_moe else None
        return mesh_comm(self.W)

    def prefill_fn(self):
        cfg = self.cfg

        def run(params, batch, cache, comm=None):
            return T.prefill(params, cfg, batch, cache, comm=comm)

        if self.mesh is None:
            comm = NullComm() if self.is_moe else None
            return jax.jit(functools.partial(run, comm=comm),
                           donate_argnums=(2,))
        if not self.is_moe:
            ps = self.param_shardings()
            cs = self.cache_shardings()
            bs = self._batch_sharding(prefill=True)
            return jax.jit(run, in_shardings=(ps, bs, cs),
                           out_shardings=(None, cs), donate_argnums=(2,))
        # MoE: shard_map manual over worker axes for the EP dispatch
        comm = (mesh_comm(self.ep_axes) if self.ep_axes else NullComm())
        W = self.W

        def body(params, batch, cache):
            return T.prefill(params, cfg, batch, cache, comm=comm)

        ep = self.ep_axes
        pi = jax.tree.map(
            lambda pd: (P(*((None,) * (pd.ep_axis or 0)), ep)
                        if (not pd.dp and pd.ep_axis is not None and ep)
                        else P()),
            self.template, is_leaf=is_pd)
        ci = jax.tree.map(lambda _: P(None, W), self.abstract_cache())
        bi = P(W)
        shm = jax.shard_map(body, mesh=self.mesh, in_specs=(pi, bi, ci),
                            out_specs=(P(W), ci), axis_names=set(W),
                            check_vma=False)
        ps = self.param_shardings()
        cs = self.cache_shardings()
        bs = self._batch_sharding(prefill=True)
        return jax.jit(shm, in_shardings=(ps, bs, cs),
                       out_shardings=(None, cs), donate_argnums=(2,))

    def decode_fn(self):
        cfg = self.cfg

        def run(params, cache, tokens, pos, enc_out=None, comm=None):
            return T.decode(params, cfg, tokens, cache, pos, comm=comm,
                            enc_out=enc_out)

        if self.mesh is None:
            comm = NullComm() if self.is_moe else None
            return jax.jit(functools.partial(run, comm=comm),
                           donate_argnums=(1,))
        if not self.is_moe:
            ps = self.param_shardings()
            cs = self.cache_shardings()
            ins = (ps, cs, None, None) + ((None,) if cfg.enc_layers else ())
            return jax.jit(run, in_shardings=ins,
                           out_shardings=(None, cs), donate_argnums=(1,))
        comm = (mesh_comm(self.ep_axes) if self.ep_axes else NullComm())
        W = self.W

        def body(params, cache, tokens, pos):
            return T.decode(params, cfg, tokens, cache, pos, comm=comm)

        ep = self.ep_axes
        pi = jax.tree.map(
            lambda pd: (P(*((None,) * (pd.ep_axis or 0)), ep)
                        if (not pd.dp and pd.ep_axis is not None and ep)
                        else P()),
            self.template, is_leaf=is_pd)
        ci = jax.tree.map(lambda _: P(None, W), self.abstract_cache())
        shm = jax.shard_map(body, mesh=self.mesh,
                            in_specs=(pi, ci, P(W), P()),
                            out_specs=(P(W), ci), axis_names=set(W),
                            check_vma=False)
        ps = self.param_shardings()
        cs = self.cache_shardings()
        return jax.jit(shm, in_shardings=(ps, cs, None, None),
                       out_shardings=(None, cs), donate_argnums=(1,))

    def _batch_sharding(self, prefill: bool):
        B = self.batch
        if B % self.n_workers == 0 and B >= self.n_workers:
            return NamedSharding(self.mesh, P(self.W))
        return NamedSharding(self.mesh, P())
