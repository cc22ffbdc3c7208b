"""Per-leaf comm planning shared by every optimizer in ``repro.core``.

Historically ``adam.py`` / ``one_bit_adam.py`` / ``zero_one_adam.py`` each
re-derived the same construction-time plumbing — flatten the param tree,
align specs and the DP mask, normalize the hierarchy, build a
:class:`~repro.core.compressor.LeafLayout` and view-spec entries per leaf,
assemble the AllReduce config. :class:`LeafPlan` is that boilerplate,
factored out once; the composed :mod:`repro.core.compressed` optimizer and
the legacy reference classes both build on it, so the two code paths can
never drift on layout geometry.

The hierarchy is normalized here (``norm_hierarchy``) and nowhere else on
the optimizer side: every consumer reads ``plan.hierarchy``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax

from repro.core import compressor as C
from repro.core import onebit_allreduce as AR
from repro.core.comm import Hierarchy, norm_hierarchy


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Static per-leaf communication plan for one parameter tree."""

    n: int                          # worker count
    hierarchy: Optional[Hierarchy]  # normalized (None when flat / n == 1)
    model_axes: Tuple[str, ...]     # manual tensor-parallel axes
    treedef: Any
    leaves: List[Any]               # abstract leaves (shape/dtype)
    specs: List[Any]                # tensor-parallel PartitionSpecs
    dp_mask: List[bool]
    layouts: List[C.LeafLayout]
    vspecs: List[Any]               # view-shaped spec entries per leaf

    def flat(self, tree):
        return self.treedef.flatten_up_to(tree)


def make_plan(param_shapes, specs, dp_mask, n_workers: int,
              model_axis_sizes=None,
              hierarchy: Optional[Hierarchy] = None) -> LeafPlan:
    if specs is None:
        specs = jax.tree.map(lambda _: None, param_shapes)
    if dp_mask is None:
        dp_mask = jax.tree.map(lambda _: True, param_shapes)
    model_axis_sizes = model_axis_sizes or {}
    # a model axis of size 1 shards nothing: plan the global layouts, so a
    # data-parallel-only mesh keeps sim mode's state and wire layouts
    tp_local = any(s > 1 for s in model_axis_sizes.values())
    hierarchy = norm_hierarchy(hierarchy, n_workers)
    leaves, treedef = jax.tree.flatten(param_shapes)
    specs_f = treedef.flatten_up_to(specs)
    dp_f = treedef.flatten_up_to(dp_mask)
    layouts = [
        C.make_layout(l.shape, s, n_workers,
                      rest_factor=C.spec_model_factor(s, model_axis_sizes),
                      force_flatten=tp_local,
                      n_inner=hierarchy.inner if hierarchy else 1)
        for l, s in zip(leaves, specs_f)]
    vspecs = [C.view_spec_entries(lo, sp)
              for lo, sp in zip(layouts, specs_f)]
    return LeafPlan(n=n_workers, hierarchy=hierarchy,
                    model_axes=tuple(model_axis_sizes.keys()),
                    treedef=treedef, leaves=leaves, specs=specs_f,
                    dp_mask=dp_f, layouts=layouts, vspecs=vspecs)


def make_ar_cfg(plan: LeafPlan, *, scale_mode, quantize, use_pallas,
                comm_dtype, codec=None, codec_arg=None) -> AR.OneBitConfig:
    """Algorithm-2 exchange config bound to a plan's topology.

    ``codec`` is a wire-format name or instance (``repro.core.codecs``);
    ``None`` keeps the historical rule: sign1bit, or identity when
    ``quantize`` is False. A name is resolved here with ``codec_arg``
    applied, so callers holding an unresolved (name, arg) pair — the
    legacy optimizer classes — don't silently drop the arg."""
    if codec is not None:
        from repro.core.codecs import make_codec
        codec = make_codec(codec, codec_arg)
    return AR.OneBitConfig(scale_mode=scale_mode, quantize=quantize,
                           codec=codec,
                           model_axes=plan.model_axes,
                           use_pallas=use_pallas,
                           hierarchy=plan.hierarchy,
                           comm_dtype=comm_dtype)
