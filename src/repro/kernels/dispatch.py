"""View-level dispatch: comm views -> 2-D tiles -> Pallas kernels.

This is the seam ``OptimizerConfig.use_pallas=True`` routes through. Each
function mirrors one jnp hot-path in ``repro.core`` — same argument
semantics, same output shapes, f32-identical numerics:

    ef_compress_view      <->  compressor.ef_compress (+ the caller's
                               ``z + err`` pre-add, fused into the kernel)
    server_compress_view  <->  onebit_allreduce._server_compress
    decompress_view       <->  unpack_signs(...) * scales
    fused_local_step_view <->  zero_one_adam's unfused local half-step

Views map to the kernels' (rows, cols) frame by pure reshape (see
compressor.view_to_2d); padding is carried as per-row true counts so the
kernels' scales/error-feedback are pad-exact. Scale granularities that
group multiple 2-D rows ("tensor", "chunk", and "row" with trailing view
dims) use the two-pass reduction (abs_rowsum -> O(rows) combine ->
ef_quantize); per-2-D-row granularity uses the single-pass fused kernel.
The combine step also psums over manual tensor-parallel axes and applies
``rest_factor`` global denominators, exactly like ``compressor._scales``.

Partitioning rules: views that are model-sharded over a GSPMD-*auto* mesh
axis no longer fall back to jnp — :func:`shard_context` derives the
per-shard local layout of a structured view and each view function wraps
its kernels in a manual ``shard_map`` over the view's model axes,
recursing into itself with the local layout and the model axes extended —
so scales still psum to their global values and the outputs come back
sharded exactly as the inputs were. ``kernel_safe`` is the dispatch gate: manual-TP vspecs are
handled by the psum machinery, auto-mesh vspecs require a valid
``shard_context``, and a named vspec on a *meshless* trace is only safe
when the view is the global buffer (``rest_factor == 1``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compressor as C
from repro.kernels import ops


def _tile(x: int, cap: int, quantum: int) -> int:
    """Kernel block extent along a frame dim of size ``x``: the largest
    divisor of ``x`` that is <= ``cap`` and a multiple of the TPU tiling
    ``quantum`` (8 sublanes for rows, 128 lanes for cols), else the whole
    dim — the two block extents the TPU lowering accepts."""
    for d in range(min(x, cap) // quantum * quantum, 0, -quantum):
        if x % d == 0:
            return d
    return x


def _block_rows(rows: int) -> int:
    return _tile(rows, 8, 8)


@functools.lru_cache(maxsize=None)
def _row_counts_np(layout: C.LeafLayout) -> np.ndarray:
    return C.view_row_counts(layout)


def _counts(layout: C.LeafLayout) -> jnp.ndarray:
    return jnp.asarray(_row_counts_np(layout))


@functools.lru_cache(maxsize=None)
def _slice_counts_np(layout: C.LeafLayout) -> np.ndarray:
    return C.slice_row_counts(layout)


@functools.lru_cache(maxsize=None)
def _chunk_counts_np(layout: C.LeafLayout) -> np.ndarray:
    # cached like _row_counts_np: bucketed exchanges re-trace the server
    # compress once per bucket per pipeline stage (see
    # onebit_allreduce_buckets), and LeafLayout is hashable either way
    return C.chunk_row_counts(layout)


def _scales_to_rows(scales, lead_shape, rows, layout=None):
    """Broadcast granular scales (tensor/chunk/row shapes) over the buffer's
    leading view dims, then repeat onto frame sub-rows when the 2-D frame
    folds wider views (see compressor.view_rows_cols)."""
    s = jnp.broadcast_to(scales.astype(jnp.float32),
                         lead_shape + (1,)).reshape(-1)
    if s.shape[0] != rows:
        if s.shape[0] == 0 or rows % s.shape[0]:
            raise ValueError(
                f"cannot spread {s.shape[0]} scale rows over a {rows}-row "
                f"kernel frame (not an integer multiple); scales "
                f"{tuple(scales.shape)} broadcast over lead dims "
                f"{tuple(lead_shape)}"
                + (f", layout {layout}" if layout is not None else ""))
        s = jnp.repeat(s, rows // s.shape[0])
    return s


def kernel_codec(codec) -> bool:
    """Whether the fused Pallas path exists for this wire format.

    Only the sign-1-bit codec has kernels (this module mirrors its packed
    signs + L1 scales bit-for-bit); every other codec declares
    ``has_pallas=False`` and the exchange stays on the jnp path even when
    ``use_pallas=True`` is configured.
    """
    return bool(getattr(codec, "has_pallas", False))


def _vspec_axis_names(vspec) -> Tuple[str, ...]:
    """Flat tuple of mesh-axis names a vspec's entries reference."""
    if vspec is None:
        return ()
    names = []
    for e in tuple(vspec):
        if e is None:
            continue
        names.extend(e if isinstance(e, tuple) else (e,))
    return tuple(names)


def kernel_safe(vspec, layout: C.LeafLayout = None, model_axes=()) -> bool:
    """Whether kernel dispatch may handle a view with this tensor-parallel
    spec, given where the trace is running. Three cases:

    * the vspec's axes are all *manual* model axes (fully-manual optimizer
      region, or a sharded fused bucket): safe — the scale psum machinery
      handles them, no partitioning rule needed;
    * the vspec's axes are bound by an ambient GSPMD-*auto* mesh: safe iff
      :func:`shard_context` can derive a static per-shard layout (the view
      functions then wrap their kernels in a manual ``shard_map``, the
      partitioning rule); flatten views and non-divisible shards stay on
      the constrained jnp path;
    * the vspec names axes that no ambient mesh binds (a meshless trace
      handed a sharded vspec): safe only when the view is the GLOBAL
      buffer (``rest_factor == 1``) — a shard-LOCAL layout would silently
      skip its model psums and produce wrong scales, so that combination
      is routed to jnp where ``compressor._psum_model`` fails loudly on
      the unbound axis instead of corrupting scales.
    """
    names = _vspec_axis_names(vspec)
    if not names:
        return True
    if set(names) <= set(model_axes):
        return True
    auto = C.ambient_auto_mesh()
    if auto and all(n in auto for n in names):
        return layout is not None and shard_context(layout, vspec) is not None
    return layout is None or layout.rest_factor == 1


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """Static per-shard dispatch plan for a model-sharded view (the
    partitioning rule of the Pallas path): which mesh axes the view is
    sharded over, the total shard count, and the shard-local layout
    (sharded view dims divided by their axis sizes, ``rest_factor``
    multiplied by the same factor so scale denominators stay global)."""

    names: Tuple[str, ...]     # mesh axes the vspec shards over
    factor: int                # product of those axes' sizes
    local: C.LeafLayout        # per-shard layout
    entries: Tuple             # vspec entries padded to the view rank


def shard_context(layout: C.LeafLayout, vspec):
    """Derive the per-shard dispatch plan, or None if the sharded view has
    no uniform static local layout and must stay on the jnp path.

    Only *structured* views qualify: a GSPMD-sharded flatten view's pad
    tail lands asymmetrically in the last shard, so there is no local
    layout with pad-exact static row counts. Structured views pad whole
    chunk rows along the (never sharded) split axis, so dividing the
    sharded rest dims — when the axis sizes divide them and the local
    bit-packing dim stays a multiple of 8 — yields an ordinary local
    layout every existing count/scale helper accepts.
    """
    names = _vspec_axis_names(vspec)
    if not names or layout.flatten:
        return None
    auto = C.ambient_auto_mesh()
    if not auto or any(n not in auto for n in names):
        return None
    vs = layout.view_shape
    entries = tuple(vspec)[:len(vs)]
    entries = entries + (None,) * (len(vs) - len(entries))
    local_vs, factor = [], 1
    for dim, e in zip(vs, entries):
        if e is None:
            local_vs.append(dim)
            continue
        f = 1
        for n in (e if isinstance(e, tuple) else (e,)):
            f *= auto[n]
        if f <= 0 or dim % f:
            return None
        local_vs.append(dim // f)
        factor *= f
    if factor == 1:
        return None
    if local_vs[-1] % 8:
        return None
    local = dataclasses.replace(layout, view_shape=tuple(local_vs),
                                rest_factor=layout.rest_factor * factor)
    return ShardContext(names=names, factor=factor, local=local,
                        entries=entries)


def _shard_wrap(fn, in_specs, out_specs, ctx: ShardContext):
    """Manual ``shard_map`` around one kernel dispatch — the partitioning
    rule. The view's model axes alone go manual; the mesh is the ambient
    one the enclosing trace bound."""
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(ctx.names), check_vma=False)


# Static VMEM budget per core for the pre-check: the hardware holds ~16
# MiB; leave headroom for compiler temporaries and double-buffering.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# Worst-case simultaneous f32 operand blocks of one kernel invocation
# (fused_local_step: params, u, grad, err in, params/u/err out -> ~6
# distinct block-shaped refs after input/output aliasing).
_KERNEL_OPERANDS = 6


def frame_precheck(layout: C.LeafLayout, *, block_rows: int = 8,
                   vmem_budget: int = VMEM_BUDGET_BYTES) -> list:
    """Static tile-alignment / VMEM audit of one comm layout against the
    kernels' ``n*128`` frame contract. Returns a list of human-readable
    issues — empty means every kernel in this module can legally tile the
    layout's 2-D frame. Pure metadata: nothing is traced or compiled, so
    the IR-audit CLI can run it over a whole config matrix.
    """
    issues = []
    rows, cols = C.view_rows_cols(layout)
    if cols % 128:
        issues.append(
            f"frame cols={cols} not a multiple of the 128-lane tile "
            f"(layout shape {layout.shape}, view {layout.view_shape}) — "
            f"violates the n*128 flatten quantum")
    if cols % 8:
        issues.append(
            f"frame cols={cols} not a multiple of 8: sign-bit packing "
            f"needs byte-aligned rows")
    if cols > C.FRAME_MAX_COLS:
        issues.append(
            f"frame cols={cols} exceeds FRAME_MAX_COLS={C.FRAME_MAX_COLS} "
            f"— view_rows_cols should have folded this view")
    br = _tile(rows, block_rows, 8) if rows else 0
    est = _KERNEL_OPERANDS * br * cols * 4
    if est > vmem_budget:
        issues.append(
            f"block ({br}, {cols}) f32 working set ~{est} B exceeds the "
            f"~{vmem_budget} B VMEM budget ({_KERNEL_OPERANDS} operand "
            f"blocks)")
    return issues


def _row_group_scales(rowsum, shape, rest_factor, model_axes):
    """Row-granularity scales for a buffer of the given (lead, chunk, *rest)
    shape: one scale per (lead, chunk-row) pair, i.e. per group of
    prod(rest[:-1]) 2-D rows, divided by the full (global) rest extent —
    padding is whole rows, already zeroed in the masked rowsums. Shared by
    the worker view (lead = n) and the server chunk (lead = 1)."""
    ndim = len(shape)
    group = int(np.prod(shape[2:-1])) if ndim > 3 else 1
    rest = max(int(np.prod(shape[2:])) * rest_factor, 1)
    rs = rowsum.reshape(shape[0], shape[1], group).sum(axis=-1)
    s = C._psum_model(rs, model_axes) / rest
    return s.reshape(shape[:2] + (1,) * (ndim - 2))


def _combine_scales(rowsum, layout: C.LeafLayout, mode: C.ScaleMode,
                    model_axes, inner_index=None):
    """Masked per-row L1 sums (R,) -> scales shaped like compressor._scales.

    With ``inner_index`` the buffer is one inner reduce-scatter slice
    (n_outer leading chunks) and the denominators are the statically
    precomputed per-slice counts selected by the traced index — mirroring
    ``compressor._slice_scales``.
    """
    vs = layout.view_shape
    ndim = len(vs)
    rf = layout.rest_factor
    if inner_index is None:
        lead, shape = vs[0], vs
        total, per_chunk = C.true_counts(layout)
        denom = total * rf
        cnt = jnp.asarray(np.maximum(per_chunk * rf, 1.0), jnp.float32)
    else:
        lead, shape = layout.n_outer, layout.slice_shape
        totals, per_chunk = C.slice_true_counts(layout)
        denom = jnp.take(jnp.asarray(np.maximum(totals * rf, 1.0),
                                     jnp.float32), inner_index)
        cnt = jnp.take(jnp.asarray(np.maximum(per_chunk * rf, 1.0),
                                   jnp.float32), inner_index, axis=0)
    if mode == "tensor":
        s = C._psum_model(rowsum.sum(), model_axes) / denom
        return s.reshape((1,) * ndim)
    if mode == "chunk":
        cs = rowsum.reshape(lead, -1).sum(axis=1)
        s = C._psum_model(cs, model_axes) / cnt
        return s.reshape((lead,) + (1,) * (ndim - 1))
    return _row_group_scales(rowsum, shape, rf, model_axes)


def ef_compress_view(z, err, layout: C.LeafLayout, mode: C.ScaleMode,
                     model_axes=(), inner_index=None, vspec=None):
    """Worker-side fused EF-compress of a comm view.

    Fuses the caller's ``z + err`` accumulation; returns
    (packed view, scales shaped like compressor._scales, err view).

    With ``inner_index`` the buffer is the inner reduce-scatter slice of the
    hierarchical path (``layout.slice_shape``): the frame shrinks to the
    slice's contiguous block of rows and the pad-exact row counts/denominators
    are selected by the traced intra-pod index.

    With ``vspec`` naming ambient GSPMD-auto mesh axes the kernels run
    per shard under a manual ``shard_map`` (see :func:`shard_context`):
    this function recurses on the shard-local layout with the model axes
    extended by the view's axes, so the scales psum to their global values
    and packed/err come back sharded exactly like the inputs.
    """
    ctx = shard_context(layout, vspec)
    if ctx is not None:
        from jax.sharding import PartitionSpec as P
        pv = P(*ctx.entries)
        ma = tuple(model_axes) + ctx.names

        def body(z_l, e_l, j):
            return ef_compress_view(
                z_l, e_l, ctx.local, mode, ma,
                inner_index=(j if inner_index is not None else None))

        j_in = (inner_index if inner_index is not None
                else jnp.zeros((), jnp.int32))
        return _shard_wrap(body, in_specs=(pv, pv, P()),
                           out_specs=(pv, P(), pv), ctx=ctx)(z, err, j_in)
    rows, cols = C.view_rows_cols(layout)
    vs = layout.view_shape
    ndim = len(vs)
    eff = "chunk" if (mode == "row" and ndim == 2) else mode
    if inner_index is None:
        bshape, cnts = vs, _counts(layout)
    else:
        bshape = layout.slice_shape
        rows = rows // layout.n_inner
        cnts = jnp.take(jnp.asarray(_slice_counts_np(layout)), inner_index,
                        axis=0)
    z2, e2 = z.reshape(rows, cols), err.reshape(rows, cols)
    br = _block_rows(rows)
    if eff == "row" and ndim == 3 and not model_axes and \
            layout.rest_factor == 1:
        # per-2-D-row scales: the single-pass fully fused kernel applies
        packed2, srow, err2 = ops.ef_compress(z2, e2, cnts, block_rows=br)
        scales = srow.reshape(bshape[:2] + (1,) * (ndim - 2))
    else:
        rowsum = ops.abs_rowsum(z2, e2, cnts, block_rows=br)
        scales = _combine_scales(rowsum, layout, eff, model_axes,
                                 inner_index)
        srow = _scales_to_rows(scales, bshape[:-1], rows, layout)
        packed2, err2 = ops.ef_quantize(z2, e2, srow, cnts, block_rows=br)
    return (packed2.reshape(bshape[:-1] + (-1,)), scales,
            err2.reshape(bshape).astype(err.dtype))


def server_compress_view(avg, err, layout: C.LeafLayout, mode: C.ScaleMode,
                         worker_index, model_axes=(), vspec=None):
    """Server-side fused EF-compress of one chunk (leading dim 1).

    Mirrors onebit_allreduce._server_compress with the ``avg + err`` add
    fused in. Not applicable to row granularity on 2-D (flatten) views —
    that degenerates to per-element scales; callers keep the jnp path there.
    ``vspec`` (the VIEW's entries — the chunk shares the view rank) engages
    the per-shard dispatch exactly like :func:`ef_compress_view`.
    """
    ctx = shard_context(layout, vspec)
    if ctx is not None:
        from jax.sharding import PartitionSpec as P
        pv = P(*ctx.entries)
        ma = tuple(model_axes) + ctx.names

        def body(a_l, e_l, w):
            return server_compress_view(a_l, e_l, ctx.local, mode, w, ma)

        return _shard_wrap(body, in_specs=(pv, pv, P()),
                           out_specs=(pv, P(), pv),
                           ctx=ctx)(avg, err, worker_index)
    ys = avg.shape
    ndim = len(ys)
    assert not (mode == "row" and ndim == 2)
    rows_all, cols = C.view_rows_cols(layout)
    rows = rows_all // layout.n   # the frame splits chunks into equal blocks
    cnts = jnp.take(jnp.asarray(_chunk_counts_np(layout)), worker_index,
                    axis=0)
    z2, e2 = avg.reshape(rows, cols), err.reshape(rows, cols)
    br = _block_rows(rows)
    rowsum = ops.abs_rowsum(z2, e2, cnts, block_rows=br)
    rf = layout.rest_factor
    if mode == "row":
        scales = _row_group_scales(rowsum, ys, rf, model_axes)
    else:  # tensor / chunk -> one scale for this chunk
        denom = jnp.maximum(cnts.sum().astype(jnp.float32) * rf, 1.0)
        s = C._psum_model(rowsum.sum(), model_axes) / denom
        scales = s.reshape((1,) * ndim)
    srow = _scales_to_rows(scales, ys[:-1], rows, layout)
    packed2, err2 = ops.ef_quantize(z2, e2, srow, cnts, block_rows=br)
    return (packed2.reshape(ys[:-1] + (ys[-1] // 8,)), scales,
            err2.reshape(ys).astype(err.dtype))


def decompress_view(packed, scales, layout: C.LeafLayout,
                    dtype=jnp.float32, vspec=None):
    """Fused unpack·scale of a view-shaped packed buffer (the a2a receive
    or the gathered chunk results — both carry the full view shape).

    ``scales`` must broadcast against the packed array's leading dims (the
    shapes _scales / server compression produce for tensor/chunk/row modes).
    Slice-shaped buffers of the hierarchical path (leading dim n_outer
    instead of n) shrink the frame proportionally. ``vspec`` engages the
    per-shard dispatch (scales are already replicated — post-psum — so
    only the packed bits and the output are sharded).
    """
    ctx = shard_context(layout, vspec)
    if ctx is not None:
        from jax.sharding import PartitionSpec as P
        pv = P(*ctx.entries)

        def body(p_l, s_l):
            return decompress_view(p_l, s_l, ctx.local, dtype)

        return _shard_wrap(body, in_specs=(pv, P()), out_specs=pv,
                           ctx=ctx)(packed, scales)
    rows, cols = C.view_rows_cols(layout)
    rows = (rows * int(np.prod(packed.shape[:-1]))
            // int(np.prod(layout.view_shape[:-1])))
    p2 = packed.reshape(rows, cols // 8)
    srow = _scales_to_rows(scales, packed.shape[:-1], rows, layout)
    out2 = ops.decompress(p2, srow, block_rows=_block_rows(rows),
                          dtype=dtype)
    return out2.reshape(packed.shape[:-1] + (layout.pack_count,))


def fused_local_step_view(g, m, u, v, lr, beta1, eps,
                          layout: C.LeafLayout, kind: str = "adam",
                          vspec=None):
    """Fused local half-step over one leaf's comm view, keyed on the base
    kind ("adam" | "lamb" | "sgd" — see repro.core.base_steps).

    Returns (m', u', delta) in view shape — identical math to the unfused
    three-sweep XLA chain, in one VMEM pass. "adam" and "lamb" share the
    variance-preconditioned kernel (``v`` required; the caller applies the
    LAMB trust scalar to ``delta`` afterwards); "sgd" uses the no-variance
    kernel (``v`` ignored, may be None). ``vspec`` engages the per-shard
    dispatch — the step is elementwise, so the local call needs no psums.
    """
    ctx = shard_context(layout, vspec)
    if ctx is not None:
        from jax.sharding import PartitionSpec as P
        pv = P(*ctx.entries)
        if kind == "sgd":
            def body(g_l, m_l, u_l, lr_l):
                return fused_local_step_view(g_l, m_l, u_l, None, lr_l,
                                             beta1, eps, ctx.local, kind)
            return _shard_wrap(body, in_specs=(pv, pv, pv, P()),
                               out_specs=(pv, pv, pv),
                               ctx=ctx)(g, m, u, jnp.asarray(lr))

        def body(g_l, m_l, u_l, v_l, lr_l):
            return fused_local_step_view(g_l, m_l, u_l, v_l, lr_l,
                                         beta1, eps, ctx.local, kind)
        return _shard_wrap(body, in_specs=(pv, pv, pv, pv, P()),
                           out_specs=(pv, pv, pv),
                           ctx=ctx)(g, m, u, v, jnp.asarray(lr))
    rows, cols = C.view_rows_cols(layout)
    vs = layout.view_shape
    r2 = lambda a: a.reshape(rows, cols)
    block = (_block_rows(rows), _tile(cols, 1024, 128))
    if kind == "sgd":
        mh2, uh2, d2 = ops.fused_local_step_sgd(r2(g), r2(m), r2(u), lr,
                                                beta1, block=block)
    elif kind in ("adam", "lamb"):
        mh2, uh2, d2 = ops.fused_local_step(r2(g), r2(m), r2(u), r2(v), lr,
                                            beta1, eps, block=block)
    else:
        raise ValueError(f"unknown base kind {kind!r} for the fused "
                         f"local step")
    return mh2.reshape(vs), uh2.reshape(vs), d2.reshape(vs)
