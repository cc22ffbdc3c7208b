"""Error-feedback 1-bit compression (paper Eq. 4 + Algorithm 2 building blocks).

The compressor operates on a *comm view* of each parameter leaf:

    natural leaf (.., A, ..)  --pad/transpose/reshape-->  view (n, A_pad/n, *rest)

where ``n`` is the worker count and the leading axis enumerates the chunks of
the chunked AllReduce (worker *j* is the "server" for chunk *j*). The view
transform is chosen per-leaf at init time (:func:`make_layout`) so that:

* the chunk-split axis is never a tensor-parallel ('model') sharded axis —
  every op below is local to a chip except the worker-axis collectives
  themselves;
* sign bits are packed along the last axis of the view, which is always a
  multiple of 8 elements per model shard.

Compression follows the paper: ``C[a] = (‖a‖₁/d) · sign(a)`` with error
feedback. ``scale_mode`` controls the granularity of the magnitude:

* ``"tensor"`` — one scale per leaf (paper-faithful, Eq. 4);
* ``"chunk"``  — one scale per worker chunk (what DeepSpeed's chunked NCCL
  backend effectively does);
* ``"row"``    — one scale per view row (beyond-paper refinement; strictly
  tighter error feedback at negligible extra traffic).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ScaleMode = str  # "tensor" | "chunk" | "row"

SCALE_MODES = ("tensor", "chunk", "row")


def validate_scale_mode(mode: ScaleMode) -> ScaleMode:
    """Fail fast on a bad scale mode, at config-build time.

    ``ScaleMode`` is a plain string, so a typo like ``"rows"`` would
    otherwise only surface deep inside ``_scales`` (or silently misroute a
    branch that only checks equality). Every config object validates
    through here in its ``__post_init__``.
    """
    if mode not in SCALE_MODES:
        raise ValueError(
            f"unknown scale_mode {mode!r}; choose from {list(SCALE_MODES)}")
    return mode


# ---------------------------------------------------------------------------
# Leaf layouts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafLayout:
    """Static description of how one leaf maps to its comm view.

    The view's leading axis enumerates the ``n`` chunks of the chunked
    AllReduce. With a two-level hierarchy (``n_inner > 1``) those chunks are
    grouped two ways at once: contiguous blocks of ``n_outer`` rows form the
    **inner reduce-scatter chunk** (the slice a worker owns after the
    full-precision intra-pod reduce-scatter, shape ``(n_outer, *chunk)``),
    and each single row stays the **outer 1-bit chunk** (what one pod serves
    during the compressed inter-pod exchange, shape ``chunk``). The flat
    layout is the exact ``n_inner == 1`` degenerate case.
    """

    shape: Tuple[int, ...]        # natural (unpadded) leaf shape
    n: int                        # worker count (number of chunks)
    flatten: bool                 # True -> treat leaf as 1-D of prod(shape)
    split_axis: int               # axis chunked across workers (after flatten)
    padded: int                   # split axis size after padding
    view_shape: Tuple[int, ...]   # (n, padded//n, *rest)
    rest_factor: int = 1          # global/local element ratio when the leaf
                                  # is tensor-parallel sharded and the layout
                                  # was built on the model-LOCAL shard
    n_inner: int = 1              # intra-pod worker count (1 = flat)

    @property
    def pad(self) -> int:
        base = int(np.prod(self.shape)) if self.flatten else self.shape[self.split_axis]
        return self.padded - base

    @property
    def chunk_shape(self) -> Tuple[int, ...]:
        return self.view_shape[1:]

    @property
    def pack_count(self) -> int:
        """Number of elements packed along the last view axis."""
        return self.view_shape[-1]

    @property
    def n_outer(self) -> int:
        """Pod count (size of the compressed exchange)."""
        return self.n // self.n_inner

    @property
    def slice_shape(self) -> Tuple[int, ...]:
        """Shape of the inner reduce-scatter slice one worker owns."""
        return (self.n_outer,) + self.chunk_shape

    @property
    def ef_worker_shape(self) -> Tuple[int, ...]:
        """Worker-side EF state shape: the buffer actually compressed —
        the full view when flat, the owned slice when hierarchical."""
        return self.slice_shape


def _is_sharded(spec, axis: int) -> bool:
    if spec is None:
        return False
    entries = tuple(spec)
    if axis >= len(entries):
        return False
    return entries[axis] is not None


def spec_model_factor(spec, axis_sizes) -> int:
    """Product of mesh-axis sizes referenced by a PartitionSpec."""
    if spec is None or not axis_sizes:
        return 1
    f = 1
    for e in tuple(spec):
        if e is None:
            continue
        for name in (e if isinstance(e, tuple) else (e,)):
            f *= axis_sizes.get(name, 1)
    return f


def make_layout(shape: Sequence[int], spec, n: int,
                rest_factor: int = 1,
                force_flatten: bool = False,
                n_inner: int = 1) -> LeafLayout:
    """Choose the comm view for a leaf with the given model-sharding spec.

    ``spec`` is a ``PartitionSpec`` (or None) describing tensor-parallel
    sharding only; the worker axis is implicit.

    ``force_flatten`` is set when the optimizer runs in the fully-manual
    domain (nested shard_map over 'model'): leaf shapes are then
    tensor-parallel-LOCAL shards, so the uniform flat view is always valid —
    there is no GSPMD resharding to avoid.

    ``n_inner`` enables the two-level (intra-pod × inter-pod) chunking: the
    view geometry is unchanged, but the layout records how its ``n`` chunk
    rows group into ``n_inner`` reduce-scatter slices of ``n // n_inner``
    outer 1-bit chunks each (see :class:`LeafLayout`).
    """
    shape = tuple(int(s) for s in shape)
    if n_inner < 1 or n % n_inner:
        raise ValueError(f"n_inner={n_inner} must divide n={n}")
    replicated = spec is None or all(e is None for e in tuple(spec))
    # Flatten views pad to an n*128 quantum (not just the n*8 bit-packing
    # minimum) so the kernel frame's column width is always a multiple of
    # the 128-lane TPU register width, folded or not. Costs < n*128 extra
    # elements per leaf; scales/EF stay pad-exact via masks/row counts.
    # Deliberately mode-independent (not gated on use_pallas): state and
    # wire layouts must match between the fused and unfused paths so the
    # modes stay drop-in interchangeable, checkpoints included.
    if len(shape) == 0:
        padded = _round_up(1, n * 128)
        return LeafLayout(shape=(), n=n, flatten=True, split_axis=0,
                          padded=padded, view_shape=(n, padded // n),
                          rest_factor=1, n_inner=n_inner)
    if replicated or force_flatten:
        total = int(np.prod(shape))
        padded = _round_up(total, n * 128)
        return LeafLayout(shape=shape, n=n, flatten=True, split_axis=0,
                          padded=padded, view_shape=(n, padded // n),
                          rest_factor=rest_factor if not replicated else 1,
                          n_inner=n_inner)
    # Sharded leaf under GSPMD-auto: split along the largest unsharded axis.
    candidates = [a for a in range(len(shape)) if not _is_sharded(spec, a)]
    if not candidates:
        raise ValueError(
            f"leaf {shape} with spec {spec} has no replicated axis to chunk over")
    split_axis = max(candidates, key=lambda a: shape[a])
    rest = [shape[a] for a in range(len(shape)) if a != split_axis]
    if rest:
        if rest[-1] % 8 != 0:
            raise ValueError(
                f"leaf {shape} spec {spec}: last view dim {rest[-1]} not a "
                f"multiple of 8; cannot bit-pack without resharding")
        padded = _round_up(shape[split_axis], n)
    else:
        padded = _round_up(shape[split_axis], n * 8)
    view_shape = (n, padded // n, *rest)
    return LeafLayout(shape=shape, n=n, flatten=False, split_axis=split_axis,
                      padded=padded, view_shape=view_shape,
                      rest_factor=rest_factor, n_inner=n_inner)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def to_view(x: jnp.ndarray, layout: LeafLayout) -> jnp.ndarray:
    """Natural leaf -> comm view (n, padded//n, *rest). Purely local ops."""
    if layout.flatten:
        flat = x.reshape(-1)
        if layout.pad:
            flat = jnp.pad(flat, (0, layout.pad))
        return flat.reshape(layout.view_shape)
    if layout.pad:
        pads = [(0, 0)] * x.ndim
        pads[layout.split_axis] = (0, layout.pad)
        x = jnp.pad(x, pads)
    x = jnp.moveaxis(x, layout.split_axis, 0)
    return x.reshape(layout.view_shape)


def from_view(v: jnp.ndarray, layout: LeafLayout) -> jnp.ndarray:
    """Comm view -> natural leaf shape (drops padding)."""
    if layout.flatten:
        flat = v.reshape(-1)
        total = int(np.prod(layout.shape)) if layout.shape else 1
        flat = flat[:total]
        return flat.reshape(layout.shape)
    rest = [layout.shape[a] for a in range(len(layout.shape))
            if a != layout.split_axis]
    x = v.reshape((layout.padded, *rest))
    x = jnp.moveaxis(x, 0, layout.split_axis)
    if layout.pad:
        sl = [slice(None)] * x.ndim
        sl[layout.split_axis] = slice(0, layout.shape[layout.split_axis])
        x = x[tuple(sl)]
    return x


def pad_mask(layout: LeafLayout, dtype=jnp.float32) -> Optional[jnp.ndarray]:
    """Mask over the view that is 0 at padded positions, or None if no pad.

    Broadcastable against the view: shape (n, padded//n) + (1,)*len(rest).
    """
    if layout.pad == 0:
        return None
    a = np.arange(layout.padded).reshape(layout.view_shape[0], layout.view_shape[1])
    base = (int(np.prod(layout.shape)) if layout.flatten
            else layout.shape[layout.split_axis])
    m = (a < base).astype(np.float32)
    m = m.reshape(m.shape + (1,) * (len(layout.view_shape) - 2))
    return jnp.asarray(m, dtype=dtype)


def ambient_auto_mesh():
    """(axis->size) for GSPMD-*auto* axes of the ambient mesh, or None.

    The ambient mesh is the one ``jax.set_mesh`` or an enclosing
    ``shard_map`` binds. Inside a partial-manual shard_map body it reports
    the manual worker axes as Manual — constraints must only mention Auto
    axes.
    """
    am = jax.sharding.get_abstract_mesh()
    auto = {a: int(am.shape[a])
            for a, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Auto}
    return auto or None


def constrain(x, entries) -> jnp.ndarray:
    """with_sharding_constraint that degrades to a no-op off-mesh.

    Keeps the optimizer's comm pipeline (views, packed bits, chunk buffers)
    sharded over the tensor-parallel axis — without these GSPMD loses the
    last-dim sharding across packbits/collective boundaries and re-gathers
    full views over 'model' (observed: 18 GiB all-gathers per leaf).
    """
    if entries is None:
        return x
    auto = ambient_auto_mesh()
    if not auto:
        return x
    from jax.sharding import PartitionSpec as P
    ents = tuple(entries)[:x.ndim]
    ents = ents + (None,) * (x.ndim - len(ents))
    ok = []
    for dim, name in zip(x.shape, ents):
        if name is None:
            ok.append(None)
            continue
        names = name if isinstance(name, tuple) else (name,)
        if all(n in auto for n in names):
            size = 1
            for n in names:
                size *= auto[n]
            ok.append(name if dim % size == 0 else None)
        else:
            ok.append(None)
    if all(e is None for e in ok):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*ok))
    except Exception:
        return x


def view_spec_entries(layout: LeafLayout, spec) -> Tuple:
    """PartitionSpec entries (model axes only) for the comm-view shape.

    * GSPMD-auto structured views keep the original non-split-axis entries
      (the split axis is unsharded): view (n, A/n, *rest).
    * Fully-manual flattened views of a tensor-parallel leaf
      (rest_factor > 1): the flat dim is declared sharded over the leaf's
      model axes — each shard stores its own flat segment.
    * Replicated flattened leaves: replicated.
    """
    if layout.flatten:
        if layout.rest_factor > 1 and spec is not None:
            names = []
            for e in tuple(spec):
                if e is None:
                    continue
                names.extend(e if isinstance(e, tuple) else (e,))
            if names:
                ax = names[0] if len(names) == 1 else tuple(names)
                return (None, ax)
        return (None,) * len(layout.view_shape)
    entries = tuple(spec) if spec is not None else ()
    entries = entries + (None,) * (len(layout.shape) - len(entries))
    rest = tuple(e for a, e in enumerate(entries) if a != layout.split_axis)
    return (None, None, *rest)


def chunk_spec_entries(layout: LeafLayout, spec) -> Tuple:
    """PartitionSpec entries for the server-chunk shape (A/n, *rest)."""
    return view_spec_entries(layout, spec)[1:]


# ---------------------------------------------------------------------------
# View <-> 2-D adapter (the kernels' tile contract)
#
# The Pallas kernels in repro.kernels operate on 2-D (rows, cols) tiles.
# Every comm view (n, A/n, *rest) maps onto that frame by collapsing all
# leading axes into rows and keeping the last axis as cols:
#
#     view (n, A/n, r0, .., rk, C)  <->  2-D (n * A/n * r0 * .. * rk, C)
#
# This is a pure reshape (no data movement): the last view axis is already
# the bit-packing axis and a multiple of 8, so packed bytes produced on the
# 2-D frame are byte-identical to ``pack_signs`` on the view. Padding is
# always expressible per 2-D row as a true-element *count* (flatten views
# pad the tail of the flat element order -> tail columns of the last rows;
# structured views pad whole chunk rows -> whole 2-D rows), which is what
# :func:`view_row_counts` precomputes for the kernels' mask-aware scales.
# ---------------------------------------------------------------------------

# Max frame width handed to the kernels. Tiles are (block_rows, cols), so
# cols bounds VMEM per tile (~6 f32 operands x 8 rows x cols = 192*cols
# bytes at 8192 -> ~1.6 MB, comfortably under the ~16 MB/core budget).
# Flatten views of big leaves (cols = leaf_size/n) are refolded to respect
# it; structured views keep their (bounded, model-local) last dim.
FRAME_MAX_COLS = 8192


def view_rows_cols(layout: LeafLayout) -> Tuple[int, int]:
    """(rows, cols) of the kernel-facing 2-D frame of a comm view.

    For flatten views wider than FRAME_MAX_COLS the frame folds each chunk
    row into ``k`` sub-rows (still a pure reshape of the flat element
    order; every chunk stays a contiguous, equal block of frame rows, so
    scale-group reductions reshape cleanly and padding remains a tail
    expressible as per-row counts).
    """
    vs = layout.view_shape
    rows, cols = int(np.prod(vs[:-1])), int(vs[-1])
    if layout.flatten and cols > FRAME_MAX_COLS:
        # fold in 128-lane units so folded cols stay register-aligned;
        # worst case is a 128-wide frame, never narrower
        assert cols % 128 == 0, layout  # flatten views pad to n*128
        m = cols // 128
        k = -(-m // (FRAME_MAX_COLS // 128))  # smallest split under the cap
        while m % k:
            k += 1
        rows, cols = rows * k, 128 * (m // k)
    return rows, cols


def view_to_2d(v: jnp.ndarray, layout: LeafLayout) -> jnp.ndarray:
    """Comm view -> (rows, cols) kernel frame. Pure reshape."""
    rows, cols = view_rows_cols(layout)
    return v.reshape(rows, cols)


def view_from_2d(a2d: jnp.ndarray, layout: LeafLayout) -> jnp.ndarray:
    """Kernel frame -> comm view. The last dim is inferred so the same
    helper restores values and packed bytes, framed or not."""
    return a2d.reshape(layout.view_shape[:-1] + (-1,))


def view_row_counts(layout: LeafLayout) -> np.ndarray:
    """True (unpadded) element count per 2-D frame row, int32 (rows,).

    Agrees with ``pad_mask`` broadcast over the view, reshaped to the frame
    and row-summed; the kernels rebuild the elementwise mask as
    ``iota(cols) < count``.
    """
    rows, cols = view_rows_cols(layout)
    if layout.flatten:
        base = int(np.prod(layout.shape)) if layout.shape else 1
        starts = np.arange(rows, dtype=np.int64) * cols
        cnt = np.clip(base - starts, 0, cols)
    else:
        base = layout.shape[layout.split_axis]
        vs = layout.view_shape
        group = int(np.prod(vs[2:-1], dtype=np.int64)) if len(vs) > 3 else 1
        pos = np.arange(layout.n * vs[1], dtype=np.int64)  # split positions
        cnt = np.repeat((pos < base).astype(np.int64), group) * cols
    return cnt.astype(np.int32)


def chunk_row_counts(layout: LeafLayout) -> np.ndarray:
    """Per-worker-chunk row counts, int32 (n, rows // n): row counts of the
    server chunk that worker j owns (``view_row_counts`` regrouped)."""
    rows, _ = view_rows_cols(layout)
    return view_row_counts(layout).reshape(layout.n, rows // layout.n)


def slice_row_counts(layout: LeafLayout) -> np.ndarray:
    """Per-slice 2-D frame row counts, int32 (n_inner, rows // n_inner).

    Row ``j`` holds the true-element counts of the frame rows of the inner
    reduce-scatter slice owned by intra-pod worker ``j`` (the slices are
    contiguous equal blocks of frame rows, so this is ``view_row_counts``
    regrouped — exactly like :func:`chunk_row_counts` one level up).
    """
    rows, _ = view_rows_cols(layout)
    return view_row_counts(layout).reshape(layout.n_inner,
                                           rows // layout.n_inner)


def slice_true_counts(layout: LeafLayout) -> Tuple[np.ndarray, np.ndarray]:
    """Static per-slice element counts for the hierarchical worker compress.

    Returns ``(totals (n_inner,), per_chunk (n_inner, n_outer))`` — the
    float64 true-element counts of each inner slice and of each outer chunk
    within it. ``slice_true_counts(flat_layout)`` is ``true_counts`` with a
    leading length-1 axis, which is what makes the ``n_inner == 1``
    hierarchical path bitwise-identical to the flat one.
    """
    _, per_chunk = true_counts(layout)
    grouped = per_chunk.reshape(layout.n_inner, layout.n_outer)
    return grouped.sum(axis=1), grouped


def true_counts(layout: LeafLayout) -> Tuple[float, np.ndarray]:
    """(#real elements per leaf, #real elements per chunk row array (n, A/n))."""
    rest = int(np.prod(layout.view_shape[2:])) if len(layout.view_shape) > 2 else 1
    a = np.arange(layout.padded)
    base = (int(np.prod(layout.shape)) if layout.flatten
            else layout.shape[layout.split_axis])
    rows = (a < base).astype(np.float64).reshape(layout.view_shape[0],
                                                 layout.view_shape[1])
    per_chunk = rows.sum(axis=1) * rest          # (n,)
    total = float(per_chunk.sum())
    return total, per_chunk


# ---------------------------------------------------------------------------
# Sign packing
# ---------------------------------------------------------------------------

def pack_signs(v: jnp.ndarray) -> jnp.ndarray:
    """Pack sign bits (>= 0) along the last axis; last dim must be %8==0."""
    bits = (v >= 0).astype(jnp.uint8)
    return jnp.packbits(bits, axis=-1, bitorder="big")


def unpack_signs(p: jnp.ndarray, count: int, dtype=jnp.float32) -> jnp.ndarray:
    """Unpack to ±1 values of the given last-axis length."""
    bits = jnp.unpackbits(p, axis=-1, count=count, bitorder="big")
    return bits.astype(dtype) * 2.0 - 1.0


# ---------------------------------------------------------------------------
# 1-bit compression with error feedback
# ---------------------------------------------------------------------------

def _psum_model(x, model_axes):
    if not model_axes:
        return x
    return jax.lax.psum(x, model_axes if len(model_axes) > 1  # audit-ok: raw-collective
                        else model_axes[0])


def _scales(z: jnp.ndarray, layout: LeafLayout, mode: ScaleMode,
            mask: Optional[jnp.ndarray], model_axes=()) -> jnp.ndarray:
    """L1-mean magnitudes at the requested granularity (pad-exact).

    When the layout was built on a tensor-parallel-local shard
    (``rest_factor > 1``) the local sums are psum'd over the model axes and
    the denominators use the GLOBAL element counts so every shard agrees on
    the same scale (fully-manual optimizer region).
    """
    az = jnp.abs(z)
    if mask is not None:
        az = az * mask
    total, per_chunk = true_counts(layout)
    rf = layout.rest_factor
    if mode == "tensor":
        s = _psum_model(az.sum(), model_axes) / (total * rf)
        return s.reshape((1,) * z.ndim)
    if mode == "chunk":
        axes = tuple(range(1, z.ndim))
        cnt = jnp.asarray(np.maximum(per_chunk * rf, 1.0), dtype=z.dtype)
        s = _psum_model(az.sum(axis=axes), model_axes) / cnt
        return s.reshape((z.shape[0],) + (1,) * (z.ndim - 1))
    if mode == "row":
        axes = tuple(range(2, z.ndim))
        rest = (int(np.prod(z.shape[2:])) if z.ndim > 2 else 1) * rf
        if z.ndim > 2:
            s = _psum_model(az.sum(axis=axes), model_axes) / rest
        else:
            # (n, A/n): row scale degenerates to |value|; fall back to chunk
            return _scales(z, layout, "chunk", mask, model_axes)
        return s.reshape(z.shape[:2] + (1,) * (z.ndim - 2))
    raise ValueError(f"unknown scale mode {mode!r}")


def ef_compress(z: jnp.ndarray, layout: LeafLayout, mode: ScaleMode,
                mask: Optional[jnp.ndarray], model_axes=()):
    """One error-feedback compression pass over a comm view.

    Returns (packed uint8, scales, residual error). ``z`` already includes the
    incoming error (caller adds it): this computes ``ẑ = C[z]``, ``err = z−ẑ``.
    """
    scales = _scales(z, layout, mode, mask, model_axes)
    packed = pack_signs(z)
    signs = jnp.where(z >= 0, 1.0, -1.0).astype(z.dtype)
    zhat = signs * scales.astype(z.dtype)
    err = z - zhat
    if mask is not None:
        err = err * mask.astype(err.dtype)
    return packed, scales, err


def _slice_scales(z: jnp.ndarray, layout: LeafLayout, mode: ScaleMode,
                  mask: Optional[jnp.ndarray], inner_index,
                  model_axes=()) -> jnp.ndarray:
    """:func:`_scales` for one inner reduce-scatter slice (n_outer, *chunk).

    Denominators come from the statically precomputed per-slice true counts
    selected by the (traced) intra-pod worker index, so the padded tail —
    which always lands in the last slice — stays pad-exact. With
    ``n_inner == 1`` this selects the full-view counts and is bitwise
    identical to ``_scales`` on the whole view.
    """
    az = jnp.abs(z)
    if mask is not None:
        az = az * mask
    totals, per_chunk = slice_true_counts(layout)
    rf = layout.rest_factor
    if mode == "tensor":
        # unlike the flat path a whole slice can be padding (tiny leaves):
        # clamp so its all-zero sums produce a zero scale, not NaN
        denom = jnp.take(jnp.asarray(np.maximum(totals * rf, 1.0), z.dtype),
                         inner_index)
        s = _psum_model(az.sum(), model_axes) / denom
        return s.reshape((1,) * z.ndim)
    if mode == "chunk":
        axes = tuple(range(1, z.ndim))
        cnt = jnp.take(jnp.asarray(np.maximum(per_chunk * rf, 1.0), z.dtype),
                       inner_index, axis=0)
        s = _psum_model(az.sum(axis=axes), model_axes) / cnt
        return s.reshape((z.shape[0],) + (1,) * (z.ndim - 1))
    if mode == "row":
        if z.ndim <= 2:
            return _slice_scales(z, layout, "chunk", mask, inner_index,
                                 model_axes)
        # padding is whole split positions, so the (static) full rest extent
        # is the exact denominator — same as _scales on the flat view
        axes = tuple(range(2, z.ndim))
        rest = int(np.prod(z.shape[2:])) * rf
        s = _psum_model(az.sum(axis=axes), model_axes) / rest
        return s.reshape(z.shape[:2] + (1,) * (z.ndim - 2))
    raise ValueError(f"unknown scale mode {mode!r}")


def ef_compress_slice(z: jnp.ndarray, layout: LeafLayout, mode: ScaleMode,
                      mask: Optional[jnp.ndarray], inner_index,
                      model_axes=()):
    """Worker-side EF compression of one inner reduce-scatter slice.

    ``z`` is the pod-mean slice plus the incoming worker error, shape
    ``layout.slice_shape``; ``mask`` the matching slice of the pad mask.
    Same contract as :func:`ef_compress`, with per-slice denominators.
    """
    scales = _slice_scales(z, layout, mode, mask, inner_index, model_axes)
    packed = pack_signs(z)
    signs = jnp.where(z >= 0, 1.0, -1.0).astype(z.dtype)
    err = z - signs * scales.astype(z.dtype)
    if mask is not None:
        err = err * mask.astype(err.dtype)
    return packed, scales, err


def decompress(packed: jnp.ndarray, scales: jnp.ndarray, count: int,
               dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of the quantizer: scale · sign."""
    signs = unpack_signs(packed, count, dtype)
    return signs * scales.astype(dtype)


def compressed_bytes_levels(layout: LeafLayout, mode: ScaleMode,
                            inner_itemsize: int = 2, codec=None) -> dict:
    """Per-level bytes one worker SENDS on one hierarchical sync.

    ``inner``: the full-precision intra-pod phases — the reduce-scatter
    all_to_all ships (n_inner − 1) of the n_inner view slices, and the final
    intra-pod all_gather broadcasts the decompressed owned slice to the
    n_inner − 1 pod-mates, both at the wire dtype (``inner_itemsize``).

    ``outer``: Algorithm 2's compressed exchange across pods over the owned
    slice — scatter keeps the own chunk local, so (n_outer − 1) encoded
    chunks go out, and the gather broadcasts this pod's compressed server
    chunk to the n_outer − 1 peers: the same (n_outer − 1) payloads again.
    The payload size of one chunk in each phase is the *codec*'s
    (``codec.wire_bytes``; default sign1bit: ``elems/8`` packed sign bytes
    plus the scale-granularity-dependent f32 scales — one per chunk for
    tensor/chunk granularity, one per view row for row granularity).

    A flat layout (``n_inner == 1``) has ``inner == 0`` and ``outer`` equal
    to the historical flat-path accounting.
    """
    from repro.core.codecs import make_codec   # lazy: codecs imports us
    codec = make_codec("sign1bit" if codec is None else codec)
    chunk_elems = int(np.prod(layout.chunk_shape))
    ni, no = layout.n_inner, layout.n_outer
    inner = 2 * (ni - 1) * no * chunk_elems * inner_itemsize
    wb = codec.wire_bytes(layout, mode)
    outer = (no - 1) * (wb["scatter"] + wb["gather"])
    return {"inner": inner, "outer": outer}


def compressed_bytes(layout: LeafLayout, mode: ScaleMode,
                     inner_itemsize: int = 2, codec=None) -> int:
    """Total bytes per worker SENT on one sync, across both levels (the
    flat path is the ``inner == 0`` special case)."""
    lv = compressed_bytes_levels(layout, mode, inner_itemsize, codec)
    return lv["inner"] + lv["outer"]


def fullprec_bytes_levels(layout: LeafLayout, itemsize: int) -> dict:
    """Per-level bytes one worker sends on a full-precision round.

    Flat: the chunked scatter-mean/all-gather moves 2·(n−1)/n of the view.
    Hierarchical: the intra-pod reduce-scatter + all_gather move
    2·(n_inner−1)/n_inner of the view, the inter-pod exchange
    2·(n_outer−1)/n_outer of the owned slice (1/n_inner of the view).
    """
    ni, no = layout.n_inner, layout.n_outer
    elems = int(np.prod(layout.view_shape))
    inner = 2 * (ni - 1) * (elems // ni) * itemsize
    outer = 2 * (no - 1) * (elems // ni // no) * itemsize
    return {"inner": inner, "outer": outer}
