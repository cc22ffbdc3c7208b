"""Pallas TPU kernels: fused error-feedback 1-bit compression.

The compression hot-path of 0/1 Adam touches every parameter byte three
times when expressed as separate XLA ops (add error, compute scale+sign,
write error). These kernels fuse the whole worker-side EF-compress into one
or two VMEM passes per tile:

    zw   = z + err_in
    s    = masked-mean(|zw|) at the requested granularity
    bits = zw >= 0  -> packed uint8 (8 lanes per byte)
    err  = (zw - sign(zw)·s) · mask

Layout: operands are 2-D (rows, cols) — the optimizer's comm views reshape
to this frame (see ``compressor.view_to_2d``). Tiles are (BLOCK_R, cols): a
full row per tile so row reductions stay in-register; BLOCK_R is 8, or the
whole frame when its row count is not a multiple of 8 (the two row blocks
the TPU accepts, see ``dispatch._tile``); cols must be a multiple of 8 for
packing. Flatten views are padded and folded so their
frame cols are 128-lane aligned and capped at ``FRAME_MAX_COLS`` (VMEM
bound); structured views keep their model-local last dim.

Pad-exactness: each row carries a true-element *count* (padding is always a
row tail or a whole row — see compressor.view_row_counts); the kernels
rebuild the elementwise mask as ``iota(cols) < count`` so scales and error
feedback never see padding. ``counts=None`` means "no padding".

Scale granularities (tensor / chunk / row of the comm view) that span
multiple 2-D rows use a two-pass reduction: ``abs_rowsum`` produces masked
per-row L1 sums, the (R,)-sized combine runs as plain XLA, and
``ef_quantize`` consumes the broadcast per-row scales. The single-pass
``ef_compress`` covers the per-row granularity. ``kernels/dispatch.py``
picks the pass structure per leaf.

Sharding: these kernels are deliberately shard-oblivious — they see one
device's (rows, cols) frame and nothing else. Model-sharded views reach
them through ``dispatch._shard_wrap`` (a manual ``shard_map`` over the
view's mesh axes, the partitioning rule): the frame they receive is then
the shard-LOCAL 2-D fold, and the cross-shard parts of a scale —
the model-axis psum and the global denominator (``layout.rest_factor``) —
happen in the plain-XLA combine between the two passes, never inside a
kernel. That keeps every kernel a pure local map, so one implementation
serves unsharded, manual-TP, and GSPMD-sharded views bit-identically.

TPU layout: per-row counts and scales cross the kernel boundary as
(R, 1) columns, blocked (block_rows, 1), so every block is 2-D with a full
last dim. Bit packing and unpacking move data between lanes (8 columns
<-> 1 byte), which the TPU's vector unit cannot reshape; both run as
exact 0/1 x power-of-two matmuls on the MXU against a block-diagonal
weight built from iotas, per 1024-column tile (128 output bytes, one lane
row). Every product and sum is an integer <= 255, exact in bf16 inputs
with f32 accumulation, so the bytes stay identical to ``jnp.packbits``.

``interpret`` has no default here: ``kernels/ops.py`` decides it from the
backend, so a caller on the TPU never runs the interpreter unawares.
Correctness is validated on CPU in interpret mode against ref.py
(tests/test_kernels.py + tests/test_pallas_parity.py); tests/
test_tpu_compile.py compiles every kernel for a described v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# columns packed per MXU pass: 1024 bits -> 128 bytes, one full lane row
_PACK_TILE = 1024


def _tiles(c):
    """Static (start, width) column tiles of a c-wide row: full
    ``_PACK_TILE`` tiles, then the remainder (a multiple of 8)."""
    return [(t, min(_PACK_TILE, c - t)) for t in range(0, c, _PACK_TILE)]


def _bit_layout(shape, bit_axis):
    """Big-endian bit layout of one column tile (as ``jnp.packbits``):
    over a grid of bit columns along ``bit_axis`` and bytes along the other
    axis, (bit i belongs to byte j, 2**(7 - i % 8))."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, bit_axis)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - bit_axis)
    return i // 8 == j, 128 >> (i % 8)


def _bf16_dot(a, b):
    """bf16 product with f32 accumulation, exact for the 0/1 x power-of-two
    (and byte x 0/1) operands of the bit (un)packing. The precision is
    fixed: under a caller's ``default_matmul_precision("highest")`` Mosaic
    would be asked for an fp32 contraction of bf16 operands, and refuses
    it."""
    return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32)


def _spread(width):
    """(width, width // 8) bf16 block-diagonal weights: bit column i adds
    its weight to byte i // 8."""
    member, weight = _bit_layout((width, width // 8), 0)
    return jnp.where(member, weight, 0).astype(jnp.bfloat16)


def _row_mask(cnt, r, c):
    """(r, c) bool mask from (r, 1) per-row true counts."""
    return jax.lax.broadcasted_iota(jnp.int32, (r, c), 1) < cnt


def _store_packed(bits, packed_ref):
    """(r, c) bool -> packed_ref (r, c//8) uint8, bit-identical to
    ``jnp.packbits(..., bitorder="big")``."""
    for t, w in _tiles(bits.shape[1]):
        b = bits[:, t:t + w].astype(jnp.bfloat16)
        byte = _bf16_dot(b, _spread(w))
        packed_ref[:, t // 8:(t + w) // 8] = (
            byte.astype(jnp.int32).astype(packed_ref.dtype))


def _ef_compress_kernel(z_ref, err_ref, cnt_ref, packed_ref, scale_ref,
                        errout_ref):
    zw = z_ref[...].astype(jnp.float32) + err_ref[...].astype(jnp.float32)
    r, c = zw.shape
    cnt = cnt_ref[...]
    mask = _row_mask(cnt, r, c)
    s = (jnp.where(mask, jnp.abs(zw), 0.0).sum(axis=1, keepdims=True)
         / jnp.maximum(cnt.astype(jnp.float32), 1.0))      # (BLOCK_R, 1)
    bits = zw >= 0
    _store_packed(bits, packed_ref)
    scale_ref[...] = s.astype(scale_ref.dtype)
    zhat = jnp.where(bits, s, -s)
    errout_ref[...] = jnp.where(mask, zw - zhat, 0.0).astype(errout_ref.dtype)


def _col(x, R):
    """(R,) -> (R, 1): the 2-D column layout the kernels block over."""
    return x.reshape(R, 1)


def _counts(counts, R, C):
    if counts is None:
        return jnp.full((R, 1), C, jnp.int32)
    return _col(counts.astype(jnp.int32), R)


def _check(R, C, block_rows):
    assert C % 8 == 0, C
    assert R % block_rows == 0, (R, block_rows)


def ef_compress(z: jnp.ndarray, err: jnp.ndarray, counts=None, *,
                block_rows: int = 8, interpret: bool):
    """Fused single-pass EF 1-bit compress over (R, C) with per-row scales.
    Returns (packed u8 (R, C//8), scales f32 (R,), err_out like err)."""
    R, C = z.shape
    _check(R, C, block_rows)
    row = lambda w: pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    packed, scales, err_out = pl.pallas_call(
        _ef_compress_kernel,
        grid=(R // block_rows,),
        in_specs=[row(C), row(C), row(1)],
        out_specs=[row(C // 8), row(1), row(C)],
        out_shape=[
            jax.ShapeDtypeStruct((R, C // 8), jnp.uint8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, C), err.dtype),
        ],
        interpret=interpret,
        name="ef_compress",
    )(z, err, _counts(counts, R, C))
    return packed, scales.reshape(R), err_out


def _abs_rowsum_kernel(z_ref, err_ref, cnt_ref, out_ref):
    zw = z_ref[...].astype(jnp.float32) + err_ref[...].astype(jnp.float32)
    r, c = zw.shape
    mask = _row_mask(cnt_ref[...], r, c)
    out_ref[...] = jnp.where(mask, jnp.abs(zw), 0.0).sum(axis=1,
                                                         keepdims=True)


def abs_rowsum(z: jnp.ndarray, err: jnp.ndarray, counts=None, *,
               block_rows: int = 8, interpret: bool):
    """Pass 1 of the two-pass EF-compress: masked per-row L1 sums of
    ``z + err``. Returns f32 (R,)."""
    R, C = z.shape
    _check(R, C, block_rows)
    row = lambda w: pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    return pl.pallas_call(
        _abs_rowsum_kernel,
        grid=(R // block_rows,),
        in_specs=[row(C), row(C), row(1)],
        out_specs=row(1),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
        name="abs_rowsum",
    )(z, err, _counts(counts, R, C)).reshape(R)


def _ef_quantize_kernel(z_ref, err_ref, scale_ref, cnt_ref, packed_ref,
                        errout_ref):
    zw = z_ref[...].astype(jnp.float32) + err_ref[...].astype(jnp.float32)
    r, c = zw.shape
    mask = _row_mask(cnt_ref[...], r, c)
    s = scale_ref[...].astype(jnp.float32)                 # (BLOCK_R, 1)
    bits = zw >= 0
    _store_packed(bits, packed_ref)
    zhat = jnp.where(bits, s, -s)
    errout_ref[...] = jnp.where(mask, zw - zhat, 0.0).astype(errout_ref.dtype)


def ef_quantize(z: jnp.ndarray, err: jnp.ndarray, scales: jnp.ndarray,
                counts=None, *, block_rows: int = 8, interpret: bool):
    """Pass 2 of the two-pass EF-compress: quantize ``z + err`` against
    precomputed per-row scales (R,). Returns (packed u8 (R, C//8), err_out)."""
    R, C = z.shape
    _check(R, C, block_rows)
    row = lambda w: pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    return pl.pallas_call(
        _ef_quantize_kernel,
        grid=(R // block_rows,),
        in_specs=[row(C), row(C), row(1), row(1)],
        out_specs=[row(C // 8), row(C)],
        out_shape=[
            jax.ShapeDtypeStruct((R, C // 8), jnp.uint8),
            jax.ShapeDtypeStruct((R, C), err.dtype),
        ],
        interpret=interpret,
        name="ef_quantize",
    )(z, err, _col(scales, R), _counts(counts, R, C))


def _decompress_kernel(packed_ref, scale_ref, out_ref):
    s = scale_ref[...].astype(jnp.float32)                 # (BLOCK_R, 1)
    for t, w in _tiles(out_ref.shape[1]):
        byte = packed_ref[:, t // 8:(t + w) // 8].astype(jnp.int32)
        # each byte repeated over its 8 bit columns (unit weights, values
        # <= 255, exact), then the bit of each column picked by its weight
        member, _ = _bit_layout((w // 8, w), 1)
        rep = jnp.where(member, 1.0, 0.0).astype(jnp.bfloat16)
        v = _bf16_dot(byte.astype(jnp.float32).astype(jnp.bfloat16),
                      rep).astype(jnp.int32)
        _, weight = _bit_layout((1, w), 1)
        out_ref[:, t:t + w] = jnp.where((v & weight) != 0, s,
                                        -s).astype(out_ref.dtype)


def decompress(packed: jnp.ndarray, scales: jnp.ndarray, *,
               block_rows: int = 8, interpret: bool, dtype=jnp.float32):
    """Inverse quantizer over (R, C//8) packed + per-row scales."""
    R, CB = packed.shape
    assert R % block_rows == 0, (R, block_rows)
    row = lambda w: pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    return pl.pallas_call(
        _decompress_kernel,
        grid=(R // block_rows,),
        in_specs=[row(CB), row(1)],
        out_specs=row(CB * 8),
        out_shape=jax.ShapeDtypeStruct((R, CB * 8), dtype),
        interpret=interpret,
        name="decompress",
    )(packed, _col(scales, R))
