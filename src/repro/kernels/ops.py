"""Jitted public wrappers for the Pallas kernels.

This is the one place that decides ``interpret``: off-TPU the kernels
execute via the Pallas interpreter (correctness validation on CPU); on TPU
the same calls compile to fused Mosaic kernels.

All wrappers take 2-D (rows, cols) operands; ``counts`` is the optional
per-row true-element count for pad-exact scales/error-feedback (None means
no padding). View-shaped callers go through ``repro.kernels.dispatch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_adam as _fa
from repro.kernels import onebit as _ob


def _interpret_default():
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ef_compress(z, err, counts=None, block_rows: int = 8,
                interpret: bool | None = None):
    """Single-pass fused EF-compress with per-row scales."""
    if interpret is None:
        interpret = _interpret_default()
    return _ob.ef_compress(z, err, counts, block_rows=block_rows,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def abs_rowsum(z, err, counts=None, block_rows: int = 8,
               interpret: bool | None = None):
    """Masked per-row L1 sums of z + err (two-pass compress, pass 1)."""
    if interpret is None:
        interpret = _interpret_default()
    return _ob.abs_rowsum(z, err, counts, block_rows=block_rows,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ef_quantize(z, err, scales, counts=None, block_rows: int = 8,
                interpret: bool | None = None):
    """Quantize z + err against per-row scales (two-pass compress, pass 2)."""
    if interpret is None:
        interpret = _interpret_default()
    return _ob.ef_quantize(z, err, scales, counts, block_rows=block_rows,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "dtype"))
def decompress(packed, scales, block_rows: int = 8,
               interpret: bool | None = None, dtype=jnp.float32):
    if interpret is None:
        interpret = _interpret_default()
    return _ob.decompress(packed, scales, block_rows=block_rows,
                          interpret=interpret, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("beta1", "eps", "block",
                                             "interpret"))
def fused_local_step(g, m, u, v, lr, beta1: float = 0.9, eps: float = 1e-8,
                     block=(8, 1024), interpret: bool | None = None):
    if interpret is None:
        interpret = _interpret_default()
    return _fa.fused_local_step(g, m, u, v, lr, beta1, eps=eps, block=block,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta1", "block", "interpret"))
def fused_local_step_sgd(g, m, u, lr, beta1: float = 0.9,
                         block=(8, 1024), interpret: bool | None = None):
    if interpret is None:
        interpret = _interpret_default()
    return _fa.fused_local_step_sgd(g, m, u, lr, beta1, block=block,
                                    interpret=interpret)
