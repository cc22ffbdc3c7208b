"""Pallas TPU kernels: fused local half-steps, one per base kind.

``fused_local_step`` fuses the 0/1 **Adam** per-step elementwise chain
(Algorithm 1 lines 3-5):

    m' = β₁·m + (1−β₁)·g
    Δ  = γ·m' / sqrt(v + ε)        (applied to x outside, natural shape)
    u' = u + γ·m'

into one VMEM pass: 4 reads + 3 writes instead of ~10 memory sweeps as
separate XLA ops — the optimizer becomes strictly HBM-bandwidth-bound at
~7 bytes/param/step.

``fused_local_step_sgd`` is the momentum-SGD (0/1-SGD) variant — no second
moment, Δ = γ·m'. The LAMB base reuses the Adam kernel and applies its
per-leaf trust scalar outside the kernel (one cheap broadcast multiply),
keeping the fused/unfused bit-parity contract: both paths compute
``trust * ((γ·m')/sqrt(v+ε))``.

Operands are 2-D tiles of the comm view; scalars (γ, β₁) arrive as (1, 1)
operands so one compiled kernel serves every step. The chain is purely
elementwise, so model-sharded views need no cross-shard traffic at all:
``dispatch.fused_local_step_view`` runs this kernel per shard under its
``shard_map`` partitioning rule with the shard-local frame, and the
results compose to the global update by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(g_ref, m_ref, u_ref, v_ref, lr_ref, b1_ref, omb1_ref,
                  m_out, u_out, delta_out, *, eps):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0].astype(jnp.float32)
    b1 = b1_ref[0, 0].astype(jnp.float32)
    # 1-β₁ is folded at trace time (f64) and shipped as its own operand:
    # recomputing it in f32 here is 1 ulp off the unfused XLA path and
    # breaks the use_pallas on/off bit-parity contract
    omb1 = omb1_ref[0, 0].astype(jnp.float32)
    mh = b1 * m + omb1 * g
    # divide (not rsqrt) so use_pallas=True reproduces the unfused XLA path
    # bit-for-bit in f32; rsqrt is ~1 ulp off and breaks step-parity tests
    delta = lr * mh / jnp.sqrt(v + eps)
    m_out[...] = mh.astype(m_out.dtype)
    u_out[...] = (u + lr * mh).astype(u_out.dtype)
    delta_out[...] = delta.astype(delta_out.dtype)


def fused_local_step(g, m, u, v, lr, beta1, *, eps=1e-8,
                     block=(8, 1024), interpret: bool):
    """One fused 0/1 Adam local step over (R, C) views.

    Returns (m', u', delta). ``lr`` traced scalar; β₁ static-ish scalar.
    """
    R, C = g.shape
    br, bc = min(block[0], R), min(block[1], C)
    assert R % br == 0 and C % bc == 0, (g.shape, block)
    grid = (R // br, C // bc)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    b1_arr = jnp.asarray(beta1, jnp.float32).reshape(1, 1)
    omb1_arr = jnp.asarray(1.0 - beta1, jnp.float32).reshape(1, 1)
    tile = lambda: pl.BlockSpec((br, bc), lambda i, j: (i, j))
    scal = lambda: pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, eps=eps),
        grid=grid,
        in_specs=[tile(), tile(), tile(), tile(), scal(), scal(), scal()],
        out_specs=[tile(), tile(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), m.dtype),
            jax.ShapeDtypeStruct((R, C), u.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
        name="fused_local_step",
    )(g, m, u, v, lr_arr, b1_arr, omb1_arr)


def _fused_kernel_sgd(g_ref, m_ref, u_ref, lr_ref, b1_ref, omb1_ref,
                      m_out, u_out, delta_out):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0].astype(jnp.float32)
    b1 = b1_ref[0, 0].astype(jnp.float32)
    omb1 = omb1_ref[0, 0].astype(jnp.float32)
    mh = b1 * m + omb1 * g
    delta = lr * mh
    m_out[...] = mh.astype(m_out.dtype)
    u_out[...] = (u + delta).astype(u_out.dtype)
    delta_out[...] = delta.astype(delta_out.dtype)


def fused_local_step_sgd(g, m, u, lr, beta1, *, block=(8, 1024),
                         interpret: bool):
    """One fused momentum-SGD local step over (R, C) views.

    Returns (m', u', delta) with delta = lr·m' — the no-variance analogue of
    :func:`fused_local_step`, bit-identical to the unfused jnp chain.
    """
    R, C = g.shape
    br, bc = min(block[0], R), min(block[1], C)
    assert R % br == 0 and C % bc == 0, (g.shape, block)
    grid = (R // br, C // bc)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    b1_arr = jnp.asarray(beta1, jnp.float32).reshape(1, 1)
    omb1_arr = jnp.asarray(1.0 - beta1, jnp.float32).reshape(1, 1)
    tile = lambda: pl.BlockSpec((br, bc), lambda i, j: (i, j))
    scal = lambda: pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        _fused_kernel_sgd,
        grid=grid,
        in_specs=[tile(), tile(), tile(), scal(), scal(), scal()],
        out_specs=[tile(), tile(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), m.dtype),
            jax.ShapeDtypeStruct((R, C), u.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
        name="fused_local_step_sgd",
    )(g, m, u, lr_arr, b1_arr, omb1_arr)
