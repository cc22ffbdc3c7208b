"""Deterministic synthetic data pipelines.

The LM stream has learnable structure (a latent bigram process over a
zipf-weighted vocabulary) so training losses genuinely decrease and the
optimizer-comparison benchmarks (paper Fig. 2) have signal to converge on.
Everything is a pure function of (seed, step) — reproducible across hosts
with zero coordination, which is exactly what a multi-pod data pipeline
needs (each worker slices its own batch shard by index).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 1234
    kind: str = "lm"             # lm | mlm | classify
    mlm_mask_frac: float = 0.15
    n_classes: int = 8


def _bigram_table(vocab: int, seed: int) -> np.ndarray:
    """Sparse-ish random bigram transition targets: tok -> 4 candidates."""
    rng = np.random.RandomState(seed)
    return rng.randint(0, vocab, size=(vocab, 4)).astype(np.int32)


@functools.partial(jax.jit, static_argnames=(
    "global_batch", "seq_len", "kind", "mlm_mask_frac"))
def _lm_batch(table, seed_key, step, *, global_batch: int, seq_len: int,
              kind: str, mlm_mask_frac: float):
    """One batch, from ``(seed, step)`` to the output dict, as one program
    per batch shape and kind. The seed's key and the step are arguments, so
    a new seed or step compiles nothing."""
    key = jax.random.fold_in(seed_key, step)
    B, S, vocab = global_batch, seq_len, table.shape[0]
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (B,), 0, vocab)
    choice = jax.random.randint(k2, (B, S), 0, 4)
    noise = jax.random.bernoulli(k3, 0.1, (B, S))
    nz = jax.random.randint(jax.random.fold_in(k3, 1), (B, S), 0, vocab)

    def step_fn(tok, xs):
        ch, nv, nzv = xs
        nxt = jnp.where(nv, nzv, table[tok, ch])
        return nxt, nxt

    _, toks = jax.lax.scan(step_fn, first, (choice.T, noise.T, nz.T))
    tokens = jnp.concatenate([first[:, None], toks.T[:, :-1]], axis=1)
    labels = toks.T
    out = {"tokens": tokens.astype(jnp.int32),
           "labels": labels.astype(jnp.int32)}
    if kind == "mlm":
        km = jax.random.fold_in(key, 99)
        mask = jax.random.bernoulli(km, mlm_mask_frac, (B, S))
        out["labels"] = out["tokens"]
        out["tokens"] = jnp.where(mask, 0, out["tokens"])  # 0 = [MASK]
        out["loss_mask"] = mask.astype(jnp.float32)
    return out


class SyntheticLM:
    """Latent bigram LM stream; ~2 bits of predictable structure/token.

    ``batch`` dispatches one jitted program and returns without waiting;
    instances with equal batch shapes and kind share its executable."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        self.table = jnp.asarray(_bigram_table(cfg.vocab, cfg.seed))
        self.seed_key = jax.random.PRNGKey(cfg.seed)

    def batch(self, step: int) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        with telemetry.span("data.batch"):
            return _lm_batch(self.table, self.seed_key, step,
                             global_batch=cfg.global_batch,
                             seq_len=cfg.seq_len, kind=cfg.kind,
                             mlm_mask_frac=cfg.mlm_mask_frac)


class SyntheticClassify:
    """Linearly-separable-ish classification (GLUE/ImageNet quality proxy)."""

    def __init__(self, dim: int, n_classes: int, seed: int = 7):
        rng = np.random.RandomState(seed)
        self.w = jnp.asarray(rng.randn(dim, n_classes).astype(np.float32))
        self.dim, self.n_classes, self.seed = dim, n_classes, seed

    def batch(self, step: int, batch_size: int):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        x = jax.random.normal(key, (batch_size, self.dim))
        logits = x @ self.w
        noise = 0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                        logits.shape)
        y = jnp.argmax(logits + noise, axis=-1)
        return x, y


def worker_shard(batch: Dict[str, jnp.ndarray], idx: int, n: int):
    """Deterministic per-worker slice of a global batch (host pipelines)."""
    def sl(x):
        per = x.shape[0] // n
        return x[idx * per:(idx + 1) * per]
    return jax.tree.map(sl, batch)
