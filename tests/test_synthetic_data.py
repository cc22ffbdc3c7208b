"""The synthetic LM stream (``repro.data.SyntheticLM``): every batch is the
eager pipeline's, bit for bit, and one program serves every step and
every seed of a batch shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.data import DataConfig, SyntheticLM

SEED = 2 ** 31 + 29
# the benchmark's per-chip batches: bert-large (masked LM, S=128) and
# gpt2 (causal LM, S=1024), each vocabulary padded as the models pad it
SHAPES = {"mlm": dict(vocab=30720, global_batch=32, seq_len=128),
          "lm": dict(vocab=50432, global_batch=4, seq_len=1024)}


def eager_batch(cfg: DataConfig, table, step: int):
    """The pipeline as it ran eagerly, op by op, before it was one
    program: a frozen copy kept as the oracle."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
    B, S = cfg.global_batch, cfg.seq_len
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (B,), 0, cfg.vocab)
    choice = jax.random.randint(k2, (B, S), 0, 4)
    noise = jax.random.bernoulli(k3, 0.1, (B, S))
    nz = jax.random.randint(jax.random.fold_in(k3, 1), (B, S), 0,
                            cfg.vocab)

    def step_fn(tok, xs):
        ch, nv, nzv = xs
        nxt = jnp.where(nv, nzv, table[tok, ch])
        return nxt, nxt

    _, toks = jax.lax.scan(
        step_fn, first,
        (choice.T, noise.T, nz.T))
    tokens = jnp.concatenate([first[:, None], toks.T[:, :-1]], axis=1)
    labels = toks.T
    out = {"tokens": tokens.astype(jnp.int32),
           "labels": labels.astype(jnp.int32)}
    if cfg.kind == "mlm":
        km = jax.random.fold_in(key, 99)
        mask = jax.random.bernoulli(km, cfg.mlm_mask_frac, (B, S))
        out["labels"] = out["tokens"]
        out["tokens"] = jnp.where(mask, 0, out["tokens"])
        out["loss_mask"] = mask.astype(jnp.float32)
    return out


def _cfg(kind: str, seed: int = SEED) -> DataConfig:
    return DataConfig(seed=seed, kind=kind, **SHAPES[kind])


@pytest.mark.parametrize("step", [0, 7, 2 ** 20 + 3])
@pytest.mark.parametrize("kind", ["lm", "mlm"])
def test_batch_is_the_eager_pipelines_bitwise(kind, step):
    data = SyntheticLM(_cfg(kind))
    ours = data.batch(step)
    want = eager_batch(data.cfg, data.table, step)
    assert set(ours) == set(want)
    for k in want:
        assert ours[k].dtype == want[k].dtype, k
        assert ours[k].shape == want[k].shape, k
        np.testing.assert_array_equal(np.asarray(ours[k]),
                                      np.asarray(want[k]), err_msg=k)


@pytest.mark.parametrize("kind", ["lm", "mlm"])
def test_later_steps_compile_nothing(kind):
    counters = telemetry.CompileCounters.install()
    data = SyntheticLM(_cfg(kind, seed=11))
    jax.block_until_ready(data.batch(0))
    snap = counters.snapshot()
    for step in (1, 2, 2 ** 20 + 3):
        jax.block_until_ready(data.batch(step))
    done = counters.since(snap)
    assert done["compiles"] == 0, done["compiles_by_fun"]
    assert done["traces"] == 0


@pytest.mark.parametrize("seed", [12, 13, 2 ** 32 - 1])
def test_equal_shapes_share_the_program(seed):
    """A second instance, of the same configuration or of another seed,
    compiles nothing and still draws its own seed's batches."""
    counters = telemetry.CompileCounters.install()
    jax.block_until_ready(SyntheticLM(_cfg("mlm", seed=12)).batch(3))
    snap = counters.snapshot()
    second = SyntheticLM(_cfg("mlm", seed=seed))
    ours = jax.block_until_ready(second.batch(4))
    done = counters.since(snap)
    assert done["compiles"] == 0, done["compiles_by_fun"]
    assert done["traces"] == 0
    want = eager_batch(second.cfg, second.table, 4)
    for k in want:
        np.testing.assert_array_equal(np.asarray(ours[k]),
                                      np.asarray(want[k]), err_msg=k)
