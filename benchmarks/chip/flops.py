"""Operations and bytes the benchmark's metrics divide by, from shapes alone:
the counts module (``benchmarks/chip/__init__.py``) of the dense pre-LN
transformer that ``bert-large`` and ``gpt2`` run.

Two counts, both from a configuration file's ``model`` block:

* ``model_flops_per_token``: the operations one trained token requires in
  the forward and backward passes, PaLM's convention (Chowdhery et al.
  2022, appendix B): 6 per matmul parameter, plus 12 * layers * (heads x
  head size) * sequence length for the attention scores and their
  weighted sum, not halved for a causal mask. Recomputed operations
  (``remat``) do not count; the output head counts at the published
  vocabulary, the padded rows are not required work.
* ``optimizer_bytes``: the HBM bytes one step of 0/1 Adam must move in the
  optimizer, f32 state, per step kind: every state buffer the step reads,
  once, and every one it writes, once. Packed sign bits and scales are
  left out, so this is a lower bound on the traffic. Every leaf of this
  model takes the data-parallel path.
"""
from __future__ import annotations

F32 = 4


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 256)
    return -(-m["vocab"] // mult) * mult


def _attn_width(m: dict) -> int:
    return m["n_heads"] * (m.get("head_dim") or m["d_model"] // m["n_heads"])


def param_count(m: dict) -> int:
    """Trainable elements as the program lays them out (padded vocabulary
    rows included: the optimizer keeps state for them)."""
    d, L, ff = m["d_model"], m["n_layers"], m["d_ff"]
    hw = _attn_width(m)
    kv = m.get("n_kv", m["n_heads"]) * (hw // m["n_heads"])
    V = padded_vocab(m)
    total = V * d * (1 if m.get("tie_embeddings") else 2)
    if m.get("rope") == "learned":
        total += m["max_seq"] * d
    norm = 2 * d if m.get("norm_type", "layernorm") == "layernorm" else d
    total += norm                                        # final norm
    attn = d * hw + 2 * d * kv + hw * d
    if m.get("attn_bias"):
        attn += hw + 2 * kv
    if m.get("mlp_type", "gelu") == "gelu":
        mlp = 2 * d * ff + ff + d
    else:
        mlp = 3 * d * ff
    total += L * (2 * norm + attn + mlp)
    return total


def matmul_params(m: dict) -> int:
    """Parameters that multiply each token once in the forward pass."""
    d, L, ff = m["d_model"], m["n_layers"], m["d_ff"]
    hw = _attn_width(m)
    kv = m.get("n_kv", m["n_heads"]) * (hw // m["n_heads"])
    n_mlp = 2 if m.get("mlp_type", "gelu") == "gelu" else 3
    return L * (d * hw + 2 * d * kv + hw * d + n_mlp * d * ff) \
        + d * m["vocab"]


def model_flops_per_token(m: dict, seq_len: int) -> float:
    return float(6 * matmul_params(m)
                 + 12 * m["n_layers"] * _attn_width(m) * seq_len)


def optimizer_bytes_per_param(kind: str, n_workers: int) -> float:
    """Bytes per parameter one optimizer step of ``kind`` must move.

    ``local``: reads g, x, m, v, u; writes x, m, u.
    ``sync``: reads g, m, v, u, anchor, worker error, and this worker's
    1/n chunk of the server error; writes x, m, u, anchor, worker error
    and the server-error chunk (x comes from the anchor, so the old x is
    not needed).
    ``+var`` (``local+var``, ``sync+var``): also writes v.
    """
    base, _, var = kind.partition("+")
    if base == "local":
        b = (5 + 3) * F32
    elif base == "sync":
        b = (6 + 5) * F32 + 2 * F32 / n_workers
    else:
        raise ValueError(f"unknown step kind {kind!r}")
    if var:
        if var != "var":
            raise ValueError(f"unknown step kind {kind!r}")
        b += F32
    return float(b)


def optimizer_bytes(m: dict, n_workers: int, kind: str) -> float:
    return param_count(m) * optimizer_bytes_per_param(kind, n_workers)
