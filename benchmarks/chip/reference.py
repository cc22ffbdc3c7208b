"""Plain reference of what the timed path computes: model, loss, gradients,
0/1 Adam with the sign-1-bit error-feedback exchange, and the traffic.

Written from the published descriptions in straightforward ``jax.numpy``
and imports nothing of the program. It follows the configuration file's
``model`` block (a pre-LN transformer with learned positions and rotary
embeddings on q and k, GELU MLP and LayerNorm, as the configuration's
``departures`` list) and the algorithm
of the 0/1 Adam paper (Lu et al., ICLR 2023, Algorithms 1 and 2):

  m' = b1 m + (1 - b1) g ;  x' = x - lr m' / sqrt(v + eps) ;  u' = u + lr m'
  sync step:  ubar = 1bit-allreduce(u')  (worker and server error feedback,
              scale = mean |.| per tensor at the worker, per chunk at the
              server, sign(0) = +1);  m = ubar / (sum of lr since the last
              sync);  x = anchor - ubar / sqrt(v_old + eps);  anchor = x;
              u = 0
  var step:   v = b2 v + (1 - b2) gbar^2,  gbar the bf16 wire mean of g

The server chunk of a leaf with n workers: the leaf's largest axis that
Megatron tensor parallelism would not shard (dims divisible by 16 on the
vocabulary and head/ffn axes), split in n equal parts after padding; a
leaf with no such sharded axis is flattened and padded to n x 128 first.

``precision="float32"`` is the reference (float32 everywhere, matmuls at
``highest``); ``precision="bfloat16"`` is the control: the configuration's
float32 lowered to bfloat16 throughout, the model computed in bfloat16
(LayerNorm statistics, softmax and loss in float32) and parameters and
optimizer state stored in bfloat16 (updates computed in float32 from the
stored values). ``fault`` plants one fault of the comparison's list.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# name -> (dtype the model computes in, matmul precision, dtype parameters
# and optimizer state are stored in)
PRECISIONS = {"float32": (jnp.float32, HIGHEST, jnp.float32),
              "bfloat16": (jnp.bfloat16, jax.lax.Precision.DEFAULT,
                           jnp.bfloat16)}


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Leaf:
    path: str
    shape: tuple
    init: str            # normal | zeros | ones
    tp_axis: Optional[int] = None   # axis tensor parallelism would shard


def _tp(dim: int) -> bool:
    return dim % 16 == 0


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 256)
    return -(-m["vocab"] // mult) * mult


def leaves(m: dict) -> List[Leaf]:
    """The parameter leaves, keyed by the path the weights are drawn from."""
    d, L, ff, H = m["d_model"], m["n_layers"], m["d_ff"], m["n_heads"]
    hd = m.get("head_dim") or d // H
    hw = H * hd
    kw = m.get("n_kv", H) * hd
    V = padded_vocab(m)
    out = [Leaf("embed", (V, d), "normal", 0 if _tp(V) else None),
           Leaf("final_norm/bias", (d,), "zeros"),
           Leaf("final_norm/scale", (d,), "ones"),
           Leaf("pos_embed", (m["max_seq"], d), "normal")]
    if not m.get("tie_embeddings"):
        out.append(Leaf("lm_head", (d, V), "normal", 1 if _tp(V) else None))
    ax = lambda n, a: a if _tp(n) else None
    blk = [("attn/bk", (L, kw), "zeros", ax(kw, 1)),
           ("attn/bq", (L, hw), "zeros", ax(hw, 1)),
           ("attn/bv", (L, kw), "zeros", ax(kw, 1)),
           ("attn/wk", (L, d, kw), "normal", ax(kw, 2)),
           ("attn/wo", (L, hw, d), "normal", ax(hw, 1)),
           ("attn/wq", (L, d, hw), "normal", ax(hw, 2)),
           ("attn/wv", (L, d, kw), "normal", ax(kw, 2)),
           ("attn_norm/bias", (L, d), "zeros", None),
           ("attn_norm/scale", (L, d), "ones", None),
           ("mlp/b_in", (L, ff), "zeros", ax(ff, 1)),
           ("mlp/b_out", (L, d), "zeros", None),
           ("mlp/w_in", (L, d, ff), "normal", ax(ff, 2)),
           ("mlp/w_out", (L, ff, d), "normal", ax(ff, 1)),
           ("mlp_norm/bias", (L, d), "zeros", None),
           ("mlp_norm/scale", (L, d), "ones", None)]
    out += [Leaf("blocks/" + p, s, i, a) for p, s, i, a in blk]
    return sorted(out, key=lambda lf: lf.path)


def init_params(m: dict, key, init_std: float = 0.02) -> Dict[str, jnp.ndarray]:
    """Seeded weights: N(0, init_std) per leaf from the seed folded with the
    CRC-32 of the leaf's path; norms' scales 1 and every bias 0."""
    out = {}
    for lf in leaves(m):
        if lf.init == "zeros":
            out[lf.path] = jnp.zeros(lf.shape, jnp.float32)
        elif lf.init == "ones":
            out[lf.path] = jnp.ones(lf.shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, zlib.crc32(lf.path.encode())
                                   & 0x7FFFFFFF)
            out[lf.path] = (jax.random.normal(k, lf.shape)
                            * init_std).astype(jnp.float32)
    return out


# --------------------------------------------------------------------------
# traffic: the latent-bigram token stream, from (seed, step)
# --------------------------------------------------------------------------

def bigram_table(vocab: int, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    return rng.randint(0, vocab, size=(vocab, 4)).astype(np.int32)


def batch(table: np.ndarray, seed: int, step: int, B: int, S: int,
          mlm: bool, mask_frac: float = 0.15) -> Dict[str, np.ndarray]:
    """Rows of one step: a first token, then each next token one of the
    previous token's 4 bigram successors, replaced by a uniform draw with
    probability 0.1. Masked-LM hides ``mask_frac`` of the positions (token
    0) and predicts the originals; causal LM predicts the next token."""
    V = table.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k1, k2, k3 = jax.random.split(key, 3)
    first = np.asarray(jax.random.randint(k1, (B,), 0, V))
    choice = np.asarray(jax.random.randint(k2, (B, S), 0, 4))
    noise = np.asarray(jax.random.bernoulli(k3, 0.1, (B, S)))
    nz = np.asarray(jax.random.randint(jax.random.fold_in(k3, 1), (B, S),
                                       0, V))
    nxt = np.empty((B, S), np.int32)
    tok = first
    for s in range(S):
        tok = np.where(noise[:, s], nz[:, s], table[tok, choice[:, s]])
        nxt[:, s] = tok
    tokens = np.concatenate([first[:, None], nxt[:, :-1]], axis=1)
    out = {"tokens": tokens.astype(np.int32), "labels": nxt}
    if mlm:
        km = jax.random.fold_in(key, 99)
        mask = np.asarray(jax.random.bernoulli(km, mask_frac, (B, S)))
        out["labels"] = out["tokens"]
        out["tokens"] = np.where(mask, 0, out["tokens"]).astype(np.int32)
        out["loss_mask"] = mask.astype(np.float32)
    return out


# --------------------------------------------------------------------------
# model and loss
# --------------------------------------------------------------------------

def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x * x * x)))


def _rope(x, theta):
    """Rotary embedding over the whole head, rotate-half pairing, positions
    0..S-1: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, b, m: dict, dtype=jnp.float32, precision=HIGHEST):
    """Mean cross-entropy of one worker's rows."""
    mm = lambda x, w: jnp.matmul(x, w, precision=precision)
    c = lambda x: x.astype(dtype)
    tokens = b["tokens"]
    B, S = tokens.shape
    H = m["n_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    h = c(params["embed"])[tokens] + c(params["pos_embed"])[:S][None]
    causal = m.get("causal", True)
    theta = float(m.get("rope_theta", 10000.0))
    keep = (jnp.tril(jnp.ones((S, S), bool)) if causal
            else jnp.ones((S, S), bool))
    blocks = {k[len("blocks/"):]: v for k, v in params.items()
              if k.startswith("blocks/")}

    def layer(h, p):
        a = _layer_norm(h, p["attn_norm/scale"], p["attn_norm/bias"])
        q = (mm(a, c(p["attn/wq"])) + c(p["attn/bq"])).reshape(B, S, H, hd)
        k = (mm(a, c(p["attn/wk"])) + c(p["attn/bk"])).reshape(B, S, H, hd)
        v = (mm(a, c(p["attn/wv"])) + c(p["attn/bv"])).reshape(B, S, H, hd)
        if m.get("rope", "learned") != "none":
            # the configuration's departure: rotary on q and k as well as
            # the learned absolute positions
            q, k = _rope(q, theta), _rope(k, theta)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision)
        s = s.astype(jnp.float32) / math.sqrt(hd)
        s = jnp.where(keep, s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=precision)
        h = h + mm(o.reshape(B, S, H * hd), c(p["attn/wo"]))
        f = _layer_norm(h, p["mlp_norm/scale"], p["mlp_norm/bias"])
        f = _gelu_tanh(mm(f, c(p["mlp/w_in"])) + c(p["mlp/b_in"]))
        h = h + mm(f, c(p["mlp/w_out"])) + c(p["mlp/b_out"])
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, blocks)
    h = _layer_norm(h, params["final_norm/scale"], params["final_norm/bias"])
    head = (c(params["embed"]).T if m.get("tie_embeddings")
            else c(params["lm_head"]))
    logits = mm(h, head).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, b["labels"][..., None], -1)[..., 0]
    nll = logz - gold
    if "loss_mask" in b:
        msk = b["loss_mask"]
        return (nll * msk).sum() / jnp.maximum(msk.sum(), 1.0)
    return nll.mean()


# --------------------------------------------------------------------------
# schedule
# --------------------------------------------------------------------------

def lr_at(spec: dict, t: int) -> jnp.ndarray:
    t = jnp.float32(t)
    if spec["kind"] == "ConstantLr":
        return jnp.float32(spec["lr"])
    if spec["kind"] == "LinearWarmupExpDecay":
        peak, w = jnp.float32(spec["peak_lr"]), max(spec["warmup_steps"], 1)
        if t < spec["warmup_steps"]:
            return peak * (t + 1) / jnp.float32(w)
        k = jnp.floor((t - spec["warmup_steps"])
                      / jnp.float32(spec["decay_period"]))
        return peak * jnp.power(jnp.float32(spec["decay"]), k)
    raise ValueError(f"unknown lr schedule {spec['kind']!r}")


class Policies:
    """T_u (sync) and T_v (variance) step sets of the paper, by name."""

    def __init__(self, sync: dict, var: dict):
        self.sync, self.var = sync, var
        self.next_sync = 0
        self.var_next, self.var_j, self.var_stopped = 0, 0, False

    def interval(self, t: int) -> int:
        s = self.sync
        if s["kind"] == "EveryStepSyncPolicy":
            return 1
        if s["kind"] == "LrProportionalSyncPolicy":
            if t < s["warmup_steps"]:
                return 1
            e = min((t - s["warmup_steps"]) // s["double_every"], 30)
            return min(2 ** e, s["max_interval"])
        raise ValueError(f"unknown sync policy {s['kind']!r}")

    def step(self, t: int):
        """(sync, var) of step t."""
        iv = self.interval(t)
        sync = t >= self.next_sync
        if sync:
            self.next_sync = t + iv
        v = self.var
        if v["kind"] == "FixedWarmupPolicy":
            var = t < v["t0"]
        elif v["kind"] == "EveryStepVariancePolicy":
            var = True
        elif v["kind"] == "AdaptiveFreezePolicy":
            self.var_stopped = self.var_stopped or iv > 1
            var = t == self.var_next and not self.var_stopped
            if var:
                self.var_next = t + 2 ** min(self.var_j // v["kappa"], 30)
                self.var_j += 1
        else:
            raise ValueError(f"unknown variance policy {v['kind']!r}")
        return bool(sync), bool(var)


# --------------------------------------------------------------------------
# the 1-bit exchange: chunks, compression, error feedback
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Chunking:
    shape: tuple
    n: int
    flatten: bool
    split: int
    padded: int

    @staticmethod
    def of(lf: Leaf, n: int) -> "Chunking":
        if lf.tp_axis is None:
            total = int(np.prod(lf.shape))
            return Chunking(lf.shape, n, True, 0, -(-total // (n * 128))
                            * n * 128)
        cands = [a for a in range(len(lf.shape)) if a != lf.tp_axis]
        split = max(cands, key=lambda a: lf.shape[a])
        q = n if len(lf.shape) > 1 else n * 8
        return Chunking(lf.shape, n, False, split,
                        -(-lf.shape[split] // q) * q)

    def chunk_shape(self) -> tuple:
        rest = () if self.flatten else tuple(
            s for a, s in enumerate(self.shape) if a != self.split)
        return (self.padded // self.n,) + rest

    def true_len(self) -> int:
        return (int(np.prod(self.shape)) if self.flatten
                else self.shape[self.split])

    def view(self, x):
        """(n, padded / n, *rest); padding is zeros."""
        if self.flatten:
            f = jnp.pad(x.reshape(-1), (0, self.padded - x.size))
            return f.reshape(self.n, -1)
        x = jnp.moveaxis(x, self.split, 0)
        x = jnp.pad(x, [(0, self.padded - x.shape[0])]
                    + [(0, 0)] * (x.ndim - 1))
        return x.reshape((self.n, self.padded // self.n) + x.shape[1:])

    def unview(self, v):
        if self.flatten:
            return v.reshape(-1)[:int(np.prod(self.shape))].reshape(
                self.shape)
        x = v.reshape((self.padded,) + v.shape[2:])[:self.true_len()]
        return jnp.moveaxis(x, 0, self.split)

    def mask(self):
        pos = np.arange(self.padded).reshape(self.n, -1) < self.true_len()
        if self.flatten:
            return jnp.asarray(pos, jnp.float32)
        rest = len(self.shape) - 1
        return jnp.asarray(pos.reshape(pos.shape + (1,) * rest), jnp.float32)


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _compress(z):
    """Tensor-granularity 1-bit compression of one worker's whole leaf."""
    scale = jnp.abs(z).sum() / z.size
    zhat = _sign(z) * scale
    return zhat, z - zhat


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


# Each helper below is one jitted program per leaf shape and device, so a
# cold reference compiles a few programs per leaf and worker, not one per
# elementary op.

@functools.partial(jax.jit, static_argnums=0)
def _worker_side(c: Chunking, u, ew):
    """Worker error feedback and compression; the compressed leaf comes
    back as its n chunk rows, row j for the worker serving chunk j."""
    zhat, ew = _compress(u + ew.astype(jnp.float32))
    view = c.view(zhat)
    return ew, tuple(view[j] for j in range(c.n))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _server_side(c: Chunking, j: int, es, *rows):
    """Server j: the mean of the workers' chunk j, plus its error, in
    chunk-granularity 1-bit compression; padding is left out of the scale
    and of the error."""
    msk = c.mask()[j]
    y = sum(rows) / len(rows) + es.astype(jnp.float32)
    cnt = msk.sum() * (y.size // msk.size)
    scale = (jnp.abs(y) * msk).sum() / cnt
    yhat = _sign(y) * scale
    return yhat, (y - yhat) * msk


@functools.partial(jax.jit, static_argnums=(0, 1))
def _resync(c: Chunking, sdt, anchor, v, gamma_total, eps, *rows):
    """The synced momentum and parameters from the gathered chunks, and
    u reset to 0."""
    ubar = c.unview(jnp.stack(rows))
    anchor, v = _f32(anchor, v)
    x = anchor - ubar / jnp.sqrt(v + eps)
    return ((ubar / gamma_total).astype(sdt), x.astype(sdt),
            jnp.zeros(ubar.shape, sdt))


@functools.partial(jax.jit, static_argnums=1)
def _local(x, sdt, m, v, u, g, lr, b1, eps):
    x, m, v, u, g = _f32(x, m, v, u, g)
    mh = b1 * m + (1 - b1) * g
    return ((x - (lr * mh) / jnp.sqrt(v + eps)).astype(sdt), mh.astype(sdt),
            (u + lr * mh).astype(sdt), (u + lr * mh))


@functools.partial(jax.jit, static_argnums=0)
def _to_wire(wire, g):
    return g.astype(wire)


@functools.partial(jax.jit, static_argnums=0)
def _wire_mean(wire, *gs):
    """The full-precision round: the mean of the workers' wire-rounded
    gradients, rounded to the wire again."""
    return (sum(x.astype(jnp.float32) for x in gs) / len(gs)).astype(
        wire).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=0)
def _variance(sdt, v, gbar, b2):
    (v,) = _f32(v)
    return (b2 * v + (1 - b2) * gbar * gbar).astype(sdt)


# --------------------------------------------------------------------------
# training steps
# --------------------------------------------------------------------------

def run(m: dict, opt: dict, mix: dict, seed: int, n_workers: int,
        steps: int = 3, precision: str = "float32",
        fault: Optional[str] = None, devices: Optional[Sequence] = None):
    """Train ``steps`` steps from the seed, worker i on ``devices[i]``.
    Returns the per-step losses (mean over workers), the per-leaf norm of
    the first gradient as the optimizer receives it (read back from v
    after step 1, as the program's is), and the per-leaf norm of worker
    0's parameter change.

    ``fault``: ``None``; ``"half_batch"`` (each worker's loss and gradient
    from the first half of its rows only); ``"no_exchange"`` (nothing
    crosses between workers: each keeps its own compressed buffer and its
    own gradient)."""
    devices = list(devices or jax.devices()[:n_workers])
    if len(devices) != n_workers:
        raise ValueError(f"{n_workers} workers need as many devices, "
                         f"got {devices}")
    dtype, prec, sdt = PRECISIONS[precision]
    S = lambda a: a.astype(sdt)
    b1, b2, eps = (jnp.float32(opt[k]) for k in ("beta1", "beta2", "eps"))
    wire = jnp.dtype(opt.get("comm_dtype", "bfloat16"))
    lv = leaves(m)
    chunk = {lf.path: Chunking.of(lf, n_workers) for lf in lv}
    x0 = jax.jit(lambda k: init_params(m, k))(jax.random.PRNGKey(seed))
    x0_host = {k: np.asarray(v) for k, v in x0.items()}
    del x0
    per = mix["tokens_per_chip"] // mix["seq_len"]
    table = bigram_table(m["vocab"], seed % 2 ** 32)
    mlm = not m.get("causal", True)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, m, dtype, prec)))

    W = []
    for d in devices:
        st = {"x": {}, "anchor": {}, "m": {}, "v": {}, "u": {}, "ew": {},
              "es": {}}
        for lf in lv:
            k = lf.path
            st["x"][k] = S(jax.device_put(x0_host[k], d))
            st["anchor"][k] = S(jax.device_put(x0_host[k], d))
            for s in ("m", "v", "u", "ew"):
                st[s][k] = jax.device_put(np.zeros(lf.shape, sdt), d)
            st["es"][k] = jax.device_put(
                np.zeros(chunk[k].chunk_shape(), sdt), d)
        W.append(st)
    pol = Policies(mix["sync_policy"], mix["var_policy"])
    gamma = jnp.float32(0.0)
    losses, grad_norms = [], None
    for t in range(steps):
        sync, var = pol.step(t)
        lr = lr_at(mix["lr"], t)
        gamma_total = gamma + lr
        b = batch(table, seed % 2 ** 32, t, per * n_workers,
                  mix["seq_len"], mlm)
        gs, ls = [], []
        for i, (w, d) in enumerate(zip(W, devices)):
            rows = slice(i * per, (i + 1) * per)
            if fault == "half_batch":
                rows = slice(i * per, i * per + per // 2)
            bi = {k: jax.device_put(v[rows], d) for k, v in b.items()}
            loss, g = grad_fn(w["x"], bi)
            ls.append(loss)
            gs.append(g)
        losses.append(float(np.mean([float(x) for x in ls])))
        for lf in lv:
            k, c = lf.path, chunk[lf.path]
            half = [_local(w["x"][k], sdt, w["m"][k], w["v"][k], w["u"][k],
                           g[k], lr, b1, eps) for w, g in zip(W, gs)]
            if not sync:
                for w, (xh, mh, uh, _) in zip(W, half):
                    w["x"][k], w["m"][k], w["u"][k] = xh, mh, uh
            else:
                rows = []
                for w, (_, _, _, u32) in zip(W, half):
                    ew, r = _worker_side(c, u32, w["ew"][k])
                    w["ew"][k] = ew.astype(sdt)
                    rows.append(r)
                if fault == "no_exchange":
                    # each worker's own compressed buffer, nothing sent
                    gathered = [rows[i] for i in range(n_workers)]
                else:
                    served = []
                    for j, (w, d) in enumerate(zip(W, devices)):
                        yhat, es = _server_side(
                            c, j, w["es"][k],
                            *[jax.device_put(r[j], d) for r in rows])
                        w["es"][k] = es.astype(sdt)
                        served.append(yhat)
                    gathered = [[jax.device_put(y, d) for y in served]
                                for d in devices]
                for w, got in zip(W, gathered):
                    w["m"][k], w["x"][k], w["u"][k] = _resync(
                        c, sdt, w["anchor"][k], w["v"][k], gamma_total, eps,
                        *got)
                    w["anchor"][k] = w["x"][k]
            del half
            if var:
                wired = [_to_wire(wire, g[k]) for g in gs]
                if fault == "no_exchange":
                    gbars = [_wire_mean(wire, x) for x in wired]
                else:
                    gb = _wire_mean(wire, *[jax.device_put(x, devices[0])
                                            for x in wired])
                    gbars = [jax.device_put(gb, d) for d in devices]
                for w, gb in zip(W, gbars):
                    w["v"][k] = _variance(sdt, w["v"][k], gb, b2)
            for g in gs:
                del g[k]
        gamma = jnp.float32(0.0) if sync else gamma_total
        if t == 0:
            sums = jax.jit(lambda vs: {k: jnp.sum(v.astype(jnp.float32))
                                       for k, v in vs.items()})(W[0]["v"])
            grad_norms = {k: math.sqrt(float(s) / (1 - float(b2)))
                          for k, s in sums.items()}
    x0 = jax.device_put(x0_host, devices[0])
    change = jax.jit(lambda xs, x0: {
        k: jnp.sqrt(jnp.sum(jnp.square(xs[k].astype(jnp.float32) - x0[k])))
        for k in xs})(W[0]["x"], x0)
    change = {k: float(v) for k, v in change.items()}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}


# --------------------------------------------------------------------------
# the comparison
# --------------------------------------------------------------------------

GRAD_FLOOR = 1e-3   # leaves whose reference gradient is under this share of
                    # the median leaf's move by round-off alone under Adam


def compare(prog: dict, ref: dict) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with the step or leaf that
    gave it: ``loss``, the worst relative gap of a step's loss;
    ``grad_norm`` and ``change_norm``, the worst leaf's gap of
    first-gradient and of parameter-change norms (each leaf's gap over the
    larger of its own and the median leaf's reference norm). Leaves whose
    reference gradient is under ``GRAD_FLOOR`` of the median leaf's are
    left out of the change."""
    out = {}
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    i = int(np.argmax(gaps))
    out["loss"] = {"value": float(gaps[i]), "at": f"step {i + 1}"}
    g_ref = ref["grad_norm"]
    med_g = float(np.median(list(g_ref.values())))
    moved = sorted(k for k, v in g_ref.items() if v >= GRAD_FLOOR * med_g)
    for name, keys in (("grad_norm", sorted(g_ref)), ("change_norm", moved)):
        r = {k: ref[name][k] for k in keys}
        med = float(np.median(list(r.values())))
        gap = {k: abs(prog[name][k] - r[k]) / max(r[k], med, 1e-30)
               for k in keys}
        worst = max(gap, key=gap.get)
        out[name] = {"value": float(gap[worst]), "at": worst}
    return out
