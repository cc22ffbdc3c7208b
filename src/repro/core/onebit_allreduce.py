"""Error-feedback compressed AllReduce (paper Algorithm 2), TPU-native.

DeepSpeed implements Algorithm 2 as a custom two-phase NCCL/Gloo collective.
The TPU-idiomatic equivalent used here is a chunked scatter-reduce /
all-gather over the mesh worker axes, exchanging codec *payloads* (pytrees
of arrays — bit-packed uint8 for the default sign-1-bit codec):

  worker side   z = u + δ_w ;  (payload, δ_w') = codec.encode_worker(z)
  scatter       all_to_all of payload leaves: worker j receives every
                worker's chunk j                  — "send to server"
  server side   avg = mean_i decode(payload_i) ;  y = avg + δ_s ;
                (payload', δ_s') = codec.encode_server(y)
  gather        all_gather of the compressed chunk results — "broadcast"

With the default ``sign1bit`` codec per-worker traffic is ≈ d/8 + d/8
bytes versus 4·d for a bf16 ring AllReduce: the 32× volume reduction of
the paper, visible verbatim in the lowered HLO as uint8 collectives (this
is what the roofline's collective term reads). Other codecs
(:mod:`repro.core.codecs`: top-k, qint8/qint4, identity) trade volume for
fidelity on the same schedule; ``codec.wire_bytes`` keeps the accounting
honest per format.

All chunk bookkeeping is static (see ``compressor.make_layout``); every op
other than the two collectives is chip-local.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import codecs as CODECS
from repro.core import compressor as C
from repro.core.codecs import _server_compress  # noqa: F401 (moved to
                                                # codecs with the sign1bit
                                                # codec; alias kept for the
                                                # kernel-parity tests)
from repro.core.comm import Comm, Hierarchy


class EFState(NamedTuple):
    """Per-leaf error-feedback state for the compressed level.

    Both errors live at the level that quantizes: with a flat topology the
    worker error covers the whole comm view; with a hierarchy it covers the
    inner reduce-scatter slice this worker owns (the only buffer it ever
    compresses), and the server error the single outer chunk this pod
    serves. The uncompressed intra-pod exchanges carry no error feedback —
    they are exact up to the wire dtype.
    """

    err_worker: jnp.ndarray   # layout.ef_worker_shape (n_outer, A/n, *rest)
    err_server: jnp.ndarray   # chunk shape (A/n, *rest)


def init_ef_state(layout: C.LeafLayout, dtype=jnp.float32) -> EFState:
    return EFState(
        err_worker=jnp.zeros(layout.ef_worker_shape, dtype),
        err_server=jnp.zeros(layout.chunk_shape, dtype),
    )


@dataclasses.dataclass(frozen=True)
class OneBitConfig:
    scale_mode: C.ScaleMode = "tensor"   # paper-faithful default
    compute_dtype: jnp.dtype = jnp.float32
    quantize: bool = True                # deprecated alias: False forces the
                                         # identity codec (exact chunked mean)
    codec: Any = None                    # Codec instance or registry name;
                                         # None -> "sign1bit" (resolved at
                                         # construction, see __post_init__)
    model_axes: tuple = ()               # manual tensor-parallel axes when the
                                         # optimizer runs fully-manual (scales
                                         # psum over these)
    use_pallas: bool = False             # route EF-compress/decompress through
                                         # the fused kernels (repro.kernels);
                                         # only effective when codec.has_pallas
    hierarchy: Optional[Hierarchy] = None  # two-level topology: reduce
                                         # uncompressed over hierarchy.inner_axes,
                                         # compress only over outer_axes
    comm_dtype: jnp.dtype = jnp.bfloat16  # wire dtype of the uncompressed
                                         # intra-pod phases (hierarchy only)

    def __post_init__(self):
        C.validate_scale_mode(self.scale_mode)
        # quantize=False back-compat precedence lives in ONE place
        # (codecs.resolve_with_quantize), shared with CompressedDP so the
        # legacy and composed paths can never disagree
        codec = CODECS.resolve_with_quantize(self.codec, self.quantize)
        object.__setattr__(self, "codec", CODECS.make_codec(codec))


def _use_kernels(cfg: OneBitConfig, vspec, layout=None) -> bool:
    if not cfg.use_pallas:
        return False
    from repro.kernels import dispatch as K
    return K.kernel_codec(cfg.codec) and K.kernel_safe(vspec, layout,
                                                       cfg.model_axes)


def _flat_worker_encode(z_view, ef: EFState, layout, cfg, vspec):
    """Flat worker phase: codec encode of this worker's full view.

    Returns ``(payload, err_w, mask, use_k)`` — the mask and kernel flag
    are reused by the server phase so both phases agree on dispatch.
    """
    codec = cfg.codec
    cst = lambda x: C.constrain(x, vspec)
    mask = (C.pad_mask(layout, dtype=z_view.dtype)
            if codec.needs_ef else None)
    # Kernel dispatch: only codecs with fused kernels (sign1bit).
    # Model-sharded views run the kernels per shard under the manual
    # shard_map partitioning rule (dispatch.shard_context) when one
    # applies; otherwise dispatch.kernel_safe keeps them on the
    # constrained jnp path. The sign1bit server side of row-granularity
    # on 2-D (flatten) views also stays on jnp — it degenerates to
    # per-element scales (handled inside the codec).
    use_k = _use_kernels(cfg, vspec, layout)
    payload, err_w = codec.encode_worker(
        cst(z_view), ef.err_worker if codec.needs_ef else None, layout,
        cfg.scale_mode, mask, cfg.model_axes, use_pallas=use_k, cst=cst,
        vspec=vspec)
    return payload, err_w, mask, use_k


def _flat_server_encode(recv, ef: EFState, layout, cfg, vspec, mask, use_k,
                        widx):
    """Flat server phase: decode the received chunks, average, re-encode
    the chunk this worker serves. Returns ``(payload_s, err_s)``."""
    codec = cfg.codec
    cst = lambda x: C.constrain(x, vspec)
    vals = codec.decode(recv, layout, cfg.compute_dtype, use_pallas=use_k,
                        vspec=vspec)
    avg = cst(vals).mean(axis=0)                              # (A/n, *rest)
    s_mask = None if mask is None else mask[widx][None]
    return codec.encode_server(
        avg, ef.err_server if codec.needs_ef else None, layout,
        cfg.scale_mode, s_mask, widx, cfg.model_axes, use_pallas=use_k,
        cst=cst, vspec=vspec)


def _map_a2a(comm, payload, vspec):
    # every payload leaf carries the chunk axis first -> rows become the
    # sender index after the all_to_all.
    cst = lambda x: C.constrain(x, vspec)
    return jax.tree.map(
        lambda p: cst(comm.all_to_all(cst(p), split_axis=0, concat_axis=0)),
        payload)


def _map_gather(comm, payload, vspec):
    cst = lambda x: C.constrain(x, vspec)
    return jax.tree.map(
        lambda p: cst(comm.all_gather(cst(p), axis=0, tiled=True)),
        payload)


def onebit_allreduce_view(comm: Comm, z_view: jnp.ndarray, ef: EFState,
                          layout: C.LeafLayout, cfg: OneBitConfig,
                          vspec=None, worker_index=None):
    """Algorithm 2 over one leaf's comm view. Returns (mean estimate, EFState).

    ``z_view``: this worker's buffer in view shape (n, A/n, *rest).
    ``vspec``: tensor-parallel PartitionSpec entries of the view — threaded
    through every shape-changing op so the compressed pipeline stays
    model-sharded (see compressor.constrain).
    The returned value estimates ``mean_i z_view^{(i)}`` in view shape.

    With ``cfg.hierarchy`` set the same estimate is produced by the
    topology-aware two-level schedule (:func:`_hier_allreduce_view`); the
    flat code below is its exact ``n_inner == 1`` degenerate case.

    The wire format is ``cfg.codec``'s (sign-1-bit by default): payloads
    are pytrees whose leaves all carry the chunk-enumeration axis first, so
    the two collectives simply map over them. Exact codecs
    (``needs_ef=False``) leave the EF state untouched.
    """
    if cfg.hierarchy is not None:
        assert layout.n_inner == cfg.hierarchy.inner, (layout, cfg.hierarchy)
        return _hier_allreduce_view(comm, z_view, ef, layout, cfg, vspec,
                                    worker_index)
    codec = cfg.codec
    cst = lambda x: C.constrain(x, vspec)

    # --- worker side -------------------------------------------------------
    payload, err_w, mask, use_k = _flat_worker_encode(z_view, ef, layout,
                                                      cfg, vspec)

    # --- scatter: worker j collects chunk j from everyone ------------------
    recv = _map_a2a(comm, payload, vspec)

    # --- server side (this worker serves its chunk) -------------------------
    widx = comm.index() if worker_index is None else worker_index
    payload_s, err_s = _flat_server_encode(recv, ef, layout, cfg, vspec,
                                           mask, use_k, widx)

    # --- gather: broadcast compressed chunk results -------------------------
    gathered = _map_gather(comm, payload_s, vspec)
    out = cst(codec.decode(gathered, layout, cfg.compute_dtype,
                           use_pallas=use_k, vspec=vspec))
    if codec.needs_ef:
        ef = EFState(err_worker=cst(err_w).astype(ef.err_worker.dtype),
                     err_server=err_s.astype(ef.err_server.dtype))
    return out.astype(cfg.compute_dtype), ef


def _hier_reduce_scatter(inner, z_view, layout, cfg, vspec):
    """Hier step 1: intra-pod reduce-scatter. Returns this worker's own
    slice (inner index j)."""
    ni, no = layout.n_inner, layout.n_outer
    vs = layout.view_shape
    cst = lambda x: C.constrain(x, vspec)
    zr = z_view.reshape((ni, no) + vs[1:])
    if ni > 1:
        recv = inner.all_to_all(zr.astype(cfg.comm_dtype),
                                split_axis=0, concat_axis=0)
        own = recv.astype(jnp.float32).mean(axis=0)        # (no, A/n, *rest)
    else:
        own = zr[0]
    return cst(own.astype(cfg.compute_dtype))


def _hier_worker_encode(own, ef: EFState, layout, cfg, vspec, j):
    """Hier step 2a: codec encode of the owned slice.

    Returns ``(payload, err_w, mask_full, use_k)``."""
    codec = cfg.codec
    ni, no = layout.n_inner, layout.n_outer
    cst = lambda x: C.constrain(x, vspec)
    mask_full = (C.pad_mask(layout, dtype=own.dtype)
                 if codec.needs_ef else None)
    if mask_full is not None:
        m_slice = jnp.take(
            mask_full.reshape((ni, no) + mask_full.shape[1:]), j, axis=0)
    else:
        m_slice = None
    use_k = _use_kernels(cfg, vspec, layout)
    payload, err_w = codec.encode_worker(
        own, ef.err_worker if codec.needs_ef else None, layout,
        cfg.scale_mode, m_slice, cfg.model_axes, inner_index=j,
        use_pallas=use_k, cst=cst, vspec=vspec)
    return payload, err_w, mask_full, use_k


def _hier_server_encode(recv, ef: EFState, layout, cfg, vspec, mask_full,
                        use_k, widx):
    """Hier step 2c: server-average + re-encode of full-view chunk
    ``widx = j * n_outer + k``. Returns ``(payload_s, err_s)``."""
    codec = cfg.codec
    cst = lambda x: C.constrain(x, vspec)
    vals = codec.decode(recv, layout, cfg.compute_dtype, use_pallas=use_k,
                        vspec=vspec)
    avg = cst(vals).mean(axis=0)                           # (A/n, *rest)
    s_mask = None if mask_full is None else mask_full[widx][None]
    return codec.encode_server(
        avg, ef.err_server if codec.needs_ef else None, layout,
        cfg.scale_mode, s_mask, widx, cfg.model_axes, use_pallas=use_k,
        cst=cst, vspec=vspec)


def _hier_gather_out(inner, out_slice, layout, cfg, vspec):
    """Hier step 3: intra-pod all_gather rebuilds the full view."""
    cst = lambda x: C.constrain(x, vspec)
    vs = layout.view_shape
    if layout.n_inner > 1:
        out = inner.all_gather(out_slice.astype(cfg.comm_dtype)[None],
                               axis=0, tiled=True).reshape(vs)
    else:
        out = out_slice.reshape(vs)
    return cst(out).astype(cfg.compute_dtype)


def _hier_allreduce_view(comm: Comm, z_view: jnp.ndarray, ef: EFState,
                         layout: C.LeafLayout, cfg: OneBitConfig,
                         vspec=None, worker_index=None):
    """Topology-aware two-level AllReduce (intra-pod × inter-pod).

    Schedule, per worker (inner index j, outer index k):

      1. **intra-pod reduce-scatter** (uncompressed, wire dtype): all_to_all
         over the fast inner axes of the view reshaped (n_inner, n_outer,
         A/n, *rest); the mean over senders leaves this worker owning the
         pod-mean of slice j.
      2. **inter-pod Algorithm 2** on the owned slice: codec encode (worker
         error), all_to_all the payload across pods, server-average +
         codec encode the chunk this pod serves (server error), all_gather
         the compressed results. Identical to the flat path with n→n_outer.
      3. **intra-pod all_gather** of the decoded slice rebuilds the
         full view.

    Only step 2 crosses the slow inter-pod links — at the codec's wire
    rate — while the bulky uncompressed traffic of steps 1/3 stays inside
    the pod. With ``n_inner == 1`` steps 1/3 are skipped entirely and
    step 2 *is* the flat path (bitwise, including scale denominators),
    which the degenerate-equivalence tests pin down.
    """
    codec = cfg.codec
    h = cfg.hierarchy
    no = layout.n_outer
    cst = lambda x: C.constrain(x, vspec)
    outer, inner = comm.split(h.outer_axes, h.inner_axes)
    # (j, k) from the flat outer-major worker index: jax 0.9 cannot lower
    # axis_index of a sub-group of the worker axes inside the optimizer's
    # nested 'model' shard_map, so the caller's index is split here
    w = comm.index() if worker_index is None else worker_index
    j, k = w % layout.n_inner, w // layout.n_inner

    own = _hier_reduce_scatter(inner, z_view, layout, cfg, vspec)
    payload, err_w, mask_full, use_k = _hier_worker_encode(
        own, ef, layout, cfg, vspec, j)

    # --- 2b: inter-pod scatter: pod k collects sub-chunk k -------------------
    recv = _map_a2a(outer, payload, vspec)

    widx = j * no + k
    payload_s, err_s = _hier_server_encode(recv, ef, layout, cfg, vspec,
                                           mask_full, use_k, widx)

    # --- 2d: inter-pod gather of the compressed chunk results ---------------
    gathered = _map_gather(outer, payload_s, vspec)
    out_slice = cst(codec.decode(gathered, layout, cfg.compute_dtype,
                                 use_pallas=use_k, vspec=vspec))
    if codec.needs_ef:
        new_ef = EFState(err_worker=cst(err_w).astype(ef.err_worker.dtype),
                         err_server=err_s.astype(ef.err_server.dtype))
    else:
        new_ef = ef

    return _hier_gather_out(inner, out_slice, layout, cfg, vspec), new_ef


def fullprec_allreduce_view(comm: Comm, z_view: jnp.ndarray,
                            comm_dtype=jnp.bfloat16,
                            vspec=None, hierarchy: Optional[Hierarchy] = None,
                            layout: Optional[C.LeafLayout] = None
                            ) -> jnp.ndarray:
    """Full-precision mean over workers (used on T_v steps) at the wire
    dtype, as the paper does with fp16 training.

    Implemented as the chunked scatter-mean/all-gather (reduce-scatter +
    all-gather decomposition of a ring AllReduce: identical per-device
    traffic, ~2·d bytes). Besides matching the 1-bit path's transport, this
    sidesteps an XLA CPU-backend crash on bf16 ``all-reduce`` inside
    partial-manual shard_map (bf16 a2a/all-gather are fine; TPU unaffected).

    With ``hierarchy`` (and its ``layout``) the same mean runs the two-level
    schedule: intra-pod reduce-scatter, inter-pod exchange of the owned
    slice (1/n_inner of the traffic crosses the slow links), intra-pod
    all_gather — mirroring the 1-bit path's transport level for level.
    """
    acc = z_view.dtype
    cst = lambda x: C.constrain(x, vspec)
    if hierarchy is not None and layout is not None and layout.n_inner > 1:
        ni, no = layout.n_inner, layout.n_outer
        outer, inner = comm.split(hierarchy.outer_axes, hierarchy.inner_axes)
        zr = z_view.astype(comm_dtype).reshape((ni, no) + layout.chunk_shape)
        recv = inner.all_to_all(zr, split_axis=0, concat_axis=0)
        own = recv.astype(jnp.float32).mean(axis=0).astype(comm_dtype)
        recv2 = cst(outer.all_to_all(own, split_axis=0, concat_axis=0))
        avg = recv2.astype(jnp.float32).mean(axis=0).astype(comm_dtype)
        g1 = cst(outer.all_gather(avg[None], axis=0, tiled=True))
        out = inner.all_gather(g1[None], axis=0, tiled=True)
        return out.reshape(z_view.shape).astype(acc)
    zc = cst(z_view.astype(comm_dtype))
    recv = cst(comm.all_to_all(zc, split_axis=0, concat_axis=0))
    avg = recv.astype(jnp.float32).mean(axis=0).astype(comm_dtype)
    out = cst(comm.all_gather(avg[None], axis=0, tiled=True))
    return out.astype(acc)
