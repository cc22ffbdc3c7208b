"""Sharded fused buckets + shard-native kernel dispatch.

The contract under test (this PR's tentpole):

* TP-local flatten shards with the canonical manual-TP vspec fuse into
  *sharded* fused buckets — same-dtype, same-``rest_factor``, same-vspec
  members pack per-shard-contiguously, the bucket layout keeps the
  members' ``rest_factor`` (global scale denominators) and carries spec
  ``P(ax)``; ``scatter ∘ gather`` is the identity and true elements are
  conserved across shard boundaries;
* ``dispatch.kernel_safe`` is explicit about vspec/mesh consistency:
  model-sharded views under an ambient GSPMD-auto mesh stay on the kernel
  path exactly when ``shard_context`` can derive a per-shard plan, and a
  non-trivially sharded vspec on a *meshless* trace is only safe when the
  layout is shard-global (``rest_factor == 1``);
* the per-shard Pallas dispatch (``shard_map`` partitioning rule) is
  bitwise vs the jnp fallback on the same sharded views — asserted on a
  forced 8-host-device mesh in a subprocess (same pattern as
  test_cross_regime_parity);
* the two dispatch-path bugfix regressions: ``_scales_to_rows`` rejects
  non-divisible scale/row combinations instead of silently truncating,
  and ``make_bucket_plan`` resolves member dtypes strictly (dtype-less
  leaves fail loudly; mixed dtypes never fuse).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import bucketing as BK
from repro.core import compressor as C
from repro.core import leafwise
from repro.kernels import dispatch as K

N = 4
TP = {"model": 2}


def _tp_plan(n=N, hierarchy=None):
    """A mixed tree of TP-local shards (canonical (None, 'model') vspec)
    and unsharded leaves, as the fully-manual regime plans it: leaf shapes
    are shard-LOCAL, ``model_axis_sizes`` sets the rest factor."""
    shapes = {
        "wq": jax.ShapeDtypeStruct((16, 64), jnp.float32),
        "wk": jax.ShapeDtypeStruct((16, 64), jnp.float32),
        "wv": jax.ShapeDtypeStruct((16, 64), jnp.float32),
        "bias": jax.ShapeDtypeStruct((24,), jnp.float32),
        "emb": jax.ShapeDtypeStruct((8, 16), jnp.float32),
    }
    specs = {"wq": P(None, "model"), "wk": P(None, "model"),
             "wv": P(None, "model"), "bias": P(), "emb": None}
    return leafwise.make_plan(shapes, specs, None, n,
                              model_axis_sizes=TP, hierarchy=hierarchy)


# ---------------------------------------------------------------------------
# bucket formation
# ---------------------------------------------------------------------------

def test_tp_shards_fuse_into_sharded_bucket():
    plan = _tp_plan()
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    sharded = [b for b in bp.buckets
               if b.fused and b.layout.rest_factor > 1]
    assert sharded, "TP-local shards must fuse, not bail to singletons"
    multi = [b for b in sharded if len(b.members) > 1]
    assert multi, "same-vspec TP shards must share one fused bucket"
    b = multi[0]
    # the bucket keeps the members' rest factor and the canonical TP spec
    assert b.layout.rest_factor == TP["model"]
    assert tuple(b.spec) == ("model",)
    assert tuple(b.vspec) == (None, "model")
    assert b.layout.flatten
    # all three same-vspec TP leaves landed in it (dict order: wk, wq, wv)
    names = sorted(plan.treedef.unflatten(range(5)).items())
    tp_idx = {i for (k, i) in names if k in ("wq", "wk", "wv")}
    assert set(b.members) == tp_idx


def test_sharded_and_unsharded_never_mix():
    plan = _tp_plan()
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    for b in bp.buckets:
        rfs = {plan.layouts[i].rest_factor for i in b.members}
        assert len(rfs) == 1, "one rest_factor per bucket"
        if b.fused and len(b.members) > 1:
            vss = {tuple(plan.vspecs[i]) for i in b.members}
            assert len(vss) == 1, "one vspec per fused bucket"


def test_fusable_vspec_rules():
    lo_tp = C.make_layout((16, 64), P(None, "model"), N, rest_factor=2,
                          force_flatten=True)
    assert BK.fusable(lo_tp, (None, "model"))
    # non-canonical sharded vspecs stay singletons
    assert not BK.fusable(lo_tp, ("model", None))
    assert not BK.fusable(lo_tp, (None, None, "model"))
    assert not BK.fusable(lo_tp, None)
    # structured (non-flatten) views never fuse
    lo_st = C.make_layout((16, 40), P(None, "model"), N)
    assert not lo_st.flatten
    assert not BK.fusable(lo_st, (None, None, "model"))
    # unsharded flatten leaves need a trivial vspec
    lo_flat = C.make_layout((37,), None, N)
    assert BK.fusable(lo_flat, (None, None))
    assert not BK.fusable(lo_flat, (None, "model"))


# ---------------------------------------------------------------------------
# transport properties over TP shards
# ---------------------------------------------------------------------------

def _bucket_views(plan, bucket, seed=0):
    key = jax.random.PRNGKey(seed)
    views = []
    for j, i in enumerate(bucket.members):
        lo = plan.layouts[i]
        v = jax.random.normal(jax.random.fold_in(key, j), lo.view_shape)
        m = C.pad_mask(lo)
        views.append(v * m if m is not None else v)
    return views


def test_scatter_gather_identity_over_tp_shards():
    plan = _tp_plan()
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    for b in bp.buckets:
        if not b.fused:
            continue
        views = _bucket_views(plan, b, seed=len(b.members))
        buf = BK.gather_views(b, views)
        assert buf.shape == b.layout.view_shape
        back = BK.scatter_views(b, buf,
                                [plan.layouts[i] for i in b.members])
        for v, r in zip(views, back):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(v))


def test_true_element_conservation_across_shards():
    plan = _tp_plan()
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    acc = BK.bucket_accounting(bp)
    leaf_true = sum(int(np.prod(plan.layouts[i].shape))
                    for i, dp in enumerate(plan.dp_mask) if dp)
    assert acc["true_elems"] == leaf_true
    # per-shard local counts x rest_factor = global element conservation
    glob = sum(b.true_elems * b.layout.rest_factor for b in bp.buckets)
    leaf_glob = sum(int(np.prod(plan.layouts[i].shape))
                    * plan.layouts[i].rest_factor
                    for i, dp in enumerate(plan.dp_mask) if dp)
    assert glob == leaf_glob
    # every real element of a sharded bucket lands in exactly one slot
    for b in bp.buckets:
        if not b.fused or len(b.members) < 2:
            continue
        views = [C.to_view(jnp.arange(off, off + s, dtype=jnp.float32)
                           .reshape(plan.layouts[i].shape),
                           plan.layouts[i])
                 for i, off, s in zip(b.members, b.offsets, b.sizes)]
        flat = np.asarray(BK.gather_views(b, views)).reshape(-1)
        np.testing.assert_array_equal(flat[:b.true_elems],
                                      np.arange(b.true_elems))
        assert (flat[b.true_elems:] == 0).all()


def test_sharded_bucket_hierarchical_layout():
    from repro.core.comm import Hierarchy
    plan = _tp_plan(hierarchy=Hierarchy(inner=2))
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    sharded = [b for b in bp.buckets if b.fused and b.layout.rest_factor > 1]
    assert sharded and all(b.layout.n_inner == 2 for b in sharded), \
        "sharded fused buckets must inherit the plan's hierarchy"


# ---------------------------------------------------------------------------
# bugfix regressions
# ---------------------------------------------------------------------------

def test_scales_to_rows_rejects_non_divisible():
    # 3 scale rows cannot spread over an 8-row kernel frame: loud error,
    # never the old silent element-wise truncation
    scales = jnp.ones((3, 1), jnp.float32)
    with pytest.raises(ValueError, match="scale rows"):
        K._scales_to_rows(scales, (3,), 8)
    # zero scale rows likewise (the modulus would divide by zero)
    with pytest.raises(ValueError, match="scale rows"):
        K._scales_to_rows(jnp.ones((0, 1)), (0,), 8)
    # a layout passed through is named in the message for diagnosis
    lo = C.make_layout((37,), None, 4)
    with pytest.raises(ValueError, match="layout"):
        K._scales_to_rows(scales, (3,), 8, lo)
    # divisible combinations spread by exact repetition
    out = K._scales_to_rows(jnp.ones((2, 1), jnp.float32), (2,), 8)
    assert out.shape[0] == 8
    np.testing.assert_array_equal(np.asarray(out), np.ones(8))


def test_bucket_plan_dtype_strict():
    shapes = [jax.ShapeDtypeStruct((64,), jnp.float32),
              jax.ShapeDtypeStruct((64,), jnp.bfloat16),
              jax.ShapeDtypeStruct((64,), jnp.float32)]
    plan = leafwise.make_plan(shapes, None, None, N)
    bp = BK.make_bucket_plan(plan, bucket_mb=4.0)
    for b in bp.buckets:
        dts = {np.dtype(plan.leaves[i].dtype) for i in b.members}
        assert len(dts) == 1, "mixed-dtype leaves must never fuse"
    # f32 leaves 0 and 2 are separated by the bf16 leaf -> 3 buckets
    # (greedy in-order packing; the dtype break closes the open bucket)
    assert len(bp.buckets) == 3

    class NoDtype:
        shape = (64,)

    plan2 = leafwise.make_plan([NoDtype(), NoDtype()], None, None, N)
    with pytest.raises(ValueError, match="dtype"):
        BK.make_bucket_plan(plan2, bucket_mb=4.0)


def test_kernel_safe_vspec_mesh_consistency():
    lo_g = C.make_layout((37,), None, N)                     # rest_factor 1
    lo_l = C.make_layout((16, 64), P(None, "model"), N,      # TP-local
                         rest_factor=2, force_flatten=True)
    # trivial vspecs are always safe
    assert K.kernel_safe(None)
    assert K.kernel_safe((None, None), lo_g)
    # manual-TP axes are safe (the kernel path psums over them itself)
    assert K.kernel_safe((None, "model"), lo_l, ("model",))
    # meshless trace + sharded vspec: only shard-GLOBAL layouts are safe;
    # a shard-local layout (rest_factor > 1) would silently skip its
    # model psums on the jnp path too, so it must not claim kernel-safety
    assert K.kernel_safe((None, "model"), lo_g, ())
    assert not K.kernel_safe((None, "model"), lo_l, ())
    # without a layout a meshless trace keeps the global-view convention
    assert K.kernel_safe((None, None, "model"), None, ())


# ---------------------------------------------------------------------------
# shard_map dispatch parity on a forced 8-device mesh (subprocess, same
# pattern as test_cross_regime_parity: the forced host device count must
# not leak into other tests)
# ---------------------------------------------------------------------------

MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core import compressor as C
    from repro.launch.mesh import make_mesh
    from repro.core import onebit_allreduce as AR
    from repro.kernels import dispatch as K

    mesh = make_mesh((2, 4), ("data", "model"))
    spec = P(None, "model")
    lo = C.make_layout((16, 256), spec, 4)
    vspec = C.view_spec_entries(lo, spec)
    sh = NamedSharding(mesh, P(*vspec))
    key = jax.random.PRNGKey(0)
    z = jax.random.normal(key, lo.view_shape)
    err = jax.random.normal(jax.random.fold_in(key, 1), lo.view_shape) * .3

    # the partitioning rule engages: under the ambient auto mesh this
    # layout/vspec has a per-shard plan and kernel_safe keeps the kernels
    with jax.set_mesh(mesh):
        engaged = jax.jit(lambda a: jnp.float32(
            K.kernel_safe(vspec, lo, ())))(z)
    assert float(engaged) == 1.0, "kernel_safe must keep the fused path"
    assert K.shard_context(lo, vspec) is None  # meshless: no ambient mesh

    for mode in ("tensor", "chunk", "row"):
        p_ref, s_ref, e_ref = C.ef_compress(z + err, lo, mode, None)
        with jax.set_mesh(mesh):
            fn = jax.jit(lambda a, b: K.ef_compress_view(
                a, b, lo, mode, vspec=vspec))
            p_k, s_k, e_k = fn(jax.device_put(z, sh),
                               jax.device_put(err, sh))
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_ref),
                                   rtol=1e-5, atol=1e-6)
        v_ref = C.decompress(p_ref, s_ref, lo.pack_count)
        with jax.set_mesh(mesh):
            v_k = jax.jit(lambda p, s: K.decompress_view(
                p, s, lo, vspec=vspec))(p_k, s_k)
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref),
                                   rtol=1e-6, atol=1e-7)

        widx = 2
        avg = jax.random.normal(jax.random.PRNGKey(7), lo.chunk_shape)
        es = jax.random.normal(jax.random.PRNGKey(8), lo.chunk_shape) * .2
        p_ref, s_ref, e_ref = AR._server_compress((avg + es)[None], lo,
                                                  mode, None)
        with jax.set_mesh(mesh):
            fn = jax.jit(lambda a, b, w: K.server_compress_view(
                a, b, lo, mode, w, vspec=vspec))
            p_k, s_k, e_k = fn(jax.device_put(avg[None], sh),
                               jax.device_put(es[None], sh), widx)
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_ref),
                                   rtol=1e-5, atol=1e-6)
        print("MODE_OK", mode)

    # fused local step (adam kind), elementwise per shard
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    g, m, u = (jax.random.normal(k, lo.view_shape) for k in ks[:3])
    v = jnp.abs(jax.random.normal(ks[3], lo.view_shape)) + 1e-3
    lr, b1, eps = jnp.float32(3e-3), 0.9, 1e-8
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda g_, m_, u_, v_, lr_: K.fused_local_step_view(
            g_, m_, u_, v_, lr_, b1, eps, lo, vspec=vspec))
        mh_k, u_k, d_k = fn(jax.device_put(g, sh), jax.device_put(m, sh),
                            jax.device_put(u, sh), jax.device_put(v, sh),
                            lr)
    mh = b1 * m + (1 - b1) * g
    np.testing.assert_allclose(np.asarray(mh_k), np.asarray(mh),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u + lr * mh),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_k),
                               np.asarray(lr * mh / jnp.sqrt(v + eps)),
                               rtol=1e-6, atol=1e-6)
    print("FUSED_STEP_OK")

    # trainer-realistic nesting: the wrapper under vmap(axis_name='w')
    W = 4
    zw = jax.random.normal(key, (W,) + lo.view_shape)
    ew = jax.random.normal(jax.random.fold_in(key, 9),
                           (W,) + lo.view_shape) * .3
    p_ref, s_ref, e_ref = jax.vmap(
        lambda a, b: C.ef_compress(a + b, lo, "tensor", None),
        axis_name="w")(zw, ew)
    shw = NamedSharding(mesh, P(None, None, None, "model"))
    with jax.set_mesh(mesh):
        fn = jax.jit(jax.vmap(lambda a, b: K.ef_compress_view(
            a, b, lo, "tensor", vspec=vspec), axis_name="w"))
        p_k, s_k, e_k = fn(jax.device_put(zw, shw),
                           jax.device_put(ew, shw))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_ref),
                               rtol=1e-5, atol=1e-6)
    print("VMAP_OK")

    # non-divisible local columns (40 / 4 devices = 10, not % 8): no shard
    # plan, kernel_safe routes to the constrained jnp path instead
    lo2 = C.make_layout((16, 40), spec, 4)
    vs2 = C.view_spec_entries(lo2, spec)
    with jax.set_mesh(mesh):
        safe = jax.jit(lambda a: jnp.float32(
            K.kernel_safe(vs2, lo2, ())))(z)
    assert float(safe) == 0.0, "indivisible shard must fall back"
    print("FALLBACK_OK")
""")


@pytest.mark.slow
def test_sharded_dispatch_bitwise_on_mesh():
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, timeout=1200,
                       env={**os.environ, "PYTHONPATH": "src"})
    out = r.stdout
    assert r.returncode == 0, out[-2000:] + r.stderr[-3000:]
    assert out.count("MODE_OK") == 3, out
    for tag in ("FUSED_STEP_OK", "VMAP_OK", "FALLBACK_OK"):
        assert tag in out, out
