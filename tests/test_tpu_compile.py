"""The optimizer's Pallas kernels compile for a TPU v5e at BERT-base widths.

Interpret-mode tests (test_kernels, test_pallas_parity) prove the kernels'
numerics on CPU but not that the TPU compiler accepts their block shapes,
layouts and VMEM use. These tests compile each kernel with
``interpret=False`` for a described (not attached) v5e, on the frames the
0/1 Adam exchange really tiles: the word embedding (30720x768, vocab
30522 padded by the template) and one FFN leaf (768x3072), each as the
comm view of 2 workers, with 8-row blocks.

The topology is described inside a fixture (never at import), so only the
test worker given this file loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P, SingleDeviceSharding

from repro.core import compressor as C
from repro.kernels import dispatch, fused_adam, onebit

# (leaf shape, tensor-parallel spec) as in configs/bert_base.py's template
FRAMES = {
    "embed_30720x768": ((30720, 768), P("model", None)),
    "ffn_768x3072": ((768, 3072), P(None, "model")),
}
BLOCK_ROWS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's executables can be written to the persistent
    # cache but never read back without one; keep these compiles out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _frame(name):
    shape, spec = FRAMES[name]
    rows, cols = C.view_rows_cols(C.make_layout(shape, spec, 2))
    assert rows % BLOCK_ROWS == 0, (name, rows)
    return rows, cols


def _kernels(rows, cols, sds):
    f32 = lambda *s: sds(s, jnp.float32)
    z, cnt, scale = f32(rows, cols), sds((rows,), jnp.int32), f32(rows)
    block = (BLOCK_ROWS, dispatch._tile(cols, 1024, 128))
    k = dict(block_rows=BLOCK_ROWS, interpret=False)
    return {
        "ef_compress": (functools.partial(onebit.ef_compress, **k),
                        (z, z, cnt)),
        "abs_rowsum": (functools.partial(onebit.abs_rowsum, **k),
                       (z, z, cnt)),
        "ef_quantize": (functools.partial(onebit.ef_quantize, **k),
                        (z, z, scale, cnt)),
        "decompress": (functools.partial(onebit.decompress, **k),
                       (sds((rows, cols // 8), jnp.uint8), scale)),
        "fused_local_step": (
            lambda g, m, u, v, lr: fused_adam.fused_local_step(
                g, m, u, v, lr, 0.9, block=block, interpret=False),
            (z, z, z, z, f32())),
        "fused_local_step_sgd": (
            lambda g, m, u, lr: fused_adam.fused_local_step_sgd(
                g, m, u, lr, 0.9, block=block, interpret=False),
            (z, z, z, f32())),
    }


KERNELS = ("ef_compress", "abs_rowsum", "ef_quantize", "decompress",
           "fused_local_step", "fused_local_step_sgd")


# a caller's default_matmul_precision("highest") must not reach the bit
# (un)packing matmuls: Mosaic refuses fp32 contraction of bf16 operands
@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("frame", sorted(FRAMES))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, frame, precision):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args = _kernels(*_frame(frame), sds)[kernel]
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (kernel, frame)

