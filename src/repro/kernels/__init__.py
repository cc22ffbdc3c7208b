"""Pallas TPU kernels for the paper's compute hot-spots (optimizer side):
fused error-feedback 1-bit compress/decompress + fused 0/1 Adam local step.

``ops`` exposes the jitted 2-D kernel wrappers, ``ref`` their pure-jnp
oracles, and ``dispatch`` the comm-view-level glue that
``OptimizerConfig.use_pallas=True`` routes through. Validated in
interpret mode against ref.py on CPU; on TPU the same calls compile to
fused Mosaic kernels (``ops`` picks the mode from the backend).
"""
from repro.kernels import dispatch, ops, ref

__all__ = ["dispatch", "ops", "ref"]
