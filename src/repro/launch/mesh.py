"""Mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before any jax initialization.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """GSPMD-auto mesh: the worker axes go manual inside the step's
    ``shard_map`` and 'model' stays auto (``jax.make_mesh`` would default
    every axis to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; (2,16,16) = 512 chips across 2 pods. Used by
    the compile-only dry-run (``launch/dryrun.py``)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Data-parallel mesh over the devices present: one paper-worker per
    device (``data = jax.device_count()``, ``model = 1``). The training
    CLI's ``--mode mesh``."""
    return make_mesh((jax.device_count(), 1), ("data", "model"))


def worker_axes(mesh) -> tuple:
    """The manual (paper-worker) axes of a mesh: everything except model."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI subprocess tests (needs device_count >= product)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
