"""Named-axis communication abstraction for the worker (data-parallel) axes.

The optimizer algorithms in this package are written *per worker*: they see the
local shard of every tensor and perform cross-worker exchange exclusively
through a :class:`Comm`. A ``Comm`` is a thin wrapper over ``jax.lax``
collectives bound to one or more mesh axis names, which means the identical
algorithm code runs in two regimes:

* **production** — inside a partial-manual ``jax.shard_map`` whose manual axes
  are the worker axes (``("pod", "data")`` on the production mesh);
* **simulation** — under ``jax.vmap(..., axis_name=...)`` on a single device,
  with the worker axis materialized as a leading array axis. This is how the
  unit tests exercise n=8 workers on CPU.

Only collectives used by the paper's algorithms are exposed.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """Two-level worker topology: slow inter-pod axes × fast intra-pod axes.

    The hierarchical 1-bit AllReduce reduces *uncompressed* inside the fast
    (``inner``) domain and runs Algorithm 2's EF-compressed exchange only
    across the slow (``outer``) domain. ``inner`` is the static intra-pod
    worker count (needed at optimizer-init time, before any axis context
    exists, to size per-level EF state); the axis names match the mesh axes
    in production and the nested-vmap axis names in simulation, so one
    config value drives both regimes.
    """

    inner: int                                  # workers per pod
    outer_axes: Tuple[str, ...] = ("pod",)      # inter-pod (slow) axes
    inner_axes: Tuple[str, ...] = ("data",)     # intra-pod (fast) axes

    @property
    def axes(self) -> Tuple[str, ...]:
        return tuple(self.outer_axes) + tuple(self.inner_axes)


@dataclasses.dataclass(frozen=True)
class Comm:
    """Collectives over the worker axes.

    Attributes:
      axes: mesh/vmap axis name(s) forming the logical worker axis. When more
        than one name is given they are treated as a single flattened axis
        (``pod`` major), matching how ``jax.lax`` collectives accept tuples.
    """

    axes: Tuple[str, ...]

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def size(self) -> int:
        return jax.lax.axis_size(self.axes)

    def index(self):
        return jax.lax.axis_index(self.axes)

    def psum(self, x):
        return jax.lax.psum(x, self.axis_name)

    def pmean(self, x):
        return jax.lax.pmean(x, self.axis_name)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis_name)

    def all_gather(self, x, axis: int = 0, tiled: bool = True):
        if len(self.axes) <= 1:
            return jax.lax.all_gather(x, self.axis_name, axis=axis,
                                      tiled=tiled)
        # Flattened axis tuples: decompose into per-axis gathers, innermost
        # first — concatenation is then outer-major, exactly the flattened-
        # axis order of the native tuple call. (vmap's all_gather batching
        # rule rejects tuples — the simulation regime — and the
        # decomposition is collective-equivalent on a mesh: same payload,
        # one ring per topology level.)
        if not tiled:
            x = jnp.expand_dims(x, axis)
        for a in reversed(self.axes):
            x = jax.lax.all_gather(x, a, axis=axis, tiled=True)
        return x

    def all_to_all(self, x, split_axis: int = 0, concat_axis: int = 0):
        return jax.lax.all_to_all(
            x, self.axis_name, split_axis=split_axis, concat_axis=concat_axis,
            tiled=True)

    def split(self, outer_axes: Sequence[str], inner_axes: Sequence[str]):
        """(outer_comm, inner_comm) over grouped sub-axes of this comm.

        ``outer_axes + inner_axes`` must equal ``self.axes`` in order (the
        flattened worker index is outer-major, so contiguous groups of the
        flat index land in the inner domain). Works identically under
        shard_map (mesh sub-axes) and nested vmap (simulation). An empty
        group degenerates to a :class:`NullComm`.
        """
        outer, inner = tuple(outer_axes), tuple(inner_axes)
        if outer + inner != self.axes:
            raise ValueError(
                f"cannot split axes {self.axes} into {outer} + {inner}")
        return (Comm(outer) if outer else NullComm(),
                Comm(inner) if inner else NullComm())


class NullComm(Comm):
    """Single-worker comm: every collective is the identity (n=1).

    Lets the same optimizer/MoE code run un-mapped on one device (CPU smoke
    tests, single-host debugging).
    """

    def __init__(self):
        object.__setattr__(self, "axes", ())

    def size(self) -> int:
        return 1

    def index(self):
        return jnp.zeros((), jnp.int32)

    def psum(self, x):
        return x

    def pmean(self, x):
        return x

    def pmax(self, x):
        return x

    def all_gather(self, x, axis: int = 0, tiled: bool = True):
        return x if tiled else jnp.expand_dims(x, axis)

    def all_to_all(self, x, split_axis: int = 0, concat_axis: int = 0):
        return x


def norm_hierarchy(h: "Hierarchy | None", n_workers: int):
    """Validate a Hierarchy against the worker count; None when it cannot
    apply (single worker) so callers fall back to the flat path."""
    if h is None or n_workers <= 1:
        return None
    if n_workers % h.inner:
        raise ValueError(
            f"hierarchy.inner={h.inner} must divide n_workers={n_workers}")
    return h


def sim_comm(axis_name: str = "workers") -> Comm:
    """Comm for vmap-simulated workers (tests / CPU benchmarks)."""
    return Comm(axes=(axis_name,))


def mesh_comm(axes: Sequence[str]) -> Comm:
    """Comm over real mesh axes (inside shard_map)."""
    return Comm(axes=tuple(axes))


def run_simulated(fn, n_workers: int, axis_name: str = "workers"):
    """Wrap ``fn(comm, *per_worker_args)`` to run with vmap-simulated workers.

    Every argument must carry a leading ``n_workers`` axis. Returns outputs
    with the same leading axis.
    """
    comm = sim_comm(axis_name)

    def wrapped(*args):
        return jax.vmap(lambda *a: fn(comm, *a), axis_name=axis_name)(*args)

    return wrapped
