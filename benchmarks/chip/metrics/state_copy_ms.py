"""Device milliseconds per step of the step program's copies outside the
forward and backward: the ``copy``, ``copy-start`` and ``copy-done`` ops
of the optimizer class (``trace.classify``), which XLA makes to move the
optimizer state around the per-unit ``lax.cond``s. No JAX op makes them,
so no scope names them. Mean over chips; a step program without copies
reads 0."""
from benchmarks.chip import scopes as SC
from benchmarks.chip.metrics import split_ok

COPIES = ("copy", "copy-start", "copy-done")


def read(ctx):
    if not split_ok(ctx):
        return None
    ops = SC.optimizer_opcodes(ctx["trace_path"], len(ctx["kinds"]),
                               ctx["chips"], ctx["hlo"])
    return 1e3 * sum(ops.get(k, 0.0) for k in COPIES)
