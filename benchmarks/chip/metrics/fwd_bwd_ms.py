"""Device milliseconds per step of the step program's forward and backward
ops (JAX name stack under ``jvp(`` or ``transpose(``), mean over chips."""
from benchmarks.chip.metrics import split_ok


def read(ctx):
    if not split_ok(ctx):
        return None
    return 1e3 * ctx["trace"]["per_step_s"]["fwd_bwd"]
