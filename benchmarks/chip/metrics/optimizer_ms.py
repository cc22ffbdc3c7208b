"""Device milliseconds per step of the step program's other non-collective
ops: the 0/1 Adam local step, the 1-bit encode and decode, the variance
and anchor updates. Mean over chips."""
from benchmarks.chip.metrics import split_ok


def read(ctx):
    if not split_ok(ctx):
        return None
    return 1e3 * ctx["trace"]["per_step_s"]["optimizer"]
