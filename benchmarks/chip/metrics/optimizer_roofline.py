"""Share (%) of the optimizer's device time that the HBM bytes its step
kinds must move would take at the chip's peak bandwidth
(``flops.optimizer_bytes``, over the traced steps' kinds)."""
from benchmarks.chip import flops as F
from benchmarks.chip.metrics import peak, split_ok


def read(ctx):
    if not split_ok(ctx):
        return None
    kinds = ctx["kinds"]
    need = sum(F.optimizer_bytes(ctx["n_params"], ctx["chips"], k)
               for k in kinds) / len(kinds)
    t_min = need / peak(ctx)["hbm_bytes_per_s"]
    return 100.0 * t_min / ctx["trace"]["per_step_s"]["optimizer"]
