"""Model FLOP utilization (%) of the whole step over the traced window:
the counts module's ``model_flops_per_token`` x tokens per second over
chips x the chip's bf16 peak. Recomputed operations do not count."""
from benchmarks.chip.metrics import peak


def read(ctx):
    f = ctx["counts"].model_flops_per_token(ctx["model"],
                                            ctx["mix"]["seq_len"])
    rate = ctx["tokens"] / ctx["window_s"]
    return 100.0 * f * rate / (ctx["chips"] * peak(ctx)["bf16_flops"])
