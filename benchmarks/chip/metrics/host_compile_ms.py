"""Host milliseconds per traced step spent tracing, lowering and
compiling or loading programs: the union of the program's
``telemetry.CompileCounters`` spans that start in the traced window."""
from benchmarks.chip.scopes import compile_window


def read(ctx):
    w = compile_window(ctx)
    return None if w is None else 1e3 * w["busy_s"] / w["steps"]
