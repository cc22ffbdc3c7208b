"""Compile-or-load requests of the host per traced step: the program's
``telemetry.CompileCounters`` events (a load from the persistent cache
counts) that start between the first traced step's batch and the last
one's read."""
from benchmarks.chip.scopes import compile_window


def read(ctx):
    w = compile_window(ctx)
    return None if w is None else w["compiles"] / w["steps"]
