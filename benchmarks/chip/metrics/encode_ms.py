"""Device milliseconds per step under the program's ``opt.encode`` scope:
the worker's and the server's 1-bit encode of the momentum exchange, with
what XLA fuses into them (a fusion counts under its root's scope), from
``scopes.optimizer_scopes``. Mean over chips; a window with no sync step
reads 0. None where the step program carries no scopes."""
from benchmarks.chip import scopes as SC

SCOPE = "opt.encode"


def read(ctx):
    sc = SC.optimizer_scopes(ctx["trace_path"], len(ctx["kinds"]),
                             ctx["chips"], ctx["hlo"])
    return None if sc is None else 1e3 * sc.get(SCOPE, 0.0)
