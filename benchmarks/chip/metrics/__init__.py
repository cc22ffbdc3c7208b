"""Per-layer metric readers: ``<metric>.read(ctx)`` returns the metric's
value, or None where the run holds nothing to read it from.

``ctx`` (built by ``run.py`` in a ``--trace 1`` run): ``trace`` (the
summary of ``trace.summarize``), ``trace_path`` (the profiler's
``.xplane.pb``, there until every reader has run), ``hlo`` (the optimized
step program's text), ``anchors`` (the bounds of two
``telemetry.clock_anchor()``s, one after the trace starts and one before
it stops), ``spans`` ((batch start,
step start, read end) host times of each traced step), ``kinds`` (step
kind of each traced step, ``step_kind``), ``window_s`` and ``tokens`` of
the traced window (host clock), ``chips``, ``model`` and ``mix`` (the
configuration's model block and the mix), ``counts`` (the
configuration's counts module), ``peaks`` (``peaks.json``),
``device_kind`` and ``memory_peak_bytes``.
"""


def step_kind(synced: bool, var_round: bool) -> str:
    """The kind of one step, as the counts modules' ``optimizer_bytes``
    take it: ``local`` or ``sync``, with ``+var`` on a variance round."""
    return ("sync" if synced else "local") + ("+var" if var_round else "")


def peak(ctx: dict) -> dict:
    """The chip's row of ``peaks.json``; an unknown chip is an error."""
    try:
        return ctx["peaks"]["devices"][ctx["device_kind"]]
    except KeyError:
        raise KeyError(f"no peaks for device kind {ctx['device_kind']!r} in "
                       f"peaks.json") from None


def split_ok(ctx: dict) -> bool:
    """Whether the trace told forward/backward ops from the optimizer's:
    some step-program ops carry a ``jvp(``/``transpose(`` name stack and
    some do not."""
    s = ctx["trace"]["per_step_s"]
    return s["fwd_bwd"] > 0 and s["optimizer"] > 0
