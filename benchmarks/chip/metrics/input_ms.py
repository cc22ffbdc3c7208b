"""Host milliseconds per step spent in ``SyntheticLM.batch`` (the
benchmark's span around the call), mean over the traced steps."""


def read(ctx):
    spans = ctx["spans"]
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in spans) / len(spans)
