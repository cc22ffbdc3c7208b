"""Share (%) of the traced window in which no op runs on the device (one
minus the union of the device's op intervals over the window), mean over
chips."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
