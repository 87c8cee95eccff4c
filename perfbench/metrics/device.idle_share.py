"""Share (%) of the traced window in which no op ran on the device: 1 less
the union of the device-op intervals over the window."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
