"""idle_frac: share of the traced window in which no op ran on the
device."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]
