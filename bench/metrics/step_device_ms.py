"""step_device_ms: device milliseconds per batch of the lookup, scan and
rank programs together, in the traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    progs = ctx.trace["programs"]
    n = progs["scan"]["n"]
    if not n:
        return None
    return 1e3 * sum(p["s"] for p in progs.values()) / n
