"""scan_ms: device milliseconds per batch of the scan program, in the
traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    scan = ctx.trace["programs"]["scan"]
    return 1e3 * scan["s"] / scan["n"] if scan["n"] else None
