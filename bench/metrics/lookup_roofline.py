"""lookup_roofline: the DLRM lookup stage's least time per batch (its
rows, scales and ids at HBM peak, `counts/dlrm.py`) over the device time
per batch of its program in the traced window, in percent. It reads the
same work whichever implementation of the lookup runs."""


def read(ctx):
    if ctx.trace is None:
        return None
    prog = ctx.trace["programs"]["lookup"]
    count = ctx.count("dlrm")
    samples = count.batch_samples(ctx)
    if not prog["n"] or not prog["s"] or samples is None:
        return None
    least = count.lookup_seconds(ctx.work["config"], samples, ctx.peaks)
    return 100.0 * least / (prog["s"] / prog["n"])
