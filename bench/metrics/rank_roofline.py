"""rank_roofline: the DLRM rank stage's least time per batch (the bottom
MLP, cross network and top MLP of its samples at the bf16 peak,
`counts/dlrm.py`) over the device time per batch of its program in the
traced window, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    prog = ctx.trace["programs"]["rank"]
    count = ctx.count("dlrm")
    samples = count.batch_samples(ctx)
    if not prog["n"] or not prog["s"] or samples is None:
        return None
    least = count.rank_seconds(ctx.work["config"], samples, ctx.peaks)
    return 100.0 * least / (prog["s"] / prog["n"])
