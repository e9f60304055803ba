"""pad_frac: padding rows over all rows of the batches formed in the
window (`serving.padded` / (`serving.served` + `serving.padded`))."""
from bench import stats


def read(ctx):
    served = stats.counter_delta(ctx.snap0, ctx.snap1, "serving.served")
    padded = stats.counter_delta(ctx.snap0, ctx.snap1, "serving.padded")
    return padded / (served + padded) if served + padded else None
