"""streaming_nns_roofline: the streaming scan kernel's least time per
batch (`counts/streaming_nns.py` at the chip's peaks) over its device
time per batch in the traced window, in percent."""
from bench import stats


def read(ctx):
    if ctx.trace is None:
        return None
    kernel = ctx.trace["kernels"]["streaming_nns"]
    batches = ctx.trace["programs"]["scan"]["n"]
    queries, rows = stats.scan_shape(ctx)
    if not kernel["n"] or not batches or queries is None:
        return None
    least, _ = ctx.count("streaming_nns").least_seconds(
        queries, rows, ctx.work["bits"], ctx.peaks)
    return 100.0 * least / (kernel["s"] / batches)
