"""serve_mfu: the share of one chip's peak that the served rate uses:
qps times the least seconds a request needs on the chip (its share of a
batch's scan plus its dense stages), in percent."""
from bench import stats


def read(ctx):
    qps = ctx.values.get("qps")
    queries, rows = stats.scan_shape(ctx)
    if qps is None or queries is None:
        return None
    scan, _ = ctx.count("streaming_nns").least_seconds(
        queries, rows, ctx.work["bits"], ctx.peaks)
    dense = ctx.count("serve_mlps").least_seconds(ctx.work, ctx.peaks)
    return 100.0 * qps * (scan / queries + dense)
