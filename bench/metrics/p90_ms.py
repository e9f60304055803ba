"""p90_ms: 90th-percentile latency, due time to resolve, over every
request due in the window; a failed or unresolved request is a miss."""
from bench import stats


def read(ctx):
    return 1e3 * stats.percentile(stats.latencies_s(ctx.records), 90)
