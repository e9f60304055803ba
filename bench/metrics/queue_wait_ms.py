"""queue_wait_ms: 99th percentile of the admit -> bucket gap of the
front end's span chains (time a request waited in its tenant queue and the
ring before its bucket was formed)."""
from bench import stats


def read(ctx):
    gaps = stats.stage_gaps_s(ctx.records, "admit", "bucket")
    return 1e3 * stats.percentile(gaps, 99) if len(gaps) else None
