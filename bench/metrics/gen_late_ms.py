"""gen_late_ms: 99th percentile of submit minus due, how late the load
generator handed requests to the front end."""
from bench import stats


def read(ctx):
    return 1e3 * stats.percentile(stats.submit_lateness_s(ctx.records), 99)
