"""qps: requests served per second between the first and the last
completion inside the window (stalls between completions count)."""
from bench import stats


def read(ctx):
    return stats.completion_rate(ctx.records, ctx.window)
