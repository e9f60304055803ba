"""merge_frac: share of the streaming kernel's scanned (query-tile,
db-block) cells whose candidate merge ran, in the window
(`nns.merge_cells` / `nns.scan_cells`); nothing where the program keeps
no such counters."""
from bench import stats


def read(ctx):
    scanned = stats.counter_delta(ctx.snap0, ctx.snap1, "nns.scan_cells")
    merged = stats.counter_delta(ctx.snap0, ctx.snap1, "nns.merge_cells")
    return merged / scanned if scanned else None
