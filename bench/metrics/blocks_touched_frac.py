"""blocks_touched_frac: summary blocks the pruned scan admitted per query,
over the catalog's summary blocks, in the window
(`nns.blocks_touched` / (`nns.block_scan_queries` x `nns.summary_blocks`))."""
from bench import stats


def read(ctx):
    touched = stats.counter_delta(ctx.snap0, ctx.snap1, "nns.blocks_touched")
    queries = stats.counter_delta(ctx.snap0, ctx.snap1,
                                  "nns.block_scan_queries")
    blocks = ctx.snap1.get("nns.summary_blocks", 0)
    return touched / (queries * blocks) if queries and blocks else None
