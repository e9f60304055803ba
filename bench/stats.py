"""Metric arithmetic over request records, on the host clock.

Requests are `loadgen.Records`: when each was due, when it was
submitted, when its ticket resolved (NaN if it never did) and its status.
Latency runs from the due time, so a generator that falls behind cannot
make the server look fast, and a request that failed or never resolved is
a miss: slower than any limit.
"""
from __future__ import annotations

import math

import numpy as np

from bench.loadgen import OK

MISS = math.inf


def latencies_s(records) -> np.ndarray:
    """Resolve minus due of every request; `MISS` where it did not come
    back served."""
    ok = (records.status == OK) & np.isfinite(records.done)
    return np.where(ok, records.done - records.due, MISS)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least q % of
    the values at or below it); a miss sorts above every number."""
    values = np.sort(np.asarray(values, np.float64))
    if not len(values):
        return math.nan
    return float(values[max(math.ceil(q / 100 * len(values)) - 1, 0)])


def completion_rate(records, window) -> float | None:
    """Requests served per second between the first and the last
    completion inside `window` (t0, t1): completions after the first,
    over the time between them. Completions that arrive in chunks then
    give the chunk rate, wherever the window's edges fall. None when fewer
    than two distinct completion times fall in the window."""
    t0, t1 = window
    done = records.done[records.status == OK]
    done = np.sort(done[(done >= t0) & (done <= t1)])
    if len(done) < 2 or done[-1] <= done[0]:
        return None
    return float(np.sum(done > done[0]) / (done[-1] - done[0]))


def submit_lateness_s(records) -> np.ndarray:
    """Submit minus due: how late the generator handed each request in."""
    return records.submit - records.due


def stage_gaps_s(records, first: str, second: str) -> np.ndarray:
    """Per request, the time from span-chain stage `first` to `second`
    (requests whose chain lacks either are skipped)."""
    a, b = records.stage(first), records.stage(second)
    if a is None or b is None:
        return np.zeros(0)
    both = np.isfinite(a) & np.isfinite(b)
    return (b - a)[both]


def counter_delta(start: dict, end: dict, name: str) -> float:
    """Increase of registry counter or gauge `name` over the window."""
    return float(end.get(name, 0) or 0) - float(start.get(name, 0) or 0)


def scan_shape(ctx):
    """(queries per batch, rows scanned per query) of the window's scans:
    the mean bucket, and the rows of the summary blocks the pruned scan
    admitted (every row when nothing was pruned)."""
    rows = ctx.work["rows"]
    batches = counter_delta(ctx.snap0, ctx.snap1, "serving.batches")
    if not batches:
        return None, rows
    queries = (counter_delta(ctx.snap0, ctx.snap1, "serving.served")
               + counter_delta(ctx.snap0, ctx.snap1, "serving.padded")) \
        / batches
    scanned = counter_delta(ctx.snap0, ctx.snap1, "nns.block_scan_queries")
    if scanned and ctx.work["block_rows"]:
        touched = counter_delta(ctx.snap0, ctx.snap1, "nns.blocks_touched")
        rows = min(rows, touched / scanned * ctx.work["block_rows"])
    return queries, rows
