"""Split the device's idle time by what the serving thread was doing.

    python3 bench/host_phases.py [trace dir]     (default bench_out/trace)

prints the split of the trace `bench/run.py --trace 1` left, over its
``bench.traced`` window, as JSON.

The program's drain thread tiles its time with phase spans on the
profiler's clock (``serving.<phase>``, `repro.obs.PHASES`): waiting for
work, collecting a chunk, stacking it, dispatching the stage programs,
blocked on the device (``sync``), fanning results out per ticket, and
publishing them per outer ticket. Definitions, by interval intersection
(no sampling), device times averaged over chips:

* idle: the stretches of the window in which no op ran on a device
  (`trace_reduce.gaps` of the ops' union);
* the phase line: the host line that carries the most ``serving.*`` phase
  spans (the drain thread);
* ``idle_s``: the idle seconds (per chip);
* ``host_frac``: the share of idle time that overlaps a host phase of
  the phase line (`HOST_PHASES`); the rest is idle while that thread
  waited for work, sat in ``sync``, or ran under no phase span;
* ``by_phase_s``: idle seconds under each phase, under none
  (``"none"``), and under a ``host.gc`` span on any host line
  (``"host.gc"``, the collector's pauses, which overlap the phases);
* ``phase_s``: seconds of each phase on the phase line in the window;
* ``cover``: the share of the window the phase line's spans cover.

A trace without phase spans (a program that does not emit them) gives
None.
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as TR  # noqa: E402

# where bench/run.py leaves the trace of a --trace 1 run
TRACE_DIR = ROOT / "bench_out" / "trace"
WINDOW_SPAN = "bench.traced"
PHASES = ("wait", "collect", "stack", "dispatch", "sync", "fanout",
          "publish")
# the drain thread's own host work: not waiting for work, not on the device
HOST_PHASES = ("collect", "stack", "dispatch", "fanout", "publish")
SPAN_PREFIX = "serving."
GC_SPAN = "host.gc"


def overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def phase_line(host):
    """The host line carrying the most phase spans, or None."""
    names = {SPAN_PREFIX + p for p in PHASES}
    counts: dict = {}
    for n, _, _, line in host:
        if n in names:
            counts[line] = counts.get(line, 0) + 1
    return max(counts, key=counts.get) if counts else None


def split_intervals(device_ops, host, lo, hi) -> dict | None:
    """The split (module docstring) of window [lo, hi].

    `device_ops`: {device: [(start, end)] of its ops}; `host`: [(name,
    start, end, line)] host events. Times in ns; seconds out.
    """
    line = phase_line(host)
    if line is None or not device_ops:
        return None
    spans = {p: TR.union([(s, e) for n, s, e, ln in host
                          if ln == line and n == SPAN_PREFIX + p], lo, hi)
             for p in PHASES}
    any_phase = TR.union([iv for p in PHASES for iv in spans[p]])
    on_host = TR.union([iv for p in HOST_PHASES for iv in spans[p]])
    gc_spans = TR.union([(s, e) for n, s, e, _ in host if n == GC_SPAN],
                        lo, hi)
    n_dev = len(device_ops)
    idle_ns, host_ns = 0, 0
    by = dict.fromkeys((*PHASES, "none", GC_SPAN), 0)
    for ops in device_ops.values():
        idle = TR.gaps(TR.union(ops, lo, hi), lo, hi)
        idle_ns += sum(e - s for s, e in idle)
        host_ns += overlap(idle, on_host)
        for p in PHASES:
            by[p] += overlap(idle, spans[p])
        by["none"] += sum(e - s for s, e in idle) - overlap(idle, any_phase)
        by[GC_SPAN] += overlap(idle, gc_spans)
    return {"idle_s": idle_ns / n_dev / 1e9,
            "host_frac": host_ns / idle_ns if idle_ns else None,
            "by_phase_s": {k: v / n_dev / 1e9 for k, v in by.items()},
            "phase_s": {p: sum(e - s for s, e in spans[p]) / 1e9
                        for p in PHASES},
            "cover": sum(e - s for s, e in any_phase) / (hi - lo),
            "line": line}


def split(pd, window=None) -> dict | None:
    """`split_intervals` of profile `pd` over `window` (default: the
    ``bench.traced`` span)."""
    lo, hi = window or TR.span_window(pd, WINDOW_SPAN)
    device_ops = {d: [(s, e) for _, s, e, _ in lines[TR.OPS_LINE]]
                  for d, lines in TR.device_lines(pd).items()}
    return split_intervals(device_ops, TR.host_events(pd), lo, hi)


def read_split(path=TRACE_DIR) -> dict | None:
    """The split of the trace at `path` (a run's trace directory)."""
    return split(TR.load(path))


if __name__ == "__main__":
    print(json.dumps(read_split(sys.argv[1] if len(sys.argv) > 1
                                else TRACE_DIR)))
