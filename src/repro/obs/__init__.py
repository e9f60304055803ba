"""Observability: the unified telemetry layer for the serving stack.

One `MetricsRegistry` per server (or one shared across a serving stack)
is the single home for every counter the subsystems used to keep ad-hoc
— hot-cache hits, pruned-scan blocks touched, delta-overlay occupancy,
tier residency, compaction pauses, shed/error accounting, fold staleness
— plus per-request **stage spans** threaded through the ticket lifecycle
(submit -> admit -> bucket -> dispatch -> scan -> rank -> resolve) in all
three `make_server` modes, and per-cycle **phase spans** of the serving
thread on the profiler's clock (`PhaseSpans`, `watch_gc`). Exporters:
`MetricsRegistry.snapshot()` (flat dict, embedded in BENCH_*.json),
`to_prometheus()` (text exposition),
`EventLog` JSONL, and the `tools/obs_report.py` breakdown CLI. The whole
layer is overhead-gated: benchmarks/obs_overhead.py asserts instrumented
serving holds >= 0.95x uninstrumented qps. See docs/OBSERVABILITY.md.
"""
from repro.obs.registry import (
    EventLog,
    MetricsRegistry,
    bucket_upper_bounds,
)
from repro.obs.tracing import (
    PHASES,
    STAGES,
    PhaseSpans,
    TicketTrace,
    dump_trace,
    phase_spans,
    stage_durations,
    trace_record,
    unwatch_gc,
    watch_gc,
    well_ordered,
)

__all__ = [
    "PHASES",
    "STAGES",
    "PhaseSpans",
    "EventLog",
    "MetricsRegistry",
    "TicketTrace",
    "bucket_upper_bounds",
    "dump_trace",
    "phase_spans",
    "stage_durations",
    "trace_record",
    "unwatch_gc",
    "watch_gc",
    "well_ordered",
]
