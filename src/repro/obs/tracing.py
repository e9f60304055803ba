"""Per-request stage spans: the ticket lifecycle as host timestamps.

iMARS Fig. 3 is a *pipeline*: lookups feed the filtering NNS which feeds
the ranking crossbars, and the paper's claims are per-stage latency
breakdowns. The serving tier mirrors that pipeline in software, so every
ticket — in all three `make_server` modes, including shed and error
outcomes — carries a **span chain**: ``((stage, t), ...)`` with
`time.perf_counter()` timestamps at each lifecycle boundary, ordered by
`STAGES`:

    submit    the caller handed the query in
    admit     the admission decision (== submit for the single-tenant
              front-ends; shed tickets stop here and jump to resolve)
    bucket    the query left its queue and was assigned a batch bucket
    dispatch  the jitted stage pipeline was dispatched to the device
    scan      the filtering NNS scan completed (sync mode observes the
              real device boundary via an intermediate block; pipelined
              mode retires scan+rank together at the ring sync, so scan
              carries the whole device wait and rank is ~0 there)
    rank      the ranked items were materialized on the host
    resolve   the ticket's result was recorded / redeemable

A chain is *contiguous*: stage i starts where stage i-1 ended, so the sum
of stage durations equals ``done_s - submit_s`` exactly — the property
`benchmarks/obs_overhead.py` gates (stage sum within 10% of measured
ticket latency) and `tools/obs_report.py` renders as a breakdown table.

A chain may be a **subsequence** of `STAGES` (shed: submit/admit/resolve;
error: submit/admit/resolve) but is always non-empty when tracing is on,
starts at ``submit``, ends at ``resolve``, and is non-decreasing in time
(`well_ordered` checks all of it; tested in tests/test_obs.py).

**Phase spans** time the serving thread itself, per cycle and never per
ticket: `PhaseSpans` tiles a serve loop with the contiguous `PHASES`
(wait, collect, stack, dispatch, sync, fanout, publish), each both a
`jax.profiler.TraceAnnotation` ``serving.<phase>`` on the profiler's
clock, beside the device ops, and one observation of the registry
histogram ``serving.phase.<phase>_s``. `watch_gc` adds the collector's
pauses as ``host.gc`` spans and the ``host.gc_pause_s`` histogram.
"""
from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
from typing import NamedTuple

# canonical stage order; every span chain's names are a subsequence
STAGES = ("submit", "admit", "bucket", "dispatch", "scan", "rank",
          "resolve")
_STAGE_RANK = {s: i for i, s in enumerate(STAGES)}


class TicketTrace(NamedTuple):
    """One completed ticket's lifecycle, for the load harness + reports.

    ``stages`` is the span chain described in the module docstring —
    ``()`` when the owning server was built with ``trace=False``. The
    first five fields predate the telemetry layer and keep their exact
    meaning (`load_gen.summarize_trace` consumes only those).
    """

    ticket: int
    tenant: int
    submit_s: float  # time.perf_counter() at admission
    done_s: float  # time.perf_counter() at resolution (== submit_s if shed)
    status: str  # "ok" | "shed" | "error"
    stages: tuple = ()  # ((stage, perf_counter_s), ...), see STAGES

    @property
    def latency_s(self) -> float:
        return self.done_s - self.submit_s


def stage_durations(stages) -> dict:
    """Per-stage wall time of one span chain: {later_stage: seconds}.

    Stage ``s`` is charged the gap since the previous boundary, so the
    values sum to last-minus-first exactly (the chain is contiguous).
    """
    out = {}
    for (_, t0), (name, t1) in zip(stages, stages[1:]):
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def well_ordered(stages) -> bool:
    """True when `stages` is a valid span chain: names form a non-empty
    subsequence of `STAGES` starting at ``submit`` and ending at
    ``resolve``, with non-decreasing timestamps."""
    if not stages:
        return False
    names = [s for s, _ in stages]
    times = [t for _, t in stages]
    if names[0] != "submit" or names[-1] != "resolve":
        return False
    ranks = [_STAGE_RANK.get(n, -1) for n in names]
    if -1 in ranks or any(b <= a for a, b in zip(ranks, ranks[1:])):
        return False
    return all(b >= a for a, b in zip(times, times[1:]))


def trace_record(rec: TicketTrace) -> dict:
    """One `TicketTrace` as the JSON shape `tools/obs_report.py` reads."""
    return {"ticket": int(rec.ticket), "tenant": int(rec.tenant),
            "submit_s": float(rec.submit_s), "done_s": float(rec.done_s),
            "status": rec.status,
            "stages": [[s, float(t)] for s, t in rec.stages]}


def dump_trace(trace, path) -> int:
    """Write a `take_trace()` result as JSONL; returns the record count.

    The file is the input format of ``python tools/obs_report.py`` (one
    JSON object per line, `trace_record` shape).
    """
    n = 0
    with open(path, "w") as f:
        for rec in trace:
            f.write(json.dumps(trace_record(rec), sort_keys=True) + "\n")
            n += 1
    return n


# the phases of one serve cycle, in the order the drain thread passes them
# (docs/OBSERVABILITY.md): blocked on its condition with nothing queued;
# taking the chunk off the tenant queues; chunk to padded device batch;
# the stage-program calls; blocked on the device's results; per-ticket
# results of the ring; per-ticket results of the front end
PHASES = ("wait", "collect", "stack", "dispatch", "sync", "fanout",
          "publish")


class PhaseSpans:
    """Contiguous phase spans of one thread's serve loop, on two clocks.

    `enter(phase)` ends the open phase and opens `phase` at the same
    `time.perf_counter()` instant (and returns it), so the phases of a
    chain tile it: their durations sum to its wall time. Each phase is a
    `jax.profiler.TraceAnnotation` named ``serving.<phase>`` and, when it
    ends, one observation of the histogram ``serving.phase.<phase>_s``.
    Entering the phase already open continues it.

    `chain()` brackets a run of phases. Chains nest; the outermost ends
    the open phase when it exits, so a ring's flush inside the drain
    thread's chain joins the drain cycle, and a caller's own flush stops
    timing when it returns. One thread owns an instance.
    """

    def __init__(self, registry):
        from jax.profiler import TraceAnnotation

        self._registry = registry
        self._annotation = TraceAnnotation
        self._labels = {p: f"serving.{p}" for p in PHASES}
        self._hists = {p: f"serving.phase.{p}_s" for p in PHASES}
        self._phase = None
        self._span = None
        self._t = 0.0
        self._depth = 0

    def enter(self, phase: str) -> float:
        """End the open phase and open `phase` now; returns the instant
        (`time.perf_counter()`) at which the one ended and the other
        began."""
        t = time.perf_counter()
        if phase == self._phase:
            return t
        span = self._annotation(self._labels[phase])
        last, t_last = self._phase, self._t
        if last is not None:  # close and open back to back on both clocks
            self._span.__exit__(None, None, None)
        span.__enter__()
        self._span, self._phase, self._t = span, phase, t
        if last is not None:
            self._registry.observe(self._hists[last], t - t_last)
        return t

    def _end(self, t: float) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._registry.observe(self._hists[self._phase], t - self._t)
            self._span = self._phase = None

    @contextlib.contextmanager
    def chain(self):
        """Bracket a run of phases; the outermost chain ends the open
        phase on exit (the class docstring)."""
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            if not self._depth:
                self._end(time.perf_counter())


class _NoPhases:
    """`PhaseSpans` of an untraced server: times and records nothing."""

    @staticmethod
    def enter(phase: str) -> float:
        return 0.0

    @staticmethod
    def chain():
        return contextlib.nullcontext()


NO_PHASES = _NoPhases()


def phase_spans(registry, enabled: bool):
    """A `PhaseSpans` into `registry`, or `NO_PHASES` when not `enabled`."""
    return PhaseSpans(registry) if enabled else NO_PHASES


# `watch_gc` state: one `gc.callbacks` hook per process, feeding the
# ``host.gc_pause_s`` histogram of every watching registry
_gc_lock = threading.Lock()
_gc_watchers: dict = {}  # id(registry) -> [registry, histogram, users]
_gc_hists: tuple = ()
_gc_open = None  # (annotation, perf_counter at "start") of the open pause


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        from jax.profiler import TraceAnnotation

        span = TraceAnnotation("host.gc")
        span.__enter__()
        _gc_open = (span, time.perf_counter())
    elif _gc_open is not None:
        (span, t0), _gc_open = _gc_open, None
        span.__exit__(None, None, None)
        pause = time.perf_counter() - t0
        for hist in _gc_hists:
            hist.observe(pause)


def watch_gc(registry) -> None:
    """Time the garbage collector's pauses as ``host.gc`` spans and into
    `registry`'s ``host.gc_pause_s`` histogram, until `unwatch_gc`.

    The `gc.callbacks` hook is installed once per process while any
    registry watches; a registry watched twice counts each pause once.
    """
    global _gc_hists
    with _gc_lock:
        entry = _gc_watchers.get(id(registry))
        if entry is None:  # holding the registry keeps its id unique
            entry = _gc_watchers[id(registry)] = [
                registry, registry.histogram("host.gc_pause_s"), 0]
        entry[2] += 1
        _gc_hists = tuple(e[1] for e in _gc_watchers.values())
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def unwatch_gc(registry) -> None:
    """Undo one `watch_gc(registry)`; the hook goes with the last one."""
    global _gc_hists
    with _gc_lock:
        entry = _gc_watchers.get(id(registry))
        if entry is None:
            return
        entry[2] -= 1
        if not entry[2]:
            del _gc_watchers[id(registry)]
        _gc_hists = tuple(e[1] for e in _gc_watchers.values())
        if not _gc_watchers and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
