"""Micro-batching request queue in front of an engine's jitted serve steps.

Paper mapping (Fig. 3): each submitted query is one user hitting the
recommendation fabric. The batcher plays the role of the query scheduler in
front of the pipeline — it accumulates queries, pads/buckets them to a small
set of fixed batch shapes, and feeds each bucket through **one** jitted
`serve_step` (the engine's `dispatch(..., fused=True)`) whose stages are
exactly the paper's computation flow:

    queue  ->  (1a/1b*) UIET/ItET lookups + pooling   (hot-row cache + int8
                        embedding_pool — CMA RAM mode, Sec. III-A1)
           ->  (1b/1c)  filtering DNN -> user embedding u_i (crossbar MVMs)
           ->  (1d)     fixed-radius Hamming NNS over ItET LSH signatures
                        (TCAM threshold match, optionally bank-sharded over
                        a device mesh)
           ->  (2a-2d)  ranking DNN: CTR per candidate
           ->  (2e)     CTR-buffer threshold top-k -> final items

The front ends see an engine only through the `ServingEngine` protocol
(serving/server.py): its request fields, its programs behind
`dispatch`, and its `top_k`. The stages above are `RecSysEngine`'s; a
`DLRMEngine` runs lookup and rank only, through the same classes.

Bucketing keeps the set of compiled shapes tiny (powers of two up to
`max_batch`): a bucket compiles once and is reused forever after, so the
steady-state cost of a query is pure device compute. Padding rows carry
*invalid* ids (-1 everywhere) — they read zero rows, never touch the
hot-row cache counters, and are dropped before results are handed back, so
padding can never change a served result or a measured hit rate (tested;
this used to replicate the last pending query, which made the padded tail
of a bucket — e.g. a queue smaller than the smallest bucket — re-serve real
ids and lean on the `valid` mask alone to keep the counters honest).

The hot-cache hit accumulator is donated to the engine's jitted stages
(`ServingEngine.dispatch`'s `acc`), so the counters update in place
across batches without a host round-trip per flush.

Telemetry (docs/OBSERVABILITY.md): with ``trace=True`` (the default)
every ticket carries a stage-span chain (submit -> admit -> bucket ->
dispatch -> scan -> rank -> resolve, `repro.obs.tracing.STAGES`) on its
`ServedQuery.stages` and on the `TicketTrace` records `take_trace()`
hands back; a flush's own host time is timed in phases (stack, dispatch,
sync, fanout: `repro.obs.PhaseSpans`); the per-server `MetricsRegistry`
accumulates the ticket-latency and phase histograms plus pruned-scan
block counts, and `stats()` is a compatibility view over `snapshot()`
(`server.stats_view`), safe to take while another thread serves. The
whole layer is overhead-gated in benchmarks/obs_overhead.py.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Sequence

import jax
import numpy as np

from repro.obs import MetricsRegistry, TicketTrace, phase_spans
from repro.serving.hot_cache import CacheStats
from repro.serving.server import (
    STATUS_OK,
    SchemaMismatchError,
    ServerClosedError,
    ServerConfigError,
    ServingEngine,
    stats_view,
)

# tickets traced beyond this are dropped (counted in `serving.trace_dropped`)
# rather than growing the trace list without bound between take_trace calls
TRACE_CAP = 100_000


def default_buckets(max_batch: int) -> tuple[int, ...]:
    """Powers of two up to max_batch (always includes max_batch)."""
    b, out = 1, []
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


@dataclasses.dataclass
class ServedQuery:
    """One redeemed ticket: the recommendation (or its admission outcome).

    ``status`` is ``"ok"`` for an engine-served result; the concurrent
    front-end resolves rejected/failed tickets as ``"shed"`` / ``"error"``
    with sentinel payloads (items all -1, scores all 0) instead of raising
    through `result()` — see serving/server.py.
    """

    items: np.ndarray  # (top_k,) recommended item ids, -1 padded
    scores: np.ndarray  # (top_k,) CTR scores
    status: str = STATUS_OK  # "ok" | "shed" | "error"
    tenant: int = 0  # submitting tenant (0 for single-tenant front-ends)
    stages: tuple = ()  # stage-span chain (obs.tracing.STAGES); () untraced

    @property
    def ok(self) -> bool:
        """True when the engine actually served this ticket."""
        return self.status == STATUS_OK


class MicroBatcher:
    """Synchronous micro-batching queue over a `ServingEngine`.

    submit() enqueues single requests (dicts holding the engine's
    `request_fields`); flush() drains the queue through bucket-shaped
    jitted serve steps; results() hands back per-ticket recommendations
    in submission order. `serve_many` is the one-call convenience
    wrapper.
    """

    mode = "sync"

    def __init__(self, engine: ServingEngine, *, max_batch: int = 256,
                 buckets: Sequence[int] | None = None, trace: bool = True,
                 registry: MetricsRegistry | None = None,
                 record_tickets: bool = True):
        self.engine = engine
        self.max_batch = max_batch
        self.buckets = tuple(sorted(buckets or default_buckets(max_batch)))
        if self.buckets[-1] != max_batch:
            raise ServerConfigError(
                f"largest bucket {self.buckets[-1]} must equal "
                f"max_batch={max_batch} (buckets={self.buckets})")
        self._fields = engine.request_fields
        self._pending: list[tuple[int, dict]] = []
        self._results: dict[int, ServedQuery] = {}
        self._next_ticket = 0
        self._closed = False
        # donated accumulator: hot-cache hits/lookups across every batch.
        # The lock covers each donation and the collector's read, so a
        # snapshot from another thread never reads a donated array.
        self._stats = CacheStats.zero()
        self._stats_lock = threading.Lock()
        self._cache_read = {"hits": 0, "lookups": 0}
        # optional lookup-frequency hook (LiveCatalog.attach wires it to
        # LiveCatalog.observe): called per served chunk with one flat int
        # array of the item ids the batch looked up, as the engine's
        # `looked_up_ids` gives them (a RecSysEngine: history rows and the
        # served candidates). Pure host-side telemetry; never affects
        # serving results.
        self.observer = None
        self._tenant_of: dict[int, int] = {}  # ticket -> submitting tenant
        self._per_tenant: dict[int, dict] = {}
        self.n_served = 0
        self.n_padded = 0
        self.n_batches = 0
        # telemetry: submit time per open ticket + completed-ticket trace.
        # With record_tickets=False an outer front end owns the tickets:
        # results carry only the bucket..rank stamps, and no TicketTrace
        # or latency is recorded here.
        self.trace = bool(trace)
        self.record_tickets = record_tickets
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.registry.register_collector(self._collect)
        self.phases = phase_spans(self.registry, self.trace)
        self._t_submit: dict[int, float] = {}
        self._trace: list[TicketTrace] = []
        self.n_trace_dropped = 0

    # ------------------------------------------------------------------
    def swap_engine(self, engine: ServingEngine) -> None:
        """Atomically swap to a new engine epoch/update view.

        The live-catalog publication point (`catalog.LiveCatalog.attach`):
        every bucket dispatched *after* the swap serves from `engine`;
        buckets already dispatched (the `AsyncServer` in-flight ring) hold
        device buffers of the old engine value and finish on that epoch —
        a bucket is always entirely one epoch, never mixed. The hot-cache
        hit accumulator and the served/padded counters carry over.
        An engine whose `request_fields` differ raises
        `SchemaMismatchError`.
        """
        if engine.request_fields != self._fields:
            raise SchemaMismatchError(
                "swap_engine: request schema changed; "
                "start a new server instead")
        self.engine = engine

    # ------------------------------------------------------------------
    def submit(self, query: dict, *, tenant: int = 0) -> int:
        """Enqueue one user query; returns a ticket for `result()`.

        `tenant` tags the ticket for per-tenant accounting (`stats()`);
        single-tenant front-ends serve every tenant from the one queue.
        """
        if self._closed:
            raise ServerClosedError("submit() on a closed server")
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, query))
        if tenant != 0:
            self._tenant_of[ticket] = tenant
        t = self._per_tenant.setdefault(tenant, {"submitted": 0, "served": 0,
                                                 "shed": 0, "errors": 0})
        t["submitted"] += 1
        if self.trace and self.record_tickets:
            self._t_submit[ticket] = time.perf_counter()
        return ticket

    def result(self, ticket: int, *,
               timeout: float | None = None) -> ServedQuery:
        """Recommendations for `ticket` (flushes the queue if still pending).

        Pops the result — each ticket can be redeemed exactly once.
        `timeout` is accepted for protocol uniformity; the synchronous
        front-ends resolve every ticket inside `flush()` and never wait.
        """
        if ticket not in self._results:
            self.flush()
        return self._results.pop(ticket)

    def serve_many(self, queries: Sequence[dict], *,
                   tenant: int = 0) -> list[ServedQuery]:
        """Submit, flush, and collect: one ServedQuery per input query,
        in submission order."""
        tickets = [self.submit(q, tenant=tenant) for q in queries]
        self.flush()
        return [self.result(t) for t in tickets]

    # ------------------------------------------------------------------
    def flush(self) -> None:
        """Drain the queue through bucket-shaped jitted serve steps.

        With tracing on, the synchronous path observes the *real* device
        stage boundaries: an intermediate block on the dispatch's `edge`
        (the NNS result of a RecSysEngine) marks the scan->rank edge (free
        here — this path blocks on the ranked items immediately after
        anyway), and the batch's meters (a pruned scan's blocks-touched
        counts, say) are summed into the registry. The flush's phases
        (stack, dispatch, sync, fanout) tile its wall time.
        """
        phases = self.phases
        with phases.chain():
            while self._pending:
                chunk = self._pending[: self.max_batch]
                self._pending = self._pending[self.max_batch:]
                bucket = next(b for b in self.buckets if b >= len(chunk))
                t_bucket = phases.enter("stack")
                batch = self._stack([q for _, q in chunk], bucket)
                phases.enter("dispatch")
                with self._stats_lock:
                    out = self.engine.dispatch(batch, self._stats,
                                               fused=True)
                    self._stats = out.acc
                t_dispatch = phases.enter("sync")
                tail = ()
                if self.trace:
                    jax.block_until_ready(out.edge)
                    t_scan = time.perf_counter()
                items = np.asarray(out.items)
                scores = np.asarray(out.scores)
                t_rank = phases.enter("fanout")
                if self.trace:
                    self._meter(out.meters)
                    tail = (("bucket", t_bucket), ("dispatch", t_dispatch),
                            ("scan", t_scan), ("rank", t_rank))
                self._observe(chunk, items)
                for row, (ticket, _) in enumerate(chunk):
                    self._resolve(ticket, items[row], scores[row], tail)
                self.n_served += len(chunk)
                self.n_padded += bucket - len(chunk)
                self.n_batches += 1

    def _observe(self, chunk, items) -> None:
        """Feed the frequency observer one served chunk's item lookups, as
        the engine's `looked_up_ids` names them for the real (non-padding)
        requests and the items served back to them. Invalid (-1) ids are
        filtered by the observer."""
        if self.observer is None or not len(chunk):
            return
        self.observer(self.engine.looked_up_ids(
            [q for _, q in chunk], items[: len(chunk)]))

    def _resolve(self, ticket: int, items, scores, tail: tuple) -> None:
        """Record one served ticket (+ its tenant accounting + spans);
        `tail` is its bucket's bucket/dispatch/scan/rank stamps."""
        tenant = self._tenant_of.pop(ticket, 0)
        stages = self._close_span(ticket, tenant, STATUS_OK, tail)
        self._results[ticket] = ServedQuery(items=items, scores=scores,
                                            tenant=tenant, stages=stages)
        self._per_tenant[tenant]["served"] += 1

    def _close_span(self, ticket: int, tenant: int, status: str,
                    tail: tuple) -> tuple:
        """The ticket's finished span chain: submit/admit (the synchronous
        front-ends admit at submit: no queue to shed from), `tail`, and
        the resolve boundary, recorded as a `TicketTrace` and a latency
        observation. Just `tail` when an outer front end owns the ticket."""
        if not self.trace:
            return ()
        if not self.record_tickets:
            return tail
        t_sub = self._t_submit.pop(ticket, None)
        if t_sub is None:
            return ()
        t_res = time.perf_counter()
        stages = (("submit", t_sub), ("admit", t_sub), *tail,
                  ("resolve", t_res))
        self._record_trace(
            TicketTrace(ticket, tenant, t_sub, t_res, status, stages))
        self.registry.observe("serving.ticket_latency_s", t_res - t_sub)
        return stages

    def _record_trace(self, rec: TicketTrace) -> None:
        if len(self._trace) >= TRACE_CAP:
            self.n_trace_dropped += 1
            return
        self._trace.append(rec)

    def take_trace(self) -> list[TicketTrace]:
        """Return and clear the completed-ticket trace (load harness /
        `tools/obs_report.py`); every record carries its span chain when
        the server was built with ``trace=True``."""
        out, self._trace = self._trace, []
        return out

    def _meter(self, meters: dict) -> None:
        """Add a batch's meters (`Dispatched.meters`: counter name -> value,
        an array summed) to the registry's counters: a RecSysEngine's
        pruned-scan blocks touched and streaming-kernel cells and merge
        rounds (`nns.*`), a DLRMEngine's samples and bag rows (`dlrm.*`).
        Called after the batch's results are materialized, so reading the
        tiny counters never stalls the pipeline."""
        if not meters:
            return
        for name, value in jax.device_get(meters).items():
            self.registry.count(name, int(np.sum(value)))

    def _stack_np(self, queries: list[dict], bucket: int) -> dict:
        """Stack requests into one padded (bucket, ...) host batch, a
        column a `request_fields` entry, plus the ``valid`` row mask.

        Padding rows hold each field's `pad` (a RecSysEngine's ids are -1,
        so its padding rows read zero rows and can never count as
        hot-cache lookups — even without the `valid` row mask, which still
        marks real queries so their results are the ones handed back).
        Returns numpy arrays so callers (the pipelined `AsyncServer`) can
        concatenate several buckets into one routed super-batch before the
        single device transfer.
        """
        n = len(queries)
        batch = {}
        for name, shape, dtype, pad in self._fields:
            col = batch[name] = np.full((bucket, *shape), pad, dtype)
            if shape:
                col[:n] = np.stack([np.asarray(q[name], dtype)
                                    for q in queries])
            else:
                col[:n] = [q[name] for q in queries]
        batch["valid"] = np.arange(bucket) < n
        return batch

    def _stack(self, queries: list[dict], bucket: int) -> dict:
        """`_stack_np` placed on device: one padded (bucket, ...) batch."""
        return {k: jax.numpy.asarray(v)
                for k, v in self._stack_np(queries, bucket).items()}

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Flush everything pending, then stop admitting queries.

        Idempotent; `submit()` afterwards raises `ServerClosedError`.
        Unredeemed tickets stay redeemable through `result()`.
        """
        if not self._closed:
            self.flush()
            self._closed = True

    def _collect(self, reg: MetricsRegistry) -> None:
        """Snapshot-time collector: publish the plain-int serving counters
        as registry gauges/info (the hot path never touches the registry
        for these — see docs/OBSERVABILITY.md's overhead contract).

        The cache counts are read only once the device has computed the
        newest accumulator, under the lock its donations take; while a
        bucket is in flight the last counts read stand (exact again once
        a flush returns), so a snapshot never waits on the device and
        never reads a donated array."""
        with self._stats_lock:
            if all(x.is_ready() for x in self._stats):
                self._cache_read = self._stats.as_dict()
            cache = self._cache_read
        reg.info("serving.mode", self.mode)
        reg.info("serving.closed", self._closed)
        reg.gauge("serving.submitted", self._next_ticket)
        reg.gauge("serving.served", self.n_served)
        reg.gauge("serving.shed", 0)
        reg.gauge("serving.errors", 0)
        reg.gauge("serving.pending", len(self._pending))
        reg.gauge("serving.padded", self.n_padded)
        reg.gauge("serving.batches", self.n_batches)
        reg.gauge("serving.trace_dropped", self.n_trace_dropped)
        reg.gauge("cache.hits", cache["hits"])
        reg.gauge("cache.lookups", cache["lookups"])
        for name, value in self.engine.gauges().items():
            reg.gauge(name, value)
        reg.info("serving.per_tenant",
                 {t: dict(v) for t, v in self._per_tenant.items()})

    def snapshot(self) -> dict:
        """The full telemetry snapshot (`MetricsRegistry.snapshot`):
        merged counters + collector gauges + histogram summaries."""
        return self.registry.snapshot()

    def stats(self) -> dict:
        """The unified `Server` stats schema (see docs/SERVING.md) — a
        compatibility view over `snapshot()` (`server.stats_view`)."""
        return stats_view(self.snapshot())
