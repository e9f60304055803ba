"""Pipelined (double-buffered) serving over an engine's staged pipeline.

iMARS's end-to-end win comes from keeping the filtering and ranking stages
busy *simultaneously* (paper Fig. 3: the CMA banks scan while the crossbars
rank the previous query's candidates). The synchronous `MicroBatcher` loses
that overlap in software: each bucket is stacked on the host, served, and
converted back to numpy before the next bucket is even assembled, so the
host sits idle while the device scans and the device sits idle while the
host stacks — the MicroRec/RecNMP observation that deployed RecSys latency
hides in lookup/compute *serialization*, not in any single kernel.

`AsyncServer` recovers the overlap with JAX async dispatch — no threads:

  * each bucket is dispatched through the engine's **staged** serve
    pipeline (`ServingEngine.dispatch`: for a `RecSysEngine`
    `lookup_step` -> `scan_step` -> `rank_stage_step`, the fused
    `serve_step` split at its stage boundaries; for a `DLRMEngine` its
    lookup and rank stages) and the resulting device futures are pushed
    onto a small ring of in-flight buckets;
  * nothing blocks until the ring holds `depth` buckets: while bucket i's
    NNS scan runs on the device, the host is already stacking/padding
    bucket i+1 and dispatching its lookup stage — double-buffering for
    `depth=2`, deeper rings for burstier devices;
  * results are materialized (the only host sync) when a bucket is retired
    off the ring, so the numpy conversion + per-ticket fan-out of bucket i
    also overlaps bucket i+1's device compute;
  * the hot-cache accumulator is threaded through the donated stage steps
    exactly like the synchronous path, so measured hit rates stay honest.

**Query-mesh routing.** When the engine was sharded with a query axis
(`RecSysEngine.shard(mesh, ..., query_axis=...)`), up to `coalesce` full
buckets are concatenated into one routed super-batch per dispatch: the
query-parallel `shard_map` splits its rows contiguously over the query
axis, so concurrent buckets land on **disjoint query blocks** and scan the
catalog in parallel instead of queueing behind each other. `coalesce`
defaults to the engine's `coalesce`, the query-axis size (1 — no
coalescing — for unrouted engines), and can be forced for testing.

Bit-for-bit contract (tested in tests/test_async_serving.py): pipelined
serving returns exactly the items, scores, and cache counters the
synchronous `MicroBatcher` returns for the same query stream — the ring,
the stage split, and the routing are pure execution knobs.

**Epoch-safe engine swap.** `swap_engine` (inherited from `MicroBatcher`,
driven by `serving/catalog.py`'s `LiveCatalog._publish`) composes with the
ring MVCC-style: a swap never touches in-flight entries — each ring entry
holds device futures of the engine value it was dispatched against, so
those buckets finish on the *old* epoch while every later dispatch serves
the new one. A bucket is always entirely one epoch (asserted over whole
streams in tests/test_catalog.py); counters and the donated hot-cache
accumulator carry across the swap.
"""
from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry
from repro.serving.batcher import MicroBatcher
from repro.serving.server import ServerConfigError, ServingEngine
# the stage programs a RecSysEngine's `dispatch` runs; kept importable
# here, where tests/bench/test_bench_cells.py patches them
from repro.serving.recsys_engine import (  # noqa: F401
    lookup_step,
    rank_stage_step,
    scan_step,
)


class _InFlight(NamedTuple):
    """One dispatched (possibly coalesced) bucket riding the ring."""

    parts: tuple  # ((chunk, bucket), ...) — chunk = [(ticket, query), ...]
    items: object  # (sum(buckets), top_k) device future
    scores: object  # (sum(buckets), top_k) device future
    meters: dict | None = None  # the batch's counter futures
    t_bucket: float = 0.0  # host time the buckets were taken off the queue
    t_dispatch: float = 0.0  # host time the staged pipeline was dispatched


class AsyncServer(MicroBatcher):
    """Pipelined micro-batching server over a `ServingEngine`.

    Drop-in for `MicroBatcher` (same submit/result/serve_many API, same
    bucketing, same counters) with a ring of up to `depth` in-flight
    buckets dispatched through the staged serve pipeline.

    Args:
      engine: the serving engine (local or sharded).
      max_batch / buckets: bucketing, as `MicroBatcher`.
      depth: in-flight ring size; 1 degenerates to synchronous serving,
        2 (default) double-buffers host work against device compute.
      coalesce: number of full buckets fused into one routed super-batch
        per dispatch. Default: the engine's `coalesce` (a RecSysEngine's
        query-mesh axis size when sharded with `query_axis=...`, else 1).
        Values > 1 route concurrent buckets onto disjoint query blocks of
        the mesh.

    Invariant: results bit-match the synchronous `MicroBatcher` for any
    depth / coalesce / bucket mix (tested).
    """

    mode = "pipelined"

    def __init__(self, engine: ServingEngine, *, max_batch: int = 256,
                 buckets: Sequence[int] | None = None, depth: int = 2,
                 coalesce: int | None = None, trace: bool = True,
                 registry: MetricsRegistry | None = None,
                 record_tickets: bool = True):
        super().__init__(engine, max_batch=max_batch, buckets=buckets,
                         trace=trace, registry=registry,
                         record_tickets=record_tickets)
        if depth < 1:
            raise ServerConfigError(f"ring depth must be >= 1, got {depth}")
        if coalesce is None:
            coalesce = engine.coalesce
        if coalesce < 1:
            raise ServerConfigError(f"coalesce must be >= 1, got {coalesce}")
        self.depth = depth
        self.coalesce = coalesce
        self._ring: deque[_InFlight] = deque()

    # ------------------------------------------------------------------
    @property
    def in_flight(self) -> int:
        """Dispatched-but-unretired buckets currently riding the ring."""
        return len(self._ring)

    def flush(self) -> None:
        """Drain the queue, keeping up to `depth` buckets in flight.

        Dispatches are non-blocking (JAX async dispatch); the only host
        syncs are the retirements, each overlapped with the following
        buckets' host prep and device compute. Returns with every pending
        ticket's result materialized, like the synchronous flush. Its
        phases (stack, dispatch, sync, fanout) tile its wall time.
        """
        with self.phases.chain():
            while self._pending:
                self._ring.append(self._dispatch(self._take_parts()))
                while len(self._ring) >= self.depth:
                    self._retire()
            while self._ring:
                self._retire()

    # ------------------------------------------------------------------
    def _take_parts(self) -> list[tuple[list, int]]:
        """Pop 1..coalesce chunks off the queue as (chunk, bucket) parts.

        Only *full* `max_batch` chunks coalesce (so the set of compiled
        super-batch shapes stays tiny); a short tail always ships alone in
        its own pow2 bucket.
        """
        parts = []
        while self._pending and len(parts) < self.coalesce:
            chunk = self._pending[: self.max_batch]
            if parts and len(chunk) < self.max_batch:
                break  # tail chunk: dispatch separately
            self._pending = self._pending[self.max_batch:]
            bucket = next(b for b in self.buckets if b >= len(chunk))
            parts.append((chunk, bucket))
        return parts

    def _dispatch(self, parts: list[tuple[list, int]]) -> _InFlight:
        """Stack `parts` into one batch and dispatch the staged pipeline."""
        t_bucket = self.phases.enter("stack")
        stacked = [self._stack_np([q for _, q in chunk], bucket)
                   for chunk, bucket in parts]
        host = (stacked[0] if len(stacked) == 1 else
                {k: np.concatenate([s[k] for s in stacked])
                 for k in stacked[0]})
        batch = {k: jnp.asarray(v) for k, v in host.items()}
        self.phases.enter("dispatch")
        with self._stats_lock:
            out = self.engine.dispatch(batch, self._stats)
            self._stats = out.acc
        for chunk, bucket in parts:
            self.n_served += len(chunk)
            self.n_padded += bucket - len(chunk)
            self.n_batches += 1
        return _InFlight(parts=tuple(parts), items=out.items,
                         scores=out.scores, meters=out.meters,
                         t_bucket=t_bucket,
                         t_dispatch=(time.perf_counter() if self.trace
                                     else 0.0))

    def _retire(self) -> None:
        """Materialize the oldest in-flight bucket and fan out its results.

        Span semantics for the ring (docs/OBSERVABILITY.md): the device
        futures retire *together* at the one host sync, so the ``scan``
        boundary lands on the retirement and ``rank`` is ~0 — the whole
        in-flight device wait shows up as dispatch -> scan. Observing the
        real scan/rank edge would require an extra intermediate block,
        which is exactly the serialization the ring exists to remove.
        """
        inf = self._ring.popleft()
        self.phases.enter("sync")
        items = np.asarray(inf.items)  # the one host sync per bucket
        scores = np.asarray(inf.scores)
        t_sync = self.phases.enter("fanout")
        tail = ()
        if self.trace:
            self._meter(inf.meters)
            tail = (("bucket", inf.t_bucket),
                    ("dispatch", inf.t_dispatch),
                    ("scan", t_sync), ("rank", t_sync))
        row = 0
        for chunk, bucket in inf.parts:
            self._observe(chunk, items[row: row + bucket])
            for j, (ticket, _) in enumerate(chunk):
                self._resolve(ticket, items[row + j], scores[row + j], tail)
            row += bucket

    # ------------------------------------------------------------------
    def _collect(self, reg: MetricsRegistry) -> None:
        """`MicroBatcher._collect` + the ring knobs and occupancy."""
        super()._collect(reg)
        reg.gauge("serving.ring_depth", self.depth)
        reg.gauge("serving.coalesce", self.coalesce)
        reg.gauge("serving.in_flight", self.in_flight)
