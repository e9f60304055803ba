"""Live catalog: versioned embedding store with delta shard + epoch compaction.

iMARS assumes the ItET sits frozen in the CMA fabric, but a production
catalog churns while traffic is live: items are added, retired, and
re-embedded. Rebuilding the engine per update would recompile the serving
pipeline and stall every in-flight query; mutating the base table in place
would corrupt concurrent serves. This module gives the serving stack an
MVCC-style mutable view that never blocks and never changes a served bit:

  * the **base epoch** (the engine's `item_table_q` / `item_sigs`) stays
    read-only — the streaming-NNS superblock layout is never touched;
  * updates land in a bounded **delta shard** (`DeltaShard`): dense int8
    rows + LSH signatures + global item ids, kept *sorted by id* so both
    the O(log D) membership probe (`searchsorted`, the hot-cache idiom) and
    the bounded candidate truncation stay exact;
  * lookups and ranking read pending rows through
    `hot_cache.cached_rows` with the delta as its overlay (delta > hot >
    base > zero);
  * base rows that were deleted or overwritten are **tombstoned** via the
    engine's `item_mask`, threaded through every NNS plan (dense,
    streaming kernel, bank-sharded, query-parallel) like `n_valid`;
  * the filtering stage scans base + delta and fuses the two bounded
    buffers with one `merge_candidate_buffers` reuse
    (`core.nns.delta_aware_nns`) — results bit-match a from-scratch
    rebuild with the final table;
  * `compact()` folds the delta into a new base **epoch**: one host-side
    scatter, a fresh (empty) delta, and an atomic engine swap between
    buckets — in-flight `AsyncServer` ring entries finish on the old
    epoch, hot-cache counters carry over, and only touched rows were ever
    invalidated from the hot set.

Catalog content is canonically **quantized**: `upsert` quantizes f32 rows
once at ingestion (the CMA stores int8 + scale), and every equality
contract — delta serving, compaction, reference rebuild — is defined over
the quantized rows and their signatures. This keeps bit-match achievable:
row-wise int8 quantization and SRP signatures are per-row operations, so a
row's image is identical whether it entered at build time, through the
delta, or through a compaction scatter.

MicroRec/RecFlash context: both show embedding *placement and remapping*
dominating RecSys latency as much as the lookup kernel. The delta shard is
the remap-friendly answer here — updates never reshuffle the base layout,
and compaction is the one (amortized, off-bucket) moment rows move.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lsh import lsh_signature
from repro.core.nns import (
    EMPTY_ID,
    SUMMARY_BLOCK_ROWS,
    build_block_summary,
    update_block_summary,
)
from repro.core.quantization import (
    QuantizedTensor,
    dequantize_rowwise,
    quantize_rowwise,
)
from repro.serving.hot_cache import (
    invalidate_rows,
    pin_rows,
    top_ids_by_freq,
)
from repro.utils import pytree_dataclass


class DeltaFullError(RuntimeError):
    """The bounded delta shard cannot hold the requested updates."""


@pytree_dataclass(meta_fields=("capacity",))
class DeltaShard:
    """Bounded mutable overlay on a read-only base item table.

    Live slots form an ascending-by-id prefix; free slots carry `EMPTY_ID`
    (which sorts after every real id) so `ids` is always globally sorted —
    the searchsorted membership probe and the (distance, slot) ==
    (distance, id) truncation argument in `core.nns.delta_scan` both hang
    off this invariant. `values`/`scales` are the quantized replacement
    rows (same row-wise int8 format as the base table), `sigs` their
    packed LSH signatures.
    """

    ids: jax.Array  # (D,) int32 ascending, EMPTY_ID = free slot
    values: jax.Array  # (D, d) int8
    scales: jax.Array  # (D, 1) f32
    sigs: jax.Array  # (D, words) uint32
    capacity: int = 0


def empty_delta(capacity: int, embed_dim: int, words: int) -> DeltaShard:
    """An all-free delta shard of `capacity` slots."""
    capacity = int(capacity)
    return DeltaShard(
        ids=jnp.full((capacity,), EMPTY_ID, jnp.int32),
        values=jnp.zeros((capacity, embed_dim), jnp.int8),
        scales=jnp.zeros((capacity, 1), jnp.float32),
        sigs=jnp.zeros((capacity, words), jnp.uint32),
        capacity=capacity)


def delta_n_live(delta: DeltaShard) -> int:
    """Host-side count of occupied delta slots."""
    return int(np.sum(np.asarray(delta.ids) != EMPTY_ID))


# ---------------------------------------------------------------------------
# host-side epoch transitions (apply / compact / materialize / rebuild)
# ---------------------------------------------------------------------------
def ensure_live(engine, delta_capacity: int = 1024):
    """Give `engine` an (empty) delta shard + alive mask if it has none.

    The treedef changes once here (None -> arrays), so jitted serve steps
    compile once for the live layout and never again across updates or
    epochs (as long as the base table does not grow). Engines built
    outside `RecSysEngine.build` (no block summary yet) also get one here,
    so every live catalog can prune its base scans.
    """
    if engine.delta is not None:
        return engine
    n, d = engine.item_table_q.shape
    words = engine.item_sigs.shape[1]
    summary = engine.block_summary
    if summary is None:
        summary = build_block_summary(
            np.asarray(engine.item_sigs), n_valid=n)
    return dataclasses.replace(
        engine,
        delta=empty_delta(delta_capacity, d, words),
        block_summary=summary,
        item_mask=jnp.ones((engine.item_sigs.shape[0],), jnp.bool_)
        .at[n:].set(False))  # shard-padding rows stay dead


def quantize_updates(engine, rows: jax.Array):
    """f32 rows (m, d) -> (int8 values, scales, packed sigs) — the exact
    build-time transform (`RecSysEngine.build`), applied per row."""
    q = quantize_rowwise(jnp.asarray(rows, jnp.float32))
    sigs = lsh_signature(dequantize_rowwise(q), engine.lsh_proj)
    return (np.asarray(q.values), np.asarray(q.scales), np.asarray(sigs))


def engine_apply_updates(engine, upsert_ids=None, upsert_rows=None,
                         delete_ids=None):
    """Fold a batch of updates into the engine's delta shard (host-side).

    upsert_ids/upsert_rows: (m,) int ids + (m, d) f32 embeddings — new ids
    extend the catalog, existing ids re-embed (base row tombstoned, row
    rides the delta until the next compaction). delete_ids: (k,) ids to
    retire (tombstoned everywhere; delete-then-re-add round-trips through
    the delta). Later entries win within one batch. Touched ids are evicted
    from the hot-row cache. Raises `DeltaFullError` when the surviving
    update set does not fit the bounded shard — `LiveCatalog` turns that
    into a forced compaction.

    Returns a new engine (the old epoch view stays valid — MVCC).
    """
    if engine.delta is None:
        raise ValueError("engine has no delta shard; wrap it in "
                         "LiveCatalog or call ensure_live() first")
    delta = engine.delta
    n_base = int(engine.item_table_q.shape[0])

    live: dict[int, tuple] = {}
    ids_np = np.asarray(delta.ids)
    vals_np, scales_np, sigs_np = (np.asarray(delta.values),
                                   np.asarray(delta.scales),
                                   np.asarray(delta.sigs))
    for slot in np.nonzero(ids_np != EMPTY_ID)[0]:
        live[int(ids_np[slot])] = (vals_np[slot], scales_np[slot],
                                   sigs_np[slot])

    mask = np.asarray(engine.item_mask).copy()
    touched: list[int] = []
    if delete_ids is not None:
        for gid in np.asarray(delete_ids, np.int64).reshape(-1):
            gid = int(gid)
            live.pop(gid, None)
            if gid < n_base:
                mask[gid] = False
            touched.append(gid)
    if upsert_ids is not None:
        ids_arr = np.asarray(upsert_ids, np.int64).reshape(-1)
        if np.any(ids_arr < 0) or np.any(ids_arr >= EMPTY_ID):
            raise ValueError(f"item ids must be in [0, {EMPTY_ID})")
        uvals, uscales, usigs = quantize_updates(engine, upsert_rows)
        if len(ids_arr) != len(uvals):
            raise ValueError(f"{len(ids_arr)} ids vs {len(uvals)} rows")
        for i, gid in enumerate(ids_arr):
            gid = int(gid)
            live[gid] = (uvals[i], uscales[i], usigs[i])
            if gid < n_base:
                mask[gid] = False  # base row stale; delta row is the truth
            touched.append(gid)

    if len(live) > delta.capacity:
        raise DeltaFullError(
            f"{len(live)} pending rows > delta capacity {delta.capacity}")

    # keep the block summary sound AND tight: every touched base row's
    # block is recomputed exactly against the new tombstone mask (an
    # incremental OR/AND could only loosen; a stale summary that still
    # counts a tombstoned row is sound but must not survive compaction
    # comparisons — see update_block_summary)
    summary = engine.block_summary
    base_touched = [g for g in touched if g < n_base]
    if summary is not None and base_touched:
        summary = update_block_summary(
            summary, np.asarray(engine.item_sigs), mask, base_touched)

    new = empty_delta(delta.capacity, vals_np.shape[1], sigs_np.shape[1])
    ids_out = np.full(delta.capacity, EMPTY_ID, np.int32)
    vals_out = np.asarray(new.values).copy()
    scales_out = np.asarray(new.scales).copy()
    sigs_out = np.asarray(new.sigs).copy()
    for slot, gid in enumerate(sorted(live)):  # ascending-id prefix
        v, s, g = live[gid]
        ids_out[slot], vals_out[slot] = gid, v
        scales_out[slot], sigs_out[slot] = s, g
    return dataclasses.replace(
        engine,
        delta=DeltaShard(ids=jnp.asarray(ids_out),
                         values=jnp.asarray(vals_out),
                         scales=jnp.asarray(scales_out),
                         sigs=jnp.asarray(sigs_out),
                         capacity=delta.capacity),
        item_mask=jnp.asarray(mask),
        block_summary=summary,
        item_hot=invalidate_rows(engine.item_hot, np.asarray(touched)))


def engine_refresh_model(engine, params):
    """New engine serving the *current* model parameters (host-side).

    The online-learning counterpart of `quantize_updates` for everything
    that is NOT the item table: the filtering/ranking MLPs and the genre
    table swap in directly, the user-feature ETs re-quantize with the
    exact build-time transform, and every pinned UIET hot row is re-pinned
    from its new quantized table (a stale pinned row would change served
    bits vs a cold rebuild — the hot tier must stay bit-transparent).

    Item rows are deliberately untouched: they flow through the delta
    shard (`quantize_updates` via `LiveCatalog.upsert`), which is what
    keeps the base epoch read-only and the MVCC swap atomic. The engine's
    treedef and every leaf shape are preserved, so jitted serve steps
    never retrace across a refresh.
    """
    tables_q = {k: quantize_rowwise(v) for k, v in params["tables"].items()}
    uiet_hot = {}
    for name, cache in engine.uiet_hot.items():
        if cache is not None and cache.capacity:
            ids = np.asarray(cache.hot_ids)
            uiet_hot[name] = pin_rows(tables_q[name], ids[ids != EMPTY_ID],
                                      cache.capacity)
        else:
            uiet_hot[name] = cache
    return dataclasses.replace(
        engine, params=params, tables_q=tables_q,
        genre_table_q=quantize_rowwise(params["genre_table"]),
        uiet_hot=uiet_hot)


def materialize(engine):
    """Fold base + delta into one flat table (the \"final table\").

    Returns (QuantizedTensor (n_total, d), sigs (n_total, words) uint32,
    alive (n_total,) bool numpy) — n_total covers every id ever upserted.
    Rows never touched keep their exact base bytes; delta rows scatter in
    verbatim; id-space gaps (never-written ids below a larger upserted id)
    get the canonical zero-row quantization and stay dead. This is both
    the compaction scatter and the reference-rebuild input, so the two are
    bitwise the same table by construction.
    """
    n_base, d = engine.item_table_q.shape
    words = engine.item_sigs.shape[1]
    ids_np = np.asarray(engine.delta.ids) if engine.delta is not None else \
        np.zeros((0,), np.int32)
    live = np.nonzero(ids_np != EMPTY_ID)[0]
    gids = ids_np[live].astype(np.int64)
    n_total = int(max(n_base, (gids.max() + 1) if len(gids) else 0))

    zero_q = quantize_rowwise(jnp.zeros((1, d), jnp.float32))
    zero_sig = lsh_signature(dequantize_rowwise(zero_q), engine.lsh_proj)
    values = np.broadcast_to(np.asarray(zero_q.values),
                             (n_total, d)).copy()
    scales = np.broadcast_to(np.asarray(zero_q.scales),
                             (n_total, 1)).copy()
    sigs = np.broadcast_to(np.asarray(zero_sig), (n_total, words)).copy()
    values[:n_base] = np.asarray(engine.item_table_q.values)
    scales[:n_base] = np.asarray(engine.item_table_q.scales)
    sigs[:n_base] = np.asarray(engine.item_sigs)[:n_base]

    alive = np.zeros((n_total,), bool)
    if engine.item_mask is not None:
        alive[:n_base] = np.asarray(engine.item_mask)[:n_base]
    else:
        alive[:n_base] = True
    if len(live):
        values[gids] = np.asarray(engine.delta.values)[live]
        scales[gids] = np.asarray(engine.delta.scales)[live]
        sigs[gids] = np.asarray(engine.delta.sigs)[live]
        alive[gids] = True
    table = QuantizedTensor(values=jnp.asarray(values),
                            scales=jnp.asarray(scales))
    return table, jnp.asarray(sigs), alive


def compact_engine(engine):
    """Fold the delta into a fresh base epoch; returns the new engine.

    One host-side scatter (`materialize`) + an empty delta: the old engine
    object — and every device buffer an in-flight bucket was dispatched
    against — stays fully valid, so callers swap epochs atomically between
    buckets. The hot cache carries over untouched: every surviving pinned
    row's backing bytes are identical in the new base (touched rows were
    already evicted at update time). A sharded engine is re-sharded onto
    its mesh after the fold.
    """
    if engine.delta is None:
        raise ValueError("engine has no delta shard to compact")
    table, sigs, alive = materialize(engine)
    d, words = table.shape[1], sigs.shape[1]
    br = (engine.block_summary.block_rows if engine.block_summary is not None
          else SUMMARY_BLOCK_ROWS)
    out = dataclasses.replace(
        engine,
        item_table_q=table, item_sigs=sigs,
        item_mask=jnp.asarray(alive),
        # fresh epoch, fresh summary: cold-built over the materialized
        # table + alive mask (the rebuild_reference summary by definition)
        block_summary=build_block_summary(np.asarray(sigs), br,
                                          db_mask=alive),
        delta=empty_delta(engine.delta.capacity, d, words),
        nns_mesh=None, nns_axis=None, nns_query_axis=None)
    if engine.nns_mesh is not None and (engine.nns_axis is not None
                                        or engine.nns_query_axis is not None):
        out = out.shard(engine.nns_mesh, engine.nns_axis,
                        query_axis=engine.nns_query_axis)
    return out


def rebuild_reference(engine):
    """A from-scratch frozen engine over the live engine's final table.

    The bit-match oracle: base/sigs/mask come from `materialize` (never
    from the incremental delta path), the delta is empty, and the hot
    cache pins exactly the live cache's surviving hot set — so `serve`
    on the reference must equal `serve` on the live engine bit for bit,
    counters included. Always unsharded (execution plans are separately
    proven result-invariant).
    """
    table, sigs, alive = materialize(engine)
    d, words = table.shape[1], sigs.shape[1]
    cap = engine.item_hot.capacity
    if cap:
        hot = np.asarray(engine.item_hot.hot_ids)
        item_hot = pin_rows(table, hot[hot != EMPTY_ID], cap)
    else:
        item_hot = engine.item_hot
    capacity = engine.delta.capacity if engine.delta is not None else 0
    br = (engine.block_summary.block_rows if engine.block_summary is not None
          else SUMMARY_BLOCK_ROWS)
    return dataclasses.replace(
        engine,
        item_table_q=table, item_sigs=sigs, item_mask=jnp.asarray(alive),
        block_summary=build_block_summary(np.asarray(sigs), br,
                                          db_mask=alive),
        item_hot=item_hot, delta=empty_delta(capacity, d, words),
        nns_mesh=None, nns_axis=None, nns_query_axis=None)


def repin_hot_from_freqs(engine, freqs):
    """Refill the item hot cache from measured lookup frequencies.

    Pins the `capacity` most-looked-up alive base rows (ties broken by
    ascending id — `top_ids_by_freq`, the one tier-selection order).
    Pending delta ids are never pinned: the delta-resolution contract
    requires delta ∩ hot = ∅, and their bytes live in the shard, not the
    base table. Called after `compact()` (delta empty, every surviving row
    in the new base) this restores hit rates that churn eviction decayed —
    the previously open hot-cache-repinning item. Serving results are
    unchanged by construction (the cache is bit-transparent); only the
    hit counters move.
    """
    cache = engine.item_hot
    if cache is None or not cache.capacity:
        return engine
    n = int(engine.item_table_q.shape[0])
    f = np.zeros((n,), np.int64)
    m = min(len(freqs), n)
    f[:m] = np.asarray(freqs)[:m]
    alive = (np.ones((n,), bool) if engine.item_mask is None
             else np.asarray(engine.item_mask)[:n].copy())
    if engine.delta is not None:
        dids = np.asarray(engine.delta.ids)
        dids = dids[dids != EMPTY_ID]
        alive[dids[dids < n]] = False
    ids = top_ids_by_freq(f, cache.capacity, eligible=alive)
    return dataclasses.replace(
        engine, item_hot=pin_rows(engine.item_table_q, ids, cache.capacity))


# ---------------------------------------------------------------------------
# the subsystem front door
# ---------------------------------------------------------------------------
class LiveCatalog:
    """Versioned item catalog over a serving engine.

    Wraps a `RecSysEngine` with the mutable-catalog lifecycle: bounded
    delta ingestion (`upsert` / `delete`), epoch compaction (`compact`,
    auto-forced when the delta fills), atomic engine publication to
    attached servers (`attach` — in-flight `AsyncServer` buckets finish on
    the epoch they were dispatched against), and epoch-numbered
    snapshot/restore through the fault-tolerant checkpointer.

    The engine exposed by `.engine` is always safe to serve: updates and
    compactions build a *new* engine value and swap it in; nothing an
    already-dispatched bucket references is ever mutated.
    """

    def __init__(self, engine, *, delta_capacity: int = 1024,
                 auto_compact: bool = True, registry=None):
        self.engine = ensure_live(engine, delta_capacity)
        self.epoch = 0
        self.auto_compact = auto_compact
        self.n_upserts = 0
        self.n_deletes = 0
        self.n_compactions = 0
        self.last_compact_s = 0.0
        self._servers: list = []
        # measured per-row lookup frequencies (serve-path observations):
        # grown on demand past the base size as new ids are upserted
        self.item_freqs = np.zeros(
            (int(self.engine.item_table_q.shape[0]),), np.int64)
        self.n_observed = 0
        # telemetry sink (repro.obs.MetricsRegistry); when None, `attach`
        # adopts the first attached server's registry so one snapshot
        # covers serving + catalog
        self.registry = None
        if registry is not None:
            self._set_registry(registry)

    def _set_registry(self, registry) -> None:
        if self.registry is None and registry is not None:
            self.registry = registry
            registry.register_collector(self._collect)

    def _collect(self, reg) -> None:
        """Snapshot-time collector: catalog lifecycle counters + the
        delta-overlay occupancy, as `catalog.*` gauges."""
        reg.gauge("catalog.epoch", self.epoch)
        reg.gauge("catalog.upserts", self.n_upserts)
        reg.gauge("catalog.deletes", self.n_deletes)
        reg.gauge("catalog.compactions", self.n_compactions)
        reg.gauge("catalog.delta_pending", self.n_pending)
        reg.gauge("catalog.delta_capacity", self.delta_capacity)
        reg.gauge("catalog.observed_lookups", self.n_observed)
        reg.gauge("catalog.last_compact_s", self.last_compact_s)

    # -- publication ---------------------------------------------------
    def attach(self, server) -> None:
        """Publish every future epoch/update swap to `server`
        (a `MicroBatcher` / `AsyncServer`). Servers exposing an `observer`
        hook also feed this catalog's per-row lookup-frequency counters
        (every valid item id a served batch looked up — history rows and
        served candidates alike), which `compact()` uses to repin the hot
        cache. A catalog built without a registry adopts the first
        attached server's, so its `catalog.*` gauges and compaction
        events ride the server's `snapshot()`."""
        self._servers.append(server)
        if hasattr(server, "observer"):
            server.observer = self.observe
        self._set_registry(getattr(server, "registry", None))
        server.swap_engine(self.engine)

    # -- frequency observation -----------------------------------------
    def observe(self, ids) -> None:
        """Count serve-path item lookups: `ids` is any int array of item
        ids; negative (padding) and sentinel ids are ignored. Purely a
        host-side counter — serving results never depend on it."""
        ids = np.asarray(ids).reshape(-1)
        ids = ids[(ids >= 0) & (ids < EMPTY_ID)]
        if not ids.size:
            return
        hi = int(ids.max()) + 1
        if hi > self.item_freqs.shape[0]:
            grown = np.zeros((hi,), np.int64)
            grown[: self.item_freqs.shape[0]] = self.item_freqs
            self.item_freqs = grown
        np.add.at(self.item_freqs, ids, 1)
        self.n_observed += int(ids.size)

    def _publish(self) -> None:
        if self.registry is not None:
            self.registry.event("publish", epoch=self.epoch,
                                delta_pending=self.n_pending)
        for server in self._servers:
            server.swap_engine(self.engine)

    # -- mutation ------------------------------------------------------
    def apply_updates(self, upsert_ids=None, upsert_rows=None,
                      delete_ids=None) -> None:
        """Apply one update batch; forces a compaction when the delta is
        full (unless `auto_compact=False`, which re-raises
        `DeltaFullError`)."""
        try:
            engine = engine_apply_updates(self.engine, upsert_ids,
                                          upsert_rows, delete_ids)
        except DeltaFullError:
            if not self.auto_compact:
                raise
            self.compact()
            engine = engine_apply_updates(self.engine, upsert_ids,
                                          upsert_rows, delete_ids)
        self.engine = engine
        if upsert_ids is not None:
            self.n_upserts += len(np.asarray(upsert_ids).reshape(-1))
        if delete_ids is not None:
            self.n_deletes += len(np.asarray(delete_ids).reshape(-1))
        self._publish()

    def upsert(self, ids, rows) -> None:
        """Add or re-embed items: (m,) ids + (m, d) f32 embeddings."""
        self.apply_updates(upsert_ids=ids, upsert_rows=rows)

    def delete(self, ids) -> None:
        """Retire items: tombstoned out of retrieval immediately."""
        self.apply_updates(delete_ids=ids)

    def refresh_model(self, params) -> None:
        """Publish the current model parameters (MLPs, UIETs, genre table)
        to every attached server — the dense-parameter half of online
        learning (`engine_refresh_model`); item-embedding updates take the
        `upsert` path instead. Atomic like every other publication: a new
        engine value swaps in between drain chunks."""
        self.engine = engine_refresh_model(self.engine, params)
        self._publish()

    def compact(self) -> float:
        """Fold the delta into a new base epoch; returns the pause in
        seconds (the fold is synchronous host work; serves issued against
        the previous epoch keep running on their own buffers)."""
        t0 = time.perf_counter()
        engine = compact_engine(self.engine)
        if self.n_observed:
            # tier migration rides the epoch fold: measured frequencies
            # refill hot slots that churn eviction emptied (delta is empty
            # here, so every surviving row is pinnable from the new base)
            engine = repin_hot_from_freqs(engine, self.item_freqs)
        jax.block_until_ready((engine.item_table_q.values, engine.item_sigs))
        self.last_compact_s = time.perf_counter() - t0
        self.engine = engine
        self.epoch += 1
        self.n_compactions += 1
        if self.registry is not None:
            self.registry.observe("catalog.compact_pause_s",
                                  self.last_compact_s)
            self.registry.event("compact", epoch=self.epoch,
                                pause_s=self.last_compact_s,
                                n_items=self.n_items)
        self._publish()
        return self.last_compact_s

    # -- introspection -------------------------------------------------
    @property
    def n_pending(self) -> int:
        """Occupied delta slots awaiting compaction."""
        return delta_n_live(self.engine.delta)

    @property
    def delta_capacity(self) -> int:
        return self.engine.delta.capacity

    @property
    def n_items(self) -> int:
        """Alive catalog size (base + delta - tombstones).

        O(n) over the mask, no materialization: the base-alive and
        live-delta id sets are disjoint (overwritten base rows are
        tombstoned), so the counts simply add.
        """
        n_base = int(self.engine.item_table_q.shape[0])
        alive = int(np.asarray(self.engine.item_mask)[:n_base].sum())
        return alive + delta_n_live(self.engine.delta)

    def rebuild_reference(self):
        """Frozen from-scratch engine over the current final table (the
        bit-match oracle for tests and benchmarks)."""
        return rebuild_reference(self.engine)

    # -- persistence ---------------------------------------------------
    def snapshot(self, directory) -> None:
        """Atomic epoch-numbered snapshot of the full engine pytree
        (base epoch + delta shard + tombstones + hot caches), via the
        fault-tolerant checkpointer (`checkpoint/checkpointer.py`)."""
        from repro.checkpoint import checkpointer

        checkpointer.save(directory, self.epoch, self.engine)

    def restore(self, directory) -> None:
        """Restore the latest committed epoch snapshot into this catalog
        (the current engine is the structural template: same table/delta
        shapes). Published to attached servers."""
        from repro.checkpoint import checkpointer

        step = checkpointer.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed snapshot in {directory}")
        self.engine = checkpointer.restore(directory, step, self.engine)
        self.epoch = step
        self._publish()
