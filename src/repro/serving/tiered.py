"""Frequency-tiered out-of-core catalog: disk -> int8 RAM pool -> f32 hot.

iMARS keeps every embedding-table row resident in the CMA fabric; at
100M-item scale one host cannot. RecFlash's answer — and this module's —
is a residency *hierarchy* driven by measured lookup frequency (RecNMP:
production embedding traffic is heavily skewed):

  * **cold base shard** (`BaseShard`) — the full int8 catalog (values,
    scales, LSH signatures) in memory-mapped files. Nothing is resident
    until touched; the streaming NNS reaches it through
    `core.nns.out_of_core_nns`, which gathers only summary blocks at
    least one query admits, so scan residency tracks the admitted working
    set, not the catalog;
  * **int8 pool** — a pure byte-cache of the hottest P rows, RAM-resident
    so popular history/candidate lookups never fault a disk page. Pool
    bytes are verbatim copies of shard bytes, so the tier can never
    change a served bit;
  * **f32 hot cache** — the existing `HotRowCache` over the hottest
    H <= P rows (hot is a prefix of the pool by construction, so every
    hot lookup is also pool-resident);
  * a bounded **delta shard** (`serving/catalog.py` semantics, verbatim)
    holds pending upserts; touched ids are evicted from BOTH caches the
    moment they change, keeping `delta ∩ hot = ∅` and the pool honest.

Byte resolution per served id: delta > pool > disk. The host gathers,
per batch, the bytes every requested id resolves to into a sorted
*overlay* — a `DeltaShard` value — and runs the engine's own stage
programs (`recsys_engine.lookup_step` / `rank_stage_step`) with that
overlay as the engine's delta, so `hot_cache.cached_rows` resolves each
row exactly as it does for the all-RAM engine: results AND `CacheStats`
counters bit-match the all-RAM engine over the same state (tested
against `to_ram_engine()` / `rebuild_reference()`). Only the filtering
scan stays host-driven (`out_of_core_nns` over the memmap, the delta
scan, and their merge).

Promotion/demotion (`rebalance`) recomputes the pool and hot tiers from
the measured `item_freqs` counters — frequency descending, ties by
ascending id (`hot_cache.top_ids_by_freq`, the one tier-selection order)
— and rides epoch compaction (`compact()`), which streams base + delta
into a fresh shard epoch exactly like `catalog.materialize` (same
canonical zero-row quantization for id gaps, same scatter), then
migrates tiers against the new epoch.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lsh import lsh_signature
from repro.core.nns import (
    EMPTY_ID,
    SUMMARY_BLOCK_ROWS,
    BlockSummary,
    build_block_summary,
    delta_scan,
    merge_delta_candidates,
    out_of_core_nns,
    update_block_summary,
)
from repro.core.quantization import (
    QuantizedTensor,
    dequantize_rowwise,
    quantize_rowwise,
)
from repro.kernels.ops import madvise_dontneed, madvise_random
from repro.serving import recsys_engine as rse
from repro.serving.catalog import (
    DeltaFullError,
    DeltaShard,
    empty_delta,
    delta_n_live,
    quantize_updates,
)
from repro.serving.hot_cache import (
    CacheStats,
    HotRowCache,
    invalidate_rows,
    top_ids_by_freq,
)

_META = "meta.json"
_FILES = {"values": ("values.int8.bin", np.int8),
          "scales": ("scales.f32.bin", np.float32),
          "sigs": ("sigs.u32.bin", np.uint32)}


# ---------------------------------------------------------------------------
# cold base shard: memmapped (values, scales, sigs) + sidecar state
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BaseShard:
    """One read-only on-disk catalog epoch, opened as memmaps.

    `values` (n, d) int8 / `scales` (n, 1) f32 / `sigs` (n, words) uint32
    are `np.memmap`s — indexing them faults in only the touched pages.
    The shard is immutable once written; mutation happens in the delta
    shard and lands in a NEW epoch directory at compaction.
    """

    directory: str
    n: int
    d: int
    words: int
    values: np.memmap
    scales: np.memmap
    sigs: np.memmap


class BaseShardWriter:
    """Chunked writer for a `BaseShard` epoch directory.

    `write(lo, values, scales, sigs)` scatters one row-chunk;
    `finish(alive=..., summary=...)` persists the sidecars (alive mask,
    precomputed block summary — computed at write time so opening the
    shard never has to fault in every signature page) and the meta file.
    """

    def __init__(self, directory: str, n: int, d: int, words: int):
        os.makedirs(directory, exist_ok=True)
        self.directory, self.n, self.d, self.words = directory, n, d, words
        shapes = {"values": (n, d), "scales": (n, 1), "sigs": (n, words)}
        self._maps = {
            key: np.memmap(os.path.join(directory, fname), dtype=dtype,
                           mode="w+", shape=shapes[key])
            for key, (fname, dtype) in _FILES.items()}

    def write(self, lo: int, values, scales, sigs) -> None:
        hi = lo + len(values)
        self._maps["values"][lo:hi] = np.asarray(values, np.int8)
        self._maps["scales"][lo:hi] = np.asarray(
            scales, np.float32).reshape(-1, 1)
        self._maps["sigs"][lo:hi] = np.asarray(sigs, np.uint32)

    def finish(self, alive=None, summary: BlockSummary | None = None) -> None:
        for m in self._maps.values():
            m.flush()
        if alive is None:
            alive = np.ones((self.n,), bool)
        np.save(os.path.join(self.directory, "alive.npy"),
                np.asarray(alive, bool))
        if summary is not None:
            np.savez(os.path.join(self.directory, "summary.npz"),
                     or_sigs=np.asarray(summary.or_sigs),
                     and_sigs=np.asarray(summary.and_sigs),
                     min_pc=np.asarray(summary.min_pc),
                     max_pc=np.asarray(summary.max_pc),
                     n_alive=np.asarray(summary.n_alive),
                     block_rows=np.int64(summary.block_rows))
        meta = {"n": self.n, "d": self.d, "words": self.words, "version": 1}
        with open(os.path.join(self.directory, _META), "w") as f:
            json.dump(meta, f)
        self._maps = {}


def write_base_shard(directory: str, values, scales, sigs, *, alive=None,
                     summary: BlockSummary | None = None) -> None:
    """One-shot shard write (small catalogs / tests); the 8M+ benchmark
    path streams chunks through `BaseShardWriter` instead."""
    values = np.asarray(values)
    w = BaseShardWriter(directory, values.shape[0], values.shape[1],
                        np.asarray(sigs).shape[1])
    w.write(0, values, scales, sigs)
    w.finish(alive=alive, summary=summary)


def pread_rows(mm: np.memmap, ids) -> np.ndarray:
    """Scattered row gather from a memmap via `os.pread`, not the mapping.

    `mm[ids]` on scattered ids is an RSS trap: each 4KB fault maps its
    fault-around window (up to 64KB of neighbour pages whenever they are
    in the global page cache — fault-around ignores MADV_RANDOM), so a
    few thousand candidate-row faults can pin hundreds of MB. pread
    copies exactly the requested bytes into an anonymous buffer and maps
    nothing. Duplicate ids are read once. Falls back to the mapping for
    non-file-backed arrays.
    """
    ids = np.asarray(ids, np.int64).reshape(-1)
    fname = getattr(mm, "filename", None)
    if fname is None:
        return np.asarray(mm[ids])
    uniq, inv = np.unique(ids, return_inverse=True)
    row = int(np.prod(mm.shape[1:], dtype=np.int64)) * mm.dtype.itemsize
    base = int(getattr(mm, "offset", 0))
    out = np.empty((uniq.size,) + mm.shape[1:], mm.dtype)
    flat = out.reshape(uniq.size, -1).view(np.uint8)
    fd = os.open(fname, os.O_RDONLY)
    try:
        for i, r in enumerate(uniq):
            flat[i] = np.frombuffer(
                os.pread(fd, row, base + int(r) * row), np.uint8)
    finally:
        os.close(fd)
    return out[inv]


def open_base_shard(directory: str):
    """-> (BaseShard, alive (n,) bool ndarray, BlockSummary | None).

    The memmaps open read-only; `alive` loads fully (1 byte/row — the one
    O(n) RAM sidecar) and the summary, if the writer persisted one, loads
    without touching a single signature page.
    """
    with open(os.path.join(directory, _META)) as f:
        meta = json.load(f)
    n, d, words = meta["n"], meta["d"], meta["words"]
    shapes = {"values": (n, d), "scales": (n, 1), "sigs": (n, words)}
    maps = {key: np.memmap(os.path.join(directory, fname), dtype=dtype,
                           mode="r", shape=shapes[key])
            for key, (fname, dtype) in _FILES.items()}
    for m in maps.values():
        # scattered row faults must not drag in 128KB of readahead each
        madvise_random(m)
    shard = BaseShard(directory=directory, n=n, d=d, words=words, **maps)
    alive = np.load(os.path.join(directory, "alive.npy"))
    summary = None
    spath = os.path.join(directory, "summary.npz")
    if os.path.exists(spath):
        z = np.load(spath)
        summary = BlockSummary(
            or_sigs=jnp.asarray(z["or_sigs"]),
            and_sigs=jnp.asarray(z["and_sigs"]),
            min_pc=jnp.asarray(z["min_pc"]),
            max_pc=jnp.asarray(z["max_pc"]),
            n_alive=jnp.asarray(z["n_alive"]),
            block_rows=int(z["block_rows"]))
    return shard, alive, summary


_sign_jit = jax.jit(lsh_signature)
_delta_scan_jit = jax.jit(delta_scan, static_argnums=(3, 4))
_merge_jit = jax.jit(merge_delta_candidates, static_argnums=(2,))


# ---------------------------------------------------------------------------
# the tiered catalog front door
# ---------------------------------------------------------------------------
class TieredCatalog:
    """Host-driven tiered serving over a memmapped base shard.

    Holds the cold `BaseShard`, the int8 pool + f32 hot tiers, the bounded
    delta shard, the block summary, the alive mask, and measured per-row
    lookup frequencies. `serve()` runs the three-stage pipeline described
    in the module docstring and bit-matches `to_ram_engine().serve()` —
    the all-RAM engine over identical state — results and counters alike.

    `inner` is a `RecSysEngine` whose USER-side leaves (UIET tables, MLP
    params, genre table, LSH projections, hot caches) are real and whose
    item table/signatures are 1-row placeholders — item bytes live on
    disk, in the pool, or in the delta, never as an engine leaf.
    """

    def __init__(self, directory: str, shard: BaseShard, inner, *,
                 alive, summary, pool_rows: int, item_freqs=None,
                 delta_capacity: int = 1024, auto_compact: bool = True,
                 registry=None):
        if inner.nns_mesh is not None:
            raise ValueError("TieredCatalog serving is host-driven; "
                             "use an unsharded engine")
        self.directory = directory
        self.base = shard
        self.alive = np.asarray(alive, bool).copy()
        self.summary = summary
        self.auto_compact = auto_compact
        self.epoch = 0
        n = shard.n
        # A matching (n,) int64 array is ADOPTED (observe() mutates it in
        # place) — at 100M-scale a defensive copy is another 800MB of
        # residency for nothing; callers wanting isolation pass a copy.
        freqs_in = None if item_freqs is None else np.asarray(item_freqs)
        if (freqs_in is not None and freqs_in.shape == (n,)
                and freqs_in.dtype == np.int64 and freqs_in.flags.writeable):
            self.item_freqs = freqs_in
        else:
            self.item_freqs = np.zeros((n,), np.int64)
            if freqs_in is not None:
                m = min(len(freqs_in), n)
                self.item_freqs[:m] = freqs_in[:m]
        self.n_observed = int(self.item_freqs.sum())
        self.delta = empty_delta(delta_capacity, shard.d, shard.words)
        # tiers: pool = top-P by measured frequency, hot = top-H prefix
        self._pool_capacity = int(pool_rows)
        hot_cap = inner.item_hot.capacity if inner.item_hot is not None \
            else 0
        if hot_cap > self._pool_capacity:
            raise ValueError(
                f"hot capacity {hot_cap} exceeds pool capacity "
                f"{self._pool_capacity}: the hot tier must be a subset "
                f"of the pool")
        self.pool_ids = np.zeros((0,), np.int32)
        self.pool_vals = np.zeros((0, shard.d), np.int8)
        self.pool_scales = np.zeros((0, 1), np.float32)
        self.inner = inner
        self.rebalance()
        # telemetry (host counters; never affect results)
        self.n_compactions = 0
        self.pool_hits = 0
        self.delta_hits = 0
        self.disk_rows = 0
        self.last_compact_s = 0.0
        # optional metrics sink (repro.obs.MetricsRegistry): tier
        # residency + hit mix ride whoever's snapshot() as tiered.* keys
        self.registry = registry
        if registry is not None:
            registry.register_collector(self._collect)

    def _collect(self, reg) -> None:
        """Snapshot-time collector: tier residency + hit-mix gauges."""
        reg.gauge("tiered.epoch", self.epoch)
        reg.gauge("tiered.compactions", self.n_compactions)
        reg.gauge("tiered.last_compact_s", self.last_compact_s)
        reg.gauge("tiered.pool_hits", self.pool_hits)
        reg.gauge("tiered.delta_hits", self.delta_hits)
        reg.gauge("tiered.disk_rows", self.disk_rows)
        reg.gauge("tiered.pool_rows", int(self.pool_ids.size))
        reg.gauge("tiered.delta_pending", self.n_pending)
        reg.gauge("tiered.resident_bytes", self.resident_bytes())

    # -- construction --------------------------------------------------
    @classmethod
    def open(cls, directory: str, engine, *, pool_rows: int = 0,
             item_freqs=None, delta_capacity: int = 1024,
             auto_compact: bool = True, registry=None) -> "TieredCatalog":
        """Open the latest shard epoch under `directory` and serve it.

        `engine` supplies the user-side model state (params, UIETs, knobs,
        hot-cache capacity); its item table/sigs leaves are discarded for
        1-row placeholders — at 100M-scale the caller builds it over a
        tiny placeholder item table and never materializes the real one.
        """
        epochs = sorted((e for e in os.listdir(directory)
                         if e.startswith("epoch_")),
                        key=lambda e: int(e.split("_")[1]))
        if not epochs:
            raise FileNotFoundError(f"no epoch_* shard under {directory}")
        shard, alive, summary = open_base_shard(
            os.path.join(directory, epochs[-1]))
        if summary is None:
            summary = build_block_summary(
                np.asarray(shard.sigs), SUMMARY_BLOCK_ROWS, db_mask=alive)
        hot_cap = engine.item_hot.capacity if engine.item_hot is not None \
            else 0
        inner = dataclasses.replace(
            engine,
            item_table_q=QuantizedTensor(
                values=jnp.zeros((1, shard.d), jnp.int8),
                scales=jnp.zeros((1, 1), jnp.float32)),
            item_sigs=jnp.zeros((1, shard.words), jnp.uint32),
            item_hot=HotRowCache(hot_ids=jnp.full((hot_cap,), EMPTY_ID,
                                                  jnp.int32),
                                 hot_rows=jnp.zeros((hot_cap, shard.d),
                                                    jnp.float32),
                                 capacity=hot_cap)
            if hot_cap else engine.item_hot,
            item_mask=None, delta=None, block_summary=None)
        cat = cls(directory, shard, inner, alive=alive, summary=summary,
                  pool_rows=pool_rows, item_freqs=item_freqs,
                  delta_capacity=delta_capacity, auto_compact=auto_compact,
                  registry=registry)
        cat.epoch = int(epochs[-1].split("_")[1])
        return cat

    @classmethod
    def from_engine(cls, engine, directory: str, *, pool_rows: int = 0,
                    item_freqs=None, delta_capacity: int = 1024,
                    auto_compact: bool = True, registry=None
                    ) -> "TieredCatalog":
        """Spill an all-RAM engine's item table to an epoch-0 shard and
        serve it tiered (the small-catalog / test construction path)."""
        sigs = np.asarray(engine.item_sigs)
        n = int(engine.item_table_q.values.shape[0])
        alive = (np.ones((n,), bool) if engine.item_mask is None
                 else np.asarray(engine.item_mask)[:n])
        summary = build_block_summary(sigs[:n], SUMMARY_BLOCK_ROWS,
                                      db_mask=alive)
        write_base_shard(
            os.path.join(directory, "epoch_0"),
            np.asarray(engine.item_table_q.values)[:n],
            np.asarray(engine.item_table_q.scales)[:n], sigs[:n],
            alive=alive, summary=summary)
        return cls.open(directory, engine, pool_rows=pool_rows,
                        item_freqs=item_freqs, delta_capacity=delta_capacity,
                        auto_compact=auto_compact, registry=registry)

    # -- tier mechanics ------------------------------------------------
    def _resolve_bytes(self, ids: np.ndarray, *, use_delta: bool = True):
        """Host resolution of `ids` -> (present, vals, scales) through
        delta > pool > disk. Tombstoned base ids still resolve to their
        (stale) base bytes — as `hot_cache.cached_rows`, which ignores
        the alive mask, does on the all-RAM engine; retrieval correctness rests on
        the NNS mask, not the row gather."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        m = ids.size
        vals = np.zeros((m, self.base.d), np.int8)
        scales = np.zeros((m, 1), np.float32)
        valid = ids >= 0
        safe = np.maximum(ids, 0)
        in_delta = np.zeros(m, bool)
        dids = np.asarray(self.delta.ids)
        if use_delta and dids.size:
            pos = np.clip(np.searchsorted(dids, safe), 0, dids.size - 1)
            in_delta = valid & (dids[pos] == ids)
            if in_delta.any():
                dvals = np.asarray(self.delta.values)
                dscales = np.asarray(self.delta.scales)
                vals[in_delta] = dvals[pos[in_delta]]
                scales[in_delta] = dscales[pos[in_delta]]
        in_pool = np.zeros(m, bool)
        if self.pool_ids.size:
            ppos = np.clip(np.searchsorted(self.pool_ids, safe), 0,
                           self.pool_ids.size - 1)
            in_pool = valid & ~in_delta & (self.pool_ids[ppos] == ids)
            if in_pool.any():
                vals[in_pool] = self.pool_vals[ppos[in_pool]]
                scales[in_pool] = self.pool_scales[ppos[in_pool]]
        in_disk = valid & ~in_delta & ~in_pool & (ids < self.base.n)
        if in_disk.any():
            didx = ids[in_disk]
            vals[in_disk] = pread_rows(self.base.values, didx)
            scales[in_disk] = pread_rows(self.base.scales, didx)
        self.delta_hits += int(in_delta.sum())
        self.pool_hits += int(in_pool.sum())
        self.disk_rows += int(in_disk.sum())
        return (in_delta | in_pool | in_disk), vals, scales

    def _build_overlay(self, ids) -> DeltaShard:
        """ids (any int shape) -> the sorted byte overlay those ids resolve
        to, as a `DeltaShard` (ids absent from the catalog hold `EMPTY_ID`
        and read zero rows). Its `sigs` is the inner engine's 1-row
        placeholder: the lookup and rank never read it. Fixed size
        (= ids.size) per bucket shape, so the stage programs compile once.
        """
        flat = np.asarray(ids, np.int64).reshape(-1)
        present, vals, scales = self._resolve_bytes(flat)
        ov_ids = np.where(present, flat, np.int64(EMPTY_ID)).astype(np.int32)
        order = np.argsort(ov_ids, kind="stable")
        return DeltaShard(ids=jnp.asarray(ov_ids[order]),
                          values=jnp.asarray(vals[order]),
                          scales=jnp.asarray(scales[order]),
                          sigs=self.inner.item_sigs, capacity=flat.size)

    def rebalance(self) -> None:
        """Recompute pool + hot membership from `item_freqs`.

        Promotion and demotion in one move: pool = top-P alive base rows
        by (frequency desc, id asc), hot = the top-H prefix of that same
        ranking (hot ⊆ pool — every f32-pinned row is also byte-resident).
        Pending delta ids never pin (delta ∩ hot = ∅ is the resolution
        contract) and tombstoned rows are ineligible. Pure residency
        movement: pinned bytes are verbatim shard bytes and the hot rows
        their exact dequantization, so serving results cannot change —
        only the hit counters and the resident set do.
        """
        eligible = self.alive.copy()
        dids = np.asarray(self.delta.ids)
        dids = dids[dids != EMPTY_ID]
        eligible[dids[dids < self.base.n]] = False
        ranked = top_ids_by_freq(self.item_freqs[: self.base.n],
                                 self._pool_capacity, eligible=eligible)
        order = np.argsort(ranked, kind="stable")
        self.pool_ids = ranked[order].astype(np.int32)
        self.pool_vals = pread_rows(self.base.values, self.pool_ids)
        self.pool_scales = pread_rows(self.base.scales, self.pool_ids)
        cache = self.inner.item_hot
        if cache is not None and cache.capacity:
            hot = np.sort(ranked[: cache.capacity]).astype(np.int32)
            hot_ids = np.full((cache.capacity,), EMPTY_ID, np.int32)
            hot_ids[: hot.size] = hot
            rows = np.zeros((cache.capacity, self.base.d), np.float32)
            if hot.size:
                hpos = np.searchsorted(self.pool_ids, hot)
                rows[: hot.size] = np.asarray(dequantize_rowwise(
                    QuantizedTensor(
                        values=jnp.asarray(self.pool_vals[hpos]),
                        scales=jnp.asarray(self.pool_scales[hpos]))))
            self.inner = dataclasses.replace(
                self.inner, item_hot=HotRowCache(
                    hot_ids=jnp.asarray(hot_ids), hot_rows=jnp.asarray(rows),
                    capacity=cache.capacity))

    def observe(self, ids) -> None:
        """Count serve-path lookups (`LiveCatalog.observe` semantics)."""
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

    # -- serving -------------------------------------------------------
    def serve(self, batch: dict) -> rse.ServeResult:
        """Serve one padded batch (the `RecSysEngine.serve` schema) from
        the tiered store; bit-matches `to_ram_engine().serve(batch)`.

        The lookup and rank are the engine's own stage programs over
        `inner` with a per-batch overlay as its delta: the history ids'
        bytes for the lookup, the candidates' for the rank."""
        hist_np = np.asarray(batch["history"])
        batch_j = {k: jnp.asarray(v) for k, v in batch.items()}
        eng = dataclasses.replace(self.inner,
                                  delta=self._build_overlay(hist_np))
        u, pooled, stats = rse.lookup_step(eng, batch_j, CacheStats.zero())
        q_sigs = _sign_jit(u, self.inner.lsh_proj)
        base = out_of_core_nns(
            q_sigs, self.base.sigs, self.inner.radius,
            self.inner.n_candidates, db_mask=self.alive,
            scan_block=self.inner.scan_block, summary=self.summary)
        pending = _delta_scan_jit(q_sigs, self.delta.sigs, self.delta.ids,
                                  self.inner.radius, self.inner.n_candidates)
        nns = _merge_jit(base, pending, self.inner.n_candidates)
        cand_np = np.asarray(nns.indices)
        eng = dataclasses.replace(self.inner,
                                  delta=self._build_overlay(cand_np))
        final, top, stats = rse.rank_stage_step(
            eng, batch_j, nns.indices, u, pooled, stats)
        final_np = np.asarray(final)
        self.observe(np.concatenate(
            [hist_np.reshape(-1).astype(np.int64),
             final_np.reshape(-1).astype(np.int64)]))
        # the RAM tiers ARE the cache: base pages faulted for this batch's
        # cold rows (overlay byte resolution; the NNS drops its own) are
        # copied out already, so evict them — resident stays O(batch
        # working set), never O(every page ever touched)
        for m in (self.base.values, self.base.scales):
            madvise_dontneed(m)
        return rse.ServeResult(items=final, topk=top, nns=nns,
                               cost=self.inner.query_cost(), stats=stats)

    # -- mutation ------------------------------------------------------
    def apply_updates(self, upsert_ids=None, upsert_rows=None,
                      delete_ids=None) -> None:
        """`catalog.engine_apply_updates` semantics against the tiered
        state: updates fold into the sorted delta shard, touched base rows
        tombstone + leave BOTH caches (pool and hot — their bytes are
        stale the moment the row changes), and the block summary's touched
        blocks recompute exactly. Forces a compaction when the delta is
        full (unless `auto_compact=False`)."""
        try:
            self._apply_updates(upsert_ids, upsert_rows, delete_ids)
        except DeltaFullError:
            if not self.auto_compact:
                raise
            self.compact()
            self._apply_updates(upsert_ids, upsert_rows, delete_ids)

    def upsert(self, ids, rows) -> None:
        self.apply_updates(upsert_ids=ids, upsert_rows=rows)

    def delete(self, ids) -> None:
        self.apply_updates(delete_ids=ids)

    def _apply_updates(self, upsert_ids, upsert_rows, delete_ids) -> None:
        delta, n_base = self.delta, self.base.n
        live: dict[int, tuple] = {}
        ids_np = np.asarray(delta.ids)
        vals_np, scales_np, sigs_np = (np.asarray(delta.values),
                                       np.asarray(delta.scales),
                                       np.asarray(delta.sigs))
        for slot in np.nonzero(ids_np != EMPTY_ID)[0]:
            live[int(ids_np[slot])] = (vals_np[slot], scales_np[slot],
                                       sigs_np[slot])
        touched: list[int] = []
        mask = self.alive
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
            uvals, uscales, usigs = quantize_updates(self.inner, upsert_rows)
            if len(ids_arr) != len(uvals):
                raise ValueError(f"{len(ids_arr)} ids vs {len(uvals)} rows")
            for i, gid in enumerate(ids_arr):
                gid = int(gid)
                live[gid] = (uvals[i], uscales[i], usigs[i])
                if gid < n_base:
                    mask[gid] = False
                touched.append(gid)
        if len(live) > delta.capacity:
            raise DeltaFullError(
                f"{len(live)} pending rows > delta capacity {delta.capacity}")

        base_touched = [g for g in touched if g < n_base]
        if base_touched:
            self.summary = update_block_summary(
                self.summary, np.asarray(self.base.sigs), mask, base_touched)

        ids_out = np.full(delta.capacity, EMPTY_ID, np.int32)
        vals_out = np.zeros((delta.capacity, self.base.d), np.int8)
        scales_out = np.zeros((delta.capacity, 1), np.float32)
        sigs_out = np.zeros((delta.capacity, self.base.words), np.uint32)
        for slot, gid in enumerate(sorted(live)):
            v, s, g = live[gid]
            ids_out[slot], vals_out[slot] = gid, v
            scales_out[slot], sigs_out[slot] = s, g
        self.delta = DeltaShard(ids=jnp.asarray(ids_out),
                                values=jnp.asarray(vals_out),
                                scales=jnp.asarray(scales_out),
                                sigs=jnp.asarray(sigs_out),
                                capacity=delta.capacity)
        if touched:
            t = np.asarray(touched)
            # evict stale bytes from both RAM tiers (delta ∩ {hot, pool} = ∅)
            self.inner = dataclasses.replace(
                self.inner,
                item_hot=invalidate_rows(self.inner.item_hot, t))
            keep = ~np.isin(self.pool_ids, t)
            if not keep.all():
                self.pool_ids = self.pool_ids[keep]
                self.pool_vals = self.pool_vals[keep]
                self.pool_scales = self.pool_scales[keep]

    # -- compaction + migration ----------------------------------------
    def compact(self, chunk_rows: int = 1 << 18) -> None:
        """Stream base + delta into a fresh shard epoch, then migrate
        tiers against it.

        The fold is `catalog.materialize` row for row — base bytes copy
        verbatim, delta rows scatter in, id-space gaps get the canonical
        zero-row quantization and stay dead — executed as a chunked
        stream (O(chunk) resident, never the table). The new epoch gets a
        cold-built summary, the delta empties, and `rebalance()` promotes
        /demotes pool + hot membership from the measured frequencies —
        tier migration riding the epoch fold.
        """
        t0 = time.perf_counter()
        n_base, d, words = self.base.n, self.base.d, self.base.words
        dids_np = np.asarray(self.delta.ids)
        live = np.nonzero(dids_np != EMPTY_ID)[0]
        gids = dids_np[live].astype(np.int64)
        n_total = int(max(n_base, (gids.max() + 1) if len(gids) else 0))
        zero_q = quantize_rowwise(jnp.zeros((1, d), jnp.float32))
        zero_sig = np.asarray(
            lsh_signature(dequantize_rowwise(zero_q), self.inner.lsh_proj))
        dvals = np.asarray(self.delta.values)[live]
        dscales = np.asarray(self.delta.scales)[live]
        dsigs = np.asarray(self.delta.sigs)[live]

        new_dir = os.path.join(self.directory, f"epoch_{self.epoch + 1}")
        writer = BaseShardWriter(new_dir, n_total, d, words)
        alive_new = np.zeros((n_total,), bool)
        alive_new[:n_base] = self.alive[:n_base]
        alive_new[gids] = True
        for lo in range(0, n_total, chunk_rows):
            hi = min(lo + chunk_rows, n_total)
            m = hi - lo
            if lo < n_base:  # base prefix: verbatim bytes (copied —
                # memmap slices are read-only and the delta may scatter in)
                b = min(hi, n_base) - lo
                vals = np.concatenate(
                    [self.base.values[lo:lo + b],
                     np.broadcast_to(np.asarray(zero_q.values),
                                     (m - b, d))]) if m > b else \
                    np.array(self.base.values[lo:hi])
                scales = np.concatenate(
                    [self.base.scales[lo:lo + b],
                     np.broadcast_to(np.asarray(zero_q.scales),
                                     (m - b, 1))]) if m > b else \
                    np.array(self.base.scales[lo:hi])
                sigs = np.concatenate(
                    [self.base.sigs[lo:lo + b],
                     np.broadcast_to(zero_sig, (m - b, words))]) if m > b \
                    else np.array(self.base.sigs[lo:hi])
            else:  # gap region: canonical zero rows
                vals = np.broadcast_to(np.asarray(zero_q.values),
                                       (m, d)).copy()
                scales = np.broadcast_to(np.asarray(zero_q.scales),
                                         (m, 1)).copy()
                sigs = np.broadcast_to(zero_sig, (m, words)).copy()
            sel = (gids >= lo) & (gids < hi)
            if sel.any():
                vals[gids[sel] - lo] = dvals[sel]
                scales[gids[sel] - lo] = dscales[sel]
                sigs[gids[sel] - lo] = dsigs[sel]
            writer.write(lo, vals, scales, sigs)
        br = self.summary.block_rows if self.summary is not None \
            else SUMMARY_BLOCK_ROWS
        writer._maps["sigs"].flush()
        summary = build_block_summary(writer._maps["sigs"], br,
                                      db_mask=alive_new)
        writer.finish(alive=alive_new, summary=summary)

        self.base = open_base_shard(new_dir)[0]
        self.alive, self.summary = alive_new, summary
        self.delta = empty_delta(self.delta.capacity, d, words)
        self.epoch += 1
        self.n_compactions += 1
        freqs = np.zeros((self.base.n,), np.int64)
        m = min(self.item_freqs.shape[0], self.base.n)
        freqs[:m] = self.item_freqs[:m]
        self.item_freqs = freqs
        self.rebalance()
        self.last_compact_s = time.perf_counter() - t0
        if self.registry is not None:
            self.registry.observe("tiered.compact_pause_s",
                                  self.last_compact_s)
            self.registry.event("compact", epoch=self.epoch,
                                pause_s=self.last_compact_s,
                                n_items=self.n_items,
                                pool_rows=int(self.pool_ids.size))

    # -- persistence ---------------------------------------------------
    def _sidecar_state(self) -> dict:
        """The mutable state the epoch shard does NOT hold: the pending
        delta shard, post-epoch tombstones, and the measured frequency
        counters. (The base shard is already durable as ``epoch_N/``
        files; pool and hot membership are pure functions of the counters
        via `rebalance`, and the block summary of (sigs, alive), so both
        are re-derived at restore rather than persisted.)"""
        return {"delta": self.delta,
                "alive": self.alive,
                "item_freqs": self.item_freqs,
                "n_observed": np.int64(self.n_observed)}

    def snapshot(self, directory) -> None:
        """Epoch-numbered snapshot of the sidecar state through the
        fault-tolerant checkpointer (`checkpoint/checkpointer.py`):
        pending delta rows + frequency counters, so a restored catalog
        resumes with the hot-set ranking it had measured — not a cold
        tier assignment that would have to re-learn the skew."""
        from repro.checkpoint import checkpointer

        checkpointer.save(directory, self.epoch, self._sidecar_state())

    def restore(self, directory) -> None:
        """Restore the latest committed sidecar snapshot into this
        catalog and re-derive the tiers from the restored counters.

        The snapshot's epoch must match the opened shard epoch (the base
        bytes it was taken against); delta shapes are the structural
        template, so `delta_capacity` must match the snapshotted one.
        Pool and hot membership recompute via `rebalance()` — the one
        tier-selection order (`top_ids_by_freq`) over bit-identical
        counters reproduces the exact pre-snapshot ranking.
        """
        from repro.checkpoint import checkpointer

        step = checkpointer.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed snapshot in {directory}")
        if step != self.epoch:
            raise ValueError(
                f"snapshot epoch {step} does not match the opened shard "
                f"epoch {self.epoch}; open the matching epoch_{step} "
                f"shard first")
        state = checkpointer.restore(directory, step,
                                     self._sidecar_state())
        self.delta = state["delta"]
        self.alive = np.asarray(state["alive"], bool).copy()
        self.item_freqs = np.asarray(state["item_freqs"], np.int64).copy()
        self.n_observed = int(state["n_observed"])
        # the summary is a pure function of (base sigs, alive): cold-build
        # it against the restored tombstones (`update_block_summary`
        # recomputes touched blocks exactly, so this bit-matches the
        # incrementally-maintained one)
        self.summary = build_block_summary(
            np.asarray(self.base.sigs), SUMMARY_BLOCK_ROWS,
            db_mask=self.alive)
        # rebalance() re-derives pool + hot from the restored counters and
        # already excludes pending delta ids, so delta ∩ caches = ∅ holds
        # for the restored pending set too
        self.rebalance()

    # -- introspection / oracles ----------------------------------------
    @property
    def n_pending(self) -> int:
        return delta_n_live(self.delta)

    @property
    def n_items(self) -> int:
        return int(self.alive.sum()) + delta_n_live(self.delta)

    def resident_bytes(self) -> int:
        """RAM bytes the item tiers pin (pool + hot + summary + alive) —
        the residency the memmapped base shard does NOT cost."""
        pool = (self.pool_vals.nbytes + self.pool_scales.nbytes
                + self.pool_ids.nbytes)
        cache = self.inner.item_hot
        hot = 0 if cache is None else int(
            np.asarray(cache.hot_rows).nbytes
            + np.asarray(cache.hot_ids).nbytes)
        summ = sum(int(np.asarray(x).nbytes) for x in
                   (self.summary.or_sigs, self.summary.and_sigs,
                    self.summary.min_pc, self.summary.max_pc,
                    self.summary.n_alive))
        return pool + hot + summ + self.alive.nbytes

    def stats(self) -> dict:
        return {"epoch": self.epoch, "n_items": self.n_items,
                "n_pending": self.n_pending,
                "n_compactions": self.n_compactions,
                "pool_rows": int(self.pool_ids.size),
                "hot_rows": 0 if self.inner.item_hot is None else
                int(self.inner.item_hot.capacity),
                "pool_hits": self.pool_hits, "delta_hits": self.delta_hits,
                "disk_rows": self.disk_rows,
                "resident_bytes": self.resident_bytes()}

    def to_ram_engine(self):
        """The all-RAM live engine over this catalog's EXACT state (base
        loaded from the shard, same delta/mask/summary/hot cache) — the
        bit-match comparator for tests and the benchmark. O(n) RAM."""
        table = QuantizedTensor(
            values=jnp.asarray(np.asarray(self.base.values)),
            scales=jnp.asarray(np.asarray(self.base.scales)))
        return dataclasses.replace(
            self.inner, item_table_q=table,
            item_sigs=jnp.asarray(np.asarray(self.base.sigs)),
            item_mask=jnp.asarray(self.alive), delta=self.delta,
            block_summary=self.summary)

    def rebuild_reference(self):
        """Frozen from-scratch oracle (`catalog.rebuild_reference`) over
        the materialized final table, pinning this catalog's surviving
        hot set — the strongest bit-match target."""
        from repro.serving.catalog import rebuild_reference

        return rebuild_reference(self.to_ram_engine())
