"""Frequency-based hot-row cache for quantized embedding tables.

RecNMP and MicroRec both observe that embedding-table traffic under real
recommendation workloads is heavily skewed: a tiny fraction of rows (popular
items, frequent users) absorbs most lookups. iMARS keeps every ET row in the
CMA fabric; the software image of the same locality win is a small cache of
the hottest rows pinned *dense in f32* next to the compute, while cold rows
take the int8 `embedding_pool` dequant-gather path.

Design contract (tested in tests/test_batched_serving.py):

  * the pinned f32 rows are bit-identical to `dequantize_rowwise` of the
    backing int8 rows, so a cached lookup / pooled bag **bit-matches** the
    uncached path — the cache is purely a bandwidth/latency optimisation and
    can never change serving results;
  * every cached op returns a `CacheStats` (hits, lookups) alongside its
    value, so engines can surface measured hit rates per served batch.

Membership is a binary search over the sorted hot-id set (`searchsorted`
plus an equality probe) — O(log K) per id, branch-free, jit-friendly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import QuantizedTensor, dequantize_rowwise
from repro.utils import pytree_dataclass

# empty-slot sentinel for invalidated hot rows: sorts after every real item
# id, so `hot_ids` stays ascending and the searchsorted probe stays valid
# (same value as core.nns.EMPTY_ID, defined locally to keep layering flat)
INVALID_ID = 2**31 - 1


class CacheStats(NamedTuple):
    hits: jax.Array  # () int32 — ids served from the hot set
    lookups: jax.Array  # () int32 — total valid (non-padding) ids

    @staticmethod
    def zero() -> "CacheStats":
        return CacheStats(hits=jnp.int32(0), lookups=jnp.int32(0))

    def __add__(self, other: "CacheStats") -> "CacheStats":
        return CacheStats(hits=self.hits + other.hits,
                          lookups=self.lookups + other.lookups)

    def hit_rate(self) -> float:
        lk = int(self.lookups)
        return float(self.hits) / lk if lk else 0.0

    def as_dict(self) -> dict:
        """Plain-int view ``{hits, lookups, hit_rate}`` for registries and
        reports (forces the one host sync on the traced scalars)."""
        hits, lk = int(self.hits), int(self.lookups)
        return {"hits": hits, "lookups": lk,
                "hit_rate": hits / lk if lk else 0.0}


@pytree_dataclass(meta_fields=("capacity",))
class HotRowCache:
    """Top-K hot rows of one int8 table, pinned dense in f32.

    `hot_ids` is sorted ascending; `hot_rows[i]` is the exact dequantized
    image of table row `hot_ids[i]`.
    """

    hot_ids: jax.Array  # (K,) int32, sorted
    hot_rows: jax.Array  # (K, d) f32
    capacity: int = 0


def top_ids_by_freq(freqs, k: int, eligible=None) -> np.ndarray:
    """Rank row ids by (frequency desc, id asc) and return the top `k`.

    The secondary ascending-id key makes frequency ties deterministic —
    `np.argpartition` tie order is implementation-defined and drifted
    across numpy versions, which made the pinned hot set (and hence the
    served cache counters) irreproducible. Every tier-selection site
    (hot cache, int8 pool, compaction repinning) goes through this one
    helper so they can never disagree on tie order.

    eligible: optional (n,) bool mask; ineligible rows are excluded even
    if fewer than `k` eligible rows exist (the result may be short).

    Runs in O(chunk) temporary memory — a full-array lexsort allocates
    several n-sized scratch arrays, which at the tiered catalog's 8M+
    row counts is hundreds of MB against a residency budget of tens.
    The chunked threshold select returns the EXACT lexsort answer: the
    k-th-largest frequency `t` is found from per-chunk top-k value
    pools, rows with freq > t (at most k of them) sort by
    (freq desc, id asc), and the remaining slots fill with the smallest
    ids at freq == t — `np.flatnonzero` per ascending chunk IS the
    ascending-id tie order.
    """
    freqs = np.asarray(freqs, np.int64)
    n = freqs.shape[0]
    k = min(int(k), n)
    if k <= 0:
        return np.zeros((0,), np.int32)
    elig = None if eligible is None else np.asarray(eligible, bool)
    chunk = 1 << 20

    def masked(lo, hi):
        c = freqs[lo:hi]
        if elig is None:
            return c
        return np.where(elig[lo:hi], c, np.int64(-1))

    pool = []  # per-chunk top-k values: the global top-k lives in here
    for lo in range(0, n, chunk):
        c = masked(lo, min(lo + chunk, n))
        m = c.shape[0]
        # .copy(): the slice would otherwise pin the whole partitioned
        # chunk (an O(chunk) array per iteration) in `pool`
        pool.append(np.partition(c, m - k)[m - k:].copy() if m > k
                    else np.array(c))
    pool = np.concatenate(pool)
    t = np.partition(pool, pool.shape[0] - k)[pool.shape[0] - k]

    gt, eq, n_eq = [], [], 0
    for lo in range(0, n, chunk):
        c = masked(lo, min(lo + chunk, n))
        gt.append(lo + np.flatnonzero(c > t))
        if n_eq < k:  # chunks ascend in id, so the first k suffice
            ids = lo + np.flatnonzero(c == t)
            eq.append(ids)
            n_eq += ids.shape[0]
    gt = np.concatenate(gt)  # at most k rows are strictly above the k-th
    order = np.lexsort((gt, -freqs[gt]))
    top = np.concatenate([gt[order], np.concatenate(eq)[: k - gt.shape[0]]])
    if elig is not None:
        top = top[elig[top] & (freqs[top] >= 0)]
    return top.astype(np.int32)


def build_hot_cache(table: QuantizedTensor, freqs=None,
                    capacity: int = 256) -> HotRowCache:
    """Pin the `capacity` most frequent rows of `table`.

    freqs: (n_rows,) lookup counts (e.g. `np.bincount` over training
    histories). None pins the lowest row ids — the right default for tables
    whose ids are already popularity-ranked, and a deterministic fallback
    otherwise.
    """
    n = int(table.values.shape[0])
    capacity = min(int(capacity), n)
    if capacity <= 0:
        d = int(table.values.shape[1])
        return HotRowCache(hot_ids=jnp.zeros((0,), jnp.int32),
                           hot_rows=jnp.zeros((0, d), jnp.float32),
                           capacity=0)
    if freqs is None:
        hot = np.arange(capacity, dtype=np.int32)
    else:
        freqs = np.asarray(freqs)
        assert freqs.shape == (n,), (freqs.shape, n)
        hot = np.sort(top_ids_by_freq(freqs, capacity))
    hot_ids = jnp.asarray(hot)
    hot_rows = dequantize_rowwise(
        QuantizedTensor(values=table.values[hot_ids],
                        scales=table.scales[hot_ids]))
    return HotRowCache(hot_ids=hot_ids, hot_rows=hot_rows, capacity=capacity)


def pin_rows(table: QuantizedTensor, ids, capacity: int) -> HotRowCache:
    """Pin exactly `ids` (unique item ids) into a capacity-`capacity` cache.

    Slots beyond ``len(ids)`` are empty (`INVALID_ID` ids, zero rows). The
    live-catalog reference rebuild uses this to reproduce a churned cache's
    exact surviving hot set, so cache counters stay comparable bit-for-bit.
    """
    d = int(table.values.shape[1])
    ids = np.sort(np.asarray(ids, np.int32))
    capacity = max(int(capacity), 0)
    if len(ids) > capacity:
        raise ValueError(
            f"pin_rows: {len(ids)} ids exceed capacity {capacity}")
    if capacity == 0:
        return HotRowCache(hot_ids=jnp.zeros((0,), jnp.int32),
                           hot_rows=jnp.zeros((0, d), jnp.float32),
                           capacity=0)
    hot_ids = np.full(capacity, INVALID_ID, np.int32)
    hot_ids[: len(ids)] = ids
    rows = np.zeros((capacity, d), np.float32)
    if len(ids):
        rows[: len(ids)] = np.asarray(dequantize_rowwise(QuantizedTensor(
            values=table.values[ids], scales=table.scales[ids])))
    return HotRowCache(hot_ids=jnp.asarray(hot_ids),
                       hot_rows=jnp.asarray(rows), capacity=capacity)


def invalidate_rows(cache: HotRowCache | None, ids) -> HotRowCache | None:
    """Evict `ids` from the hot set (live-catalog row invalidation).

    Touched rows' pinned f32 images are stale the moment the backing table
    row changes, so they must leave the hot set — everything else stays
    pinned ("invalidated only for touched rows"). Evicted slots become
    `INVALID_ID` / zero-row tails; `hot_ids` is re-sorted so the
    searchsorted probe contract holds. Host-side (updates are host-driven);
    a no-op returns the cache unchanged.
    """
    if cache is None or cache.capacity == 0:
        return cache
    ids = np.asarray(ids, np.int32).reshape(-1)
    hot = np.asarray(cache.hot_ids).copy()
    dead = np.isin(hot, ids)
    if not dead.any():
        return cache
    hot[dead] = INVALID_ID
    rows = np.asarray(cache.hot_rows).copy()
    rows[dead] = 0.0
    order = np.argsort(hot, kind="stable")
    return HotRowCache(hot_ids=jnp.asarray(hot[order]),
                       hot_rows=jnp.asarray(rows[order]),
                       capacity=cache.capacity)


def _probe(keys: jax.Array, ids: jax.Array):
    """Membership of `ids` (...,) in the ascending id set `keys` (K,) ->
    (hit mask (...,), position into `keys` (...,)). Negative ids never hit."""
    pos = jnp.searchsorted(keys, ids)
    pos = jnp.clip(pos, 0, keys.shape[0] - 1)
    hit = (keys[pos] == ids) & (ids >= 0)
    return hit, pos


def cached_rows(cache: HotRowCache | None, table: QuantizedTensor,
                ids: jax.Array, *, overlay=None):
    """Resolve item rows for `ids` (...,) -> ((..., d) f32, CacheStats).

    The one row-resolution order of every engine, whether its catalog is
    frozen, live or tiered:

      1. `overlay` (a `catalog.DeltaShard`: ascending int32 ids with
         `EMPTY_ID` padding, int8 `values`, (m, 1) `scales`) — a live
         engine's pending rows, or the tiered store's per-batch bytes;
      2. within the table: the pinned f32 hot row if the id is hot, else
         the int8 dequant-gather of the base row;
      3. zero: -1 padding ids, and (with an overlay) ids past the table
         that miss the overlay — an id out of catalog on one side of a
         rebuild reads the canonical zero row on the other.

    Stats count valid ids as lookups and hot-set probes as hits; an
    overlay hit is not a cache hit.
    """
    valid = ids >= 0
    safe = jnp.maximum(ids, 0)
    cold = table.values[safe].astype(jnp.float32) * table.scales[safe]
    if cache is None or cache.capacity == 0:
        rows = jnp.where(valid[..., None], cold, 0.0)
        stats = CacheStats(hits=jnp.int32(0),
                           lookups=jnp.sum(valid).astype(jnp.int32))
    else:
        hit, pos = _probe(cache.hot_ids, ids)
        rows = jnp.where(hit[..., None], cache.hot_rows[pos], cold)
        rows = jnp.where(valid[..., None], rows, 0.0)
        stats = CacheStats(hits=jnp.sum(hit).astype(jnp.int32),
                           lookups=jnp.sum(valid).astype(jnp.int32))
    if overlay is None or overlay.capacity == 0:
        return rows, stats
    in_range = (ids < table.values.shape[0])[..., None]
    found, opos = _probe(overlay.ids, ids)
    orows = overlay.values[opos].astype(jnp.float32) * overlay.scales[opos]
    return jnp.where(found[..., None], orows,
                     jnp.where(in_range, rows, 0.0)), stats


def pool_rows(rows: jax.Array, ids: jax.Array,
              weights: jax.Array | None = None,
              mode: str = "sum") -> jax.Array:
    """THE pooling reduction: (B, L, d) rows + (B, L) ids -> (B, d).

    One definition behind `cached_embedding_bag`, so frozen, live and
    tiered engines pool with the same ops.
    """
    valid = (ids >= 0).astype(jnp.float32)
    w = valid if weights is None else weights.astype(jnp.float32) * valid
    pooled = jnp.einsum("bld,bl->bd", rows, w)
    if mode == "mean":
        count = jnp.sum(valid, axis=-1, keepdims=True)
        pooled = pooled / jnp.maximum(count, 1.0)
    return pooled


def cached_embedding_bag(
    cache: HotRowCache | None,
    table: QuantizedTensor,
    ids: jax.Array,  # (B, L) int32, -1 padded
    weights: jax.Array | None = None,
    mode: str = "sum",
    *,
    overlay=None,
):
    """Drop-in for `core.embedding.embedding_bag` -> ((B, d), CacheStats).

    The pooling reduction is the same weighted contraction as the uncached
    kernel reference, over rows resolved by `cached_rows` (overlay, hot
    set, int8 path) — identical inputs in identical order, so the result
    bit-matches.
    """
    rows, stats = cached_rows(cache, table, ids, overlay=overlay)  # (B, L, d)
    return pool_rows(rows, ids, weights, mode), stats
