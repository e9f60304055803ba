"""Tier-migration churn matrix: the frequency-tiered out-of-core catalog
must serve bit-identically to the all-RAM engine over the same state —
items, scores, NNS candidates, AND hot-cache counters — through every
tier transition: cold->int8 promotion, int8->hot promotion, demotion in
both directions, deletes of promoted rows, and migration riding epoch
compaction, including under the depth-3 pipelined ring.

Runs in the CI pallas-interpret lane: every serve drives the streaming
NNS kernel (out-of-core chunks on the tiered side, resident superblocks
on the all-RAM side), so the bit-match also cross-checks the two kernel
drive paths against each other.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import dequantize_rowwise, quantize_rowwise
from repro.data import synthetic
from repro.models import recsys as rs
from repro.serving import (
    AsyncServer,
    LiveCatalog,
    MicroBatcher,
    RecSysEngine,
    TieredCatalog,
    open_base_shard,
    write_base_shard,
)
from repro.serving.hot_cache import INVALID_ID, cached_rows


@pytest.fixture(scope="module")
def served():
    data = synthetic.make_movielens(n_users=60, n_items=90, history_len=6)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=6)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=data.n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=16,
                                top_k=5, hot_rows=16, item_freqs=freqs)
    return engine, data, freqs


def _batch(engine, data, idx, bucket=16):
    queries = synthetic.serving_queries(data, idx)
    return MicroBatcher(engine)._stack_np(list(queries), bucket)


def _rows(rng, m, d):
    return rng.normal(size=(m, d)).astype(np.float32)


def _assert_serves_match(cat, batch):
    """Tiered serve == all-RAM serve == rebuilt-reference serve, bitwise,
    counters included. Returns the tiered result."""
    got = cat.serve(batch)
    for oracle in (cat.to_ram_engine(), cat.rebuild_reference()):
        want = oracle.serve({k: np.asarray(v) for k, v in batch.items()})
        np.testing.assert_array_equal(np.asarray(got.items),
                                      np.asarray(want.items))
        np.testing.assert_array_equal(np.asarray(got.topk.scores),
                                      np.asarray(want.topk.scores))
        np.testing.assert_array_equal(np.asarray(got.nns.indices),
                                      np.asarray(want.nns.indices))
        np.testing.assert_array_equal(np.asarray(got.nns.distances),
                                      np.asarray(want.nns.distances))
        assert int(got.stats.hits) == int(want.stats.hits)
        assert int(got.stats.lookups) == int(want.stats.lookups)
    return got


# ---------------------------------------------------------------------------
# base shard round-trip
# ---------------------------------------------------------------------------
def test_base_shard_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    vals = rng.integers(-128, 128, size=(300, 8), dtype=np.int8)
    scales = rng.random((300, 1), dtype=np.float32)
    sigs = rng.integers(0, 2**32, size=(300, 8), dtype=np.uint32)
    alive = rng.random(300) < 0.8
    write_base_shard(str(tmp_path / "s"), vals, scales, sigs, alive=alive)
    shard, alive2, summary = open_base_shard(str(tmp_path / "s"))
    assert (shard.n, shard.d, shard.words) == (300, 8, 8)
    np.testing.assert_array_equal(np.asarray(shard.values), vals)
    np.testing.assert_array_equal(np.asarray(shard.scales), scales)
    np.testing.assert_array_equal(np.asarray(shard.sigs), sigs)
    np.testing.assert_array_equal(alive2, alive)
    assert summary is None  # none persisted


# ---------------------------------------------------------------------------
# churn matrix against the all-RAM oracles
# ---------------------------------------------------------------------------
def test_tiered_initial_state_matches_allram(served, tmp_path):
    engine, data, freqs = served
    cat = TieredCatalog.from_engine(engine, str(tmp_path), pool_rows=40,
                                    item_freqs=freqs, delta_capacity=8)
    res = _assert_serves_match(cat, _batch(engine, data, range(12)))
    assert int(res.stats.lookups) > 0
    st = cat.stats()
    assert st["pool_rows"] == 40 and st["hot_rows"] == 16
    assert st["resident_bytes"] > 0
    # hot tier is a prefix of the pool: every pinned id is byte-resident
    hot = np.asarray(cat.inner.item_hot.hot_ids)
    hot = hot[hot != INVALID_ID]
    assert np.isin(hot, cat.pool_ids).all()


def test_churn_matrix_bit_matches_reference(served, tmp_path):
    """cold->int8 promote, int8->hot promote, demotions, delete of a
    promoted row, re-embed of a pool row — every intermediate state serves
    bit-identically to the all-RAM engine and the rebuilt reference."""
    engine, data, freqs = served
    rng = np.random.default_rng(1)
    d = engine.item_table_q.shape[1]
    cat = TieredCatalog.from_engine(engine, str(tmp_path), pool_rows=32,
                                    item_freqs=freqs, delta_capacity=8)
    batch = _batch(engine, data, range(12))
    hot_id = int(np.asarray(cat.inner.item_hot.hot_ids)[0])
    pool_only = int(cat.pool_ids[~np.isin(
        cat.pool_ids, np.asarray(cat.inner.item_hot.hot_ids))][0])
    cold_id = int(np.setdiff1d(np.arange(90), cat.pool_ids)[0])

    # upsert touching hot + pool rows: both tiers must evict the stale bytes
    cat.upsert([hot_id, pool_only], _rows(rng, 2, d))
    assert hot_id not in np.asarray(cat.inner.item_hot.hot_ids)
    assert hot_id not in cat.pool_ids and pool_only not in cat.pool_ids
    _assert_serves_match(cat, batch)

    # delete of a promoted row + a cold row
    cat.delete([pool_only, cold_id])
    _assert_serves_match(cat, batch)

    # cold->int8 and int8->hot promotion: skew measured frequency to a
    # cold id and compact — migration rides the epoch fold
    cat.item_freqs[:] = 0
    promoted = int(np.setdiff1d(np.arange(90), cat.pool_ids)[-1])
    cat.item_freqs[promoted] = 10_000
    cat.compact()
    assert promoted in cat.pool_ids  # cold -> int8 pool
    assert promoted in np.asarray(cat.inner.item_hot.hot_ids)  # -> hot
    assert cat.n_pending == 0 and cat.epoch == 1
    _assert_serves_match(cat, batch)

    # demotion: drop its frequency to the floor, everything else above it
    cat.item_freqs[:] = 100
    cat.item_freqs[promoted] = 0
    cat.rebalance()
    assert promoted not in cat.pool_ids
    assert promoted not in np.asarray(cat.inner.item_hot.hot_ids)
    _assert_serves_match(cat, batch)

    # deleted rows never repin
    assert cold_id not in cat.pool_ids


def test_forced_compaction_on_full_delta(served, tmp_path):
    engine, data, freqs = served
    rng = np.random.default_rng(2)
    d = engine.item_table_q.shape[1]
    cat = TieredCatalog.from_engine(engine, str(tmp_path), pool_rows=24,
                                    item_freqs=freqs, delta_capacity=4)
    batch = _batch(engine, data, range(8))
    for lo in range(0, 18, 3):  # 6 batches of 3 > capacity 4 -> compactions
        ids = (np.arange(3) * 7 + lo) % 96  # includes ids past the base
        cat.upsert(ids, _rows(rng, 3, d))
        _assert_serves_match(cat, batch)
    assert cat.n_compactions >= 1
    assert cat.epoch == cat.n_compactions


def test_observe_feeds_freqs_and_never_changes_results(served, tmp_path):
    engine, data, freqs = served
    cat = TieredCatalog.from_engine(engine, str(tmp_path), pool_rows=24,
                                    item_freqs=None, delta_capacity=8)
    batch = _batch(engine, data, range(12))
    before = cat.item_freqs.copy()
    got = _assert_serves_match(cat, batch)
    assert cat.n_observed > 0
    gained = cat.item_freqs - before
    # every real history id and every served item was counted
    hist = np.asarray(batch["history"])[np.asarray(batch["valid"])]
    for gid in hist[hist >= 0].reshape(-1):
        assert gained[gid] > 0
    items = np.asarray(got.items)
    for gid in items[items >= 0].reshape(-1):
        assert gained[gid] > 0


# ---------------------------------------------------------------------------
# migration under the depth-3 pipelined ring (all-RAM LiveCatalog repin)
# ---------------------------------------------------------------------------
def test_repin_under_depth3_ring_matches_sync(served):
    """The hot-cache repin that rides `LiveCatalog.compact` (measured
    frequencies refill churn-evicted slots) must keep the depth-3
    `AsyncServer` bit-identical to the synchronous batcher across the
    same update/serve schedule — counters included."""
    engine, data, _ = served
    rng = np.random.default_rng(3)
    d = engine.item_table_q.shape[1]

    def run(server_cls, **kw):
        cat = LiveCatalog(engine, delta_capacity=8)
        server = server_cls(cat.engine, max_batch=8, **kw)
        cat.attach(server)
        out = []
        hot_sizes = []
        for step in range(3):
            queries = synthetic.serving_queries(
                data, range(step * 10, step * 10 + 10))
            for o in server.serve_many(list(queries)):
                out.append((o.items, o.scores))
            ids = (np.arange(4) + step * 4) % 90
            cat.upsert(ids, _rows(np.random.default_rng(50 + step), 4, d))
            cat.compact()  # repins from observed frequencies
            hot = np.asarray(cat.engine.item_hot.hot_ids)
            hot_sizes.append(int((hot != INVALID_ID).sum()))
        stats = server.stats()
        return out, (stats["cache_hits"], stats["cache_lookups"]), hot_sizes

    sync_out, sync_stats, sync_hot = run(MicroBatcher)
    ring_out, ring_stats, ring_hot = run(AsyncServer, depth=3)
    for (si, ss), (ri, rs_) in zip(sync_out, ring_out):
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(ss, rs_)
    assert sync_stats == ring_stats
    assert sync_hot == ring_hot
    # the repin actually refills slots the churn evictions emptied
    assert all(h == engine.item_hot.capacity for h in sync_hot)


def test_tiered_compact_migration_with_live_traffic(served, tmp_path):
    """Drive real traffic (observe), churn, compact, and verify the
    migrated tiers reflect the measured skew while still bit-matching."""
    engine, data, _ = served
    rng = np.random.default_rng(4)
    d = engine.item_table_q.shape[1]
    cat = TieredCatalog.from_engine(engine, str(tmp_path), pool_rows=24,
                                    item_freqs=None, delta_capacity=8)
    for step in range(3):
        batch = _batch(engine, data, range(step * 12, step * 12 + 12))
        _assert_serves_match(cat, batch)
    cat.upsert([1, 2], _rows(rng, 2, d))
    cat.compact()
    batch = _batch(engine, data, range(12))
    _assert_serves_match(cat, batch)
    # post-migration pool = top-measured rows: the most-observed alive id
    # must be byte-resident
    top = int(np.argmax(cat.item_freqs[:90] * cat.alive[:90]))
    assert top in cat.pool_ids


# ---------------------------------------------------------------------------
# persistence: frequency counters + hot-set ranking survive restore
# ---------------------------------------------------------------------------
def test_snapshot_restore_preserves_freqs_and_ranking(served, tmp_path):
    """The sidecar snapshot (delta + tombstones + measured frequencies)
    restores across an epoch swap into a freshly-opened catalog: the
    counters are bit-equal, the re-derived pool/hot ranking is the exact
    pre-snapshot one (no re-learning the skew), and serving bit-matches —
    delta overlay, tombstones, and summary included."""
    engine, data, _ = served
    rng = np.random.default_rng(6)
    d = engine.item_table_q.shape[1]
    shard_dir, snap_dir = tmp_path / "shard", tmp_path / "snap"
    cat = TieredCatalog.from_engine(engine, str(shard_dir), pool_rows=24,
                                    item_freqs=None, delta_capacity=8)
    # measured traffic -> churn -> EPOCH SWAP -> more traffic + churn, so
    # the snapshot carries post-swap counters, pending rows, and
    # tombstones all at once
    for step in range(3):
        _assert_serves_match(
            cat, _batch(engine, data, range(step * 12, step * 12 + 12)))
    cat.upsert([1, 2, 92], _rows(rng, 3, d))
    cat.delete([3])
    cat.compact()
    assert cat.epoch == 1
    _assert_serves_match(cat, _batch(engine, data, range(12)))
    cat.upsert([5, 94], _rows(rng, 2, d))
    cat.delete([7])
    cat.snapshot(snap_dir)

    other = TieredCatalog.open(str(shard_dir), engine, pool_rows=24,
                               delta_capacity=8)
    assert not np.array_equal(other.item_freqs, cat.item_freqs)  # cold
    other.restore(snap_dir)
    np.testing.assert_array_equal(other.item_freqs, cat.item_freqs)
    assert other.n_observed == cat.n_observed
    np.testing.assert_array_equal(other.alive, cat.alive)
    np.testing.assert_array_equal(np.asarray(other.delta.ids),
                                  np.asarray(cat.delta.ids))
    # the hot-set ranking is the exact pre-snapshot one. (Restore ends in
    # `rebalance()`; the live side's pool has churn-evicted slots that
    # only refill at its next rebalance — pure residency movement, so
    # bring it to the same image before comparing membership.)
    cat.rebalance()
    np.testing.assert_array_equal(other.pool_ids, cat.pool_ids)
    np.testing.assert_array_equal(np.asarray(other.inner.item_hot.hot_ids),
                                  np.asarray(cat.inner.item_hot.hot_ids))
    for f in ("or_sigs", "and_sigs", "min_pc", "max_pc", "n_alive"):
        np.testing.assert_array_equal(np.asarray(getattr(other.summary, f)),
                                      np.asarray(getattr(cat.summary, f)))
    batch = _batch(engine, data, range(8, 20))
    want, got = cat.serve(batch), other.serve(batch)
    np.testing.assert_array_equal(np.asarray(want.items),
                                  np.asarray(got.items))
    np.testing.assert_array_equal(np.asarray(want.topk.scores),
                                  np.asarray(got.topk.scores))
    assert int(want.stats.hits) == int(got.stats.hits)


def test_restore_guards(served, tmp_path):
    """Restore refuses an empty snapshot dir and an epoch mismatch (the
    sidecar is only valid against the base bytes it was taken over)."""
    engine, data, _ = served
    rng = np.random.default_rng(7)
    d = engine.item_table_q.shape[1]
    cat = TieredCatalog.from_engine(engine, str(tmp_path / "a"),
                                    pool_rows=16, delta_capacity=8)
    with pytest.raises(FileNotFoundError, match="no committed snapshot"):
        cat.restore(tmp_path / "empty")
    cat.upsert([1], _rows(rng, 1, d))
    cat.compact()  # epoch 1
    cat.snapshot(tmp_path / "snap")
    fresh = TieredCatalog.from_engine(engine, str(tmp_path / "b"),
                                      pool_rows=16, delta_capacity=8)
    assert fresh.epoch == 0
    with pytest.raises(ValueError, match="does not match the opened"):
        fresh.restore(tmp_path / "snap")


# ---------------------------------------------------------------------------
# one row resolver for frozen, live and tiered catalogs
# ---------------------------------------------------------------------------
_KINDS = ("hot", "overlay", "base", "past", "pad")


@pytest.fixture(scope="module")
def resolved(served, tmp_path_factory):
    """One catalog state held three ways — a live engine with one base row
    re-embedded and pending, its frozen from-scratch rebuild, and a tiered
    store over the same bytes — plus one id of each resolution kind and
    the row each must read."""
    engine, _, freqs = served
    n, d = engine.item_table_q.values.shape
    tiered = TieredCatalog.from_engine(
        engine, str(tmp_path_factory.mktemp("resolve")), pool_rows=32,
        item_freqs=freqs.copy())
    # the tiered store ranks its own hot set from the measured counters:
    # take a hot id pinned on both sides and base ids pinned on neither
    hot = np.intersect1d(np.asarray(engine.item_hot.hot_ids),
                         np.asarray(tiered.inner.item_hot.hot_ids))
    hot = hot[hot != INVALID_ID]
    cold = np.setdiff1d(np.arange(n), np.union1d(
        np.asarray(engine.item_hot.hot_ids),
        np.asarray(tiered.inner.item_hot.hot_ids)))
    ids = dict(zip(_KINDS, (int(hot[0]), int(cold[0]), int(cold[1]),
                            n + 5, -1)))
    row = np.random.default_rng(3).normal(size=(1, d)).astype(np.float32)
    live = LiveCatalog(engine, delta_capacity=8)
    live.upsert(np.asarray([ids["overlay"]], np.int32), row)
    tiered.upsert(np.asarray([ids["overlay"]], np.int32), row)
    query = jnp.asarray([ids[k] for k in _KINDS], jnp.int32)
    frozen = live.rebuild_reference()
    sources = {
        "frozen": (frozen.item_hot, frozen.item_table_q, frozen.delta),
        "live": (live.engine.item_hot, live.engine.item_table_q,
                 live.engine.delta),
        "tiered": (tiered.inner.item_hot, tiered.inner.item_table_q,
                   tiered._build_overlay(np.asarray(query))),
    }
    base = np.asarray(dequantize_rowwise(engine.item_table_q))
    new = np.asarray(dequantize_rowwise(quantize_rowwise(jnp.asarray(row))))
    want = np.stack([base[ids["hot"]], new[0], base[ids["base"]],
                     np.zeros(d, np.float32), np.zeros(d, np.float32)])
    return sources, query, want


@pytest.mark.parametrize("catalog", ["frozen", "live", "tiered"])
def test_cached_rows_resolves_every_kind_of_id(resolved, catalog):
    """`hot_cache.cached_rows` is the one resolver: over the same bytes a
    frozen, a live and a tiered catalog read a hot id from its pinned row,
    a pending id from the overlay (the rebuilt table on the frozen side),
    a base-only id from the int8 table, and zero for an id past the table
    and for -1 padding; hot probes count as hits, valid ids as lookups."""
    sources, query, want = resolved
    cache, table, overlay = sources[catalog]
    rows, stats = cached_rows(cache, table, query, overlay=overlay)
    np.testing.assert_array_equal(np.asarray(rows), want)
    assert int(stats.hits) == 1
    assert int(stats.lookups) == 4
