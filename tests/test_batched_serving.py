"""The batched serving subsystem: hot-row cache bit-exactness, batcher
padding invariance, sharded NNS equivalence, and hit-rate accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding import embedding_bag, init_table, lookup
from repro.core.nns import fixed_radius_nns, sharded_fixed_radius_nns
from repro.data import synthetic
from repro.models import recsys as rs
from repro.serving import (
    MicroBatcher,
    RecSysEngine,
    build_hot_cache,
    cached_embedding_bag,
    cached_lookup,
    default_buckets,
    serve_step,
)
from repro.serving.hot_cache import CacheStats
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# hot-row cache
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def table(request):
    return init_table(jax.random.key(3), 200, 32)


def test_cached_lookup_bitmatches_uncached(table, rng):
    cache = build_hot_cache(table, freqs=rng.integers(1, 100, 200),
                            capacity=50)
    ids = jnp.asarray(rng.integers(-1, 200, size=(6, 9)), jnp.int32)
    got, stats = cached_lookup(cache, table, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(lookup(table, ids)))
    assert int(stats.lookups) == int((np.asarray(ids) >= 0).sum())
    assert 0 <= int(stats.hits) <= int(stats.lookups)


def test_cached_bag_bitmatches_embedding_bag(table, rng):
    freqs = rng.integers(0, 1000, 200)
    cache = build_hot_cache(table, freqs=freqs, capacity=64)
    ids = jnp.asarray(rng.integers(-1, 200, size=(8, 12)), jnp.int32)
    for mode in ("sum", "mean"):
        got, _ = cached_embedding_bag(cache, table, ids, mode=mode)
        want = embedding_bag(table, ids, mode=mode)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # weighted pooling too
    w = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    got, _ = cached_embedding_bag(cache, table, ids, weights=w)
    want = embedding_bag(table, ids, weights=w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hot_cache_pins_most_frequent_rows(table):
    freqs = np.zeros(200)
    hot_set = np.array([3, 77, 150, 199])
    freqs[hot_set] = [10, 20, 30, 40]
    cache = build_hot_cache(table, freqs=freqs, capacity=4)
    np.testing.assert_array_equal(np.asarray(cache.hot_ids), hot_set)
    # lookups of pinned rows are all hits
    _, stats = cached_lookup(cache, table, jnp.asarray(hot_set))
    assert int(stats.hits) == 4 and int(stats.lookups) == 4
    # lookups of cold rows are all misses
    _, stats = cached_lookup(cache, table, jnp.asarray([0, 1, 2]))
    assert int(stats.hits) == 0 and int(stats.lookups) == 3


def test_hot_cache_ties_break_by_ascending_id(table):
    """Tied frequencies must pin deterministically: lowest id wins.

    Regression: `build_hot_cache` used `argpartition`, whose order among
    equal keys is implementation-defined — two processes (or two numpy
    versions) could pin different hot sets for the same frequencies,
    breaking cross-process bit-match of cache counters.
    """
    freqs = np.zeros(200)
    freqs[[7, 42, 141, 190]] = 50  # four-way tie for 2 remaining slots
    freqs[[5, 100]] = 99
    cache = build_hot_cache(table, freqs=freqs, capacity=4)
    np.testing.assert_array_equal(np.asarray(cache.hot_ids), [5, 7, 42, 100])
    # all-zero frequencies: the full tie resolves to the lowest ids
    cache = build_hot_cache(table, freqs=np.zeros(200), capacity=3)
    np.testing.assert_array_equal(np.asarray(cache.hot_ids), [0, 1, 2])


def test_top_ids_by_freq_order_and_eligibility():
    from repro.serving import top_ids_by_freq

    freqs = np.array([5, 9, 9, 1, 9, 0])
    np.testing.assert_array_equal(top_ids_by_freq(freqs, 4), [1, 2, 4, 0])
    # eligibility masks rows out entirely (result may come up short)
    eligible = np.array([True, False, True, True, False, False])
    np.testing.assert_array_equal(
        top_ids_by_freq(freqs, 4, eligible=eligible), [2, 0, 3])
    np.testing.assert_array_equal(
        top_ids_by_freq(freqs, 2, eligible=np.zeros(6, bool)), [])


def test_zero_capacity_cache_is_uncached_path(table, rng):
    cache = build_hot_cache(table, capacity=0)
    ids = jnp.asarray(rng.integers(-1, 200, size=(4, 7)), jnp.int32)
    got, stats = cached_embedding_bag(cache, table, ids)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(embedding_bag(table, ids)))
    assert int(stats.hits) == 0


# ---------------------------------------------------------------------------
# engine + batcher
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    data = synthetic.make_movielens(n_users=120, n_items=90, history_len=6)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=6)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=data.n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=16,
                                top_k=5, hot_rows=32, item_freqs=freqs)
    return engine, data


def _queries(data, idx):
    return [{**{k: v[i] for k, v in data.user_feats.items()},
             "history": data.histories[i], "genre": data.genres[i]}
            for i in idx]


def _batch(data, idx):
    return {
        **{k: jnp.asarray(v[idx]) for k, v in data.user_feats.items()},
        "history": jnp.asarray(data.histories[idx]),
        "genre": jnp.asarray(data.genres[idx]),
    }


def test_batcher_padding_never_changes_topk(served):
    """Queries served through a padded bucket == exact-shape serve."""
    engine, data = served
    mb = MicroBatcher(engine, max_batch=16)
    n = 5  # pads to the 8-bucket
    out = mb.serve_many(_queries(data, range(n)))
    assert mb.n_padded > 0  # the bucket really padded
    direct = engine.serve(_batch(data, np.arange(n)))
    for i in range(n):
        np.testing.assert_array_equal(out[i].items,
                                      np.asarray(direct.items)[i])
        np.testing.assert_array_equal(out[i].scores,
                                      np.asarray(direct.topk.scores)[i])


def test_batcher_buckets_and_order(served):
    engine, data = served
    mb = MicroBatcher(engine, max_batch=8)
    assert default_buckets(8) == (1, 2, 4, 8)
    # 19 queries -> 8 + 8 + 4(padded from 3) batches, results in order
    idx = np.arange(19) % 7  # users repeat: 0 and 7 and 14 are user 0, ...
    out = mb.serve_many(_queries(data, idx))
    assert len(out) == 19 and mb.n_batches == 3
    direct = engine.serve(_batch(data, idx))
    for i in range(19):
        np.testing.assert_array_equal(out[i].items,
                                      np.asarray(direct.items)[i])
    # the same user served in different micro-batches gets identical
    # recommendations (determinism across bucket shapes)
    np.testing.assert_array_equal(out[0].items, out[7].items)
    np.testing.assert_array_equal(out[7].items, out[14].items)
    assert 0.0 <= mb.stats()["cache_hit_rate"] <= 1.0 and mb.n_served == 19


def test_padding_rows_excluded_from_cache_stats(served):
    """Bucket padding must not inflate the hot-cache hit/lookup counters."""
    engine, data = served
    n = 5  # pads to the 8-bucket
    mb = MicroBatcher(engine, max_batch=16)
    mb.serve_many(_queries(data, range(n)))
    assert mb.n_padded == 3
    _, _, _, unpadded = serve_step(engine, _batch(data, np.arange(n)),
                                   CacheStats.zero())
    assert int(mb._stats.lookups) == int(unpadded.lookups)
    assert int(mb._stats.hits) == int(unpadded.hits)


def test_serve_stats_accumulate_across_batches(served):
    engine, data = served
    batch = _batch(data, np.arange(4))
    _, _, _, stats = serve_step(engine, batch, CacheStats.zero())
    one = (int(stats.hits), int(stats.lookups))
    assert one[1] > 0
    _, _, _, stats2 = serve_step(engine, batch, stats)
    assert (int(stats2.hits), int(stats2.lookups)) == (2 * one[0], 2 * one[1])


def test_tail_bucket_pads_with_invalid_ids(served):
    """Regression: a pending queue smaller than the smallest bucket pads
    with INVALID query ids (-1) — the padded tail must neither touch the
    hot-row cache counters nor change the served result. (Padding used to
    replicate the last real query and rely on the `valid` mask alone.)"""
    engine, data = served
    mb = MicroBatcher(engine, max_batch=8, buckets=(4, 8))
    out = mb.serve_many(_queries(data, [3]))  # 1 pending < smallest bucket 4
    assert mb.n_padded == 3 and mb.n_batches == 1
    # the padded rows really are invalid queries, not clones of query 3
    batch = mb._stack([q for _, q in [(0, _queries(data, [3])[0])]], 4)
    assert (np.asarray(batch["history"])[1:] == -1).all()
    assert (np.asarray(batch["genre"])[1:] == -1).all()
    # counters match a padding-free serve of the same single query exactly
    _, _, _, unpadded = serve_step(engine, _batch(data, np.array([3])),
                                   CacheStats.zero())
    assert int(mb._stats.lookups) == int(unpadded.lookups)
    assert int(mb._stats.hits) == int(unpadded.hits)
    # and the recommendation is unchanged
    direct = engine.serve(_batch(data, np.array([3])))
    np.testing.assert_array_equal(out[0].items, np.asarray(direct.items)[0])


def test_sharded_engine_matches_local(served):
    """CPU 1-device mesh: sharded filter stage == single-device, end to end."""
    engine, data = served
    mesh = make_mesh((1,), ("model",))
    sharded = engine.shard(mesh, "model")
    batch = _batch(data, np.arange(6))
    local, dist = engine.serve(batch), sharded.serve(batch)
    np.testing.assert_array_equal(np.asarray(local.items),
                                  np.asarray(dist.items))
    np.testing.assert_array_equal(np.asarray(local.nns.counts),
                                  np.asarray(dist.nns.counts))


def test_engine_scan_block_serves_identically(served):
    """The streaming filtering plan is a pure execution knob: forcing it on
    the engine (locally and sharded) must not change a single served item."""
    import dataclasses

    engine, data = served
    batch = _batch(data, np.arange(6))
    base = engine.serve(batch)
    for eng in (
        dataclasses.replace(engine, scan_block=16),
        dataclasses.replace(engine.shard(make_mesh((1,), ("model",)),
                                         "model"), scan_block=8),
    ):
        got = eng.serve(batch)
        np.testing.assert_array_equal(np.asarray(base.items),
                                      np.asarray(got.items))
        np.testing.assert_array_equal(np.asarray(base.nns.indices),
                                      np.asarray(got.nns.indices))
        np.testing.assert_array_equal(np.asarray(base.nns.counts),
                                      np.asarray(got.nns.counts))


def test_query_parallel_engine_matches_local(served):
    """engine.shard with a query axis (with and without a db axis) must not
    change a single served item."""
    engine, data = served
    batch = _batch(data, np.arange(6))
    base = engine.serve(batch)
    qp_only = engine.shard(make_mesh((1,), ("qp",)), query_axis="qp")
    both = engine.shard(make_mesh((1, 1), ("qp", "banks")), "banks",
                        query_axis="qp")
    for eng in (qp_only, both):
        got = eng.serve(batch)
        np.testing.assert_array_equal(np.asarray(base.items),
                                      np.asarray(got.items))
        np.testing.assert_array_equal(np.asarray(base.nns.indices),
                                      np.asarray(got.nns.indices))
        np.testing.assert_array_equal(np.asarray(base.nns.counts),
                                      np.asarray(got.nns.counts))
    with pytest.raises(ValueError, match="query_axis"):
        engine.shard(make_mesh((1,), ("qp",)))


def test_query_parallel_engine_masks_padded_sigs(served):
    """Regression: an engine whose item_sigs carry pad rows (e.g. from an
    earlier bank-sharded incarnation) re-sharded to query-parallel-only
    must never surface a pad row (index >= n_items) as a candidate."""
    import dataclasses

    engine, data = served
    batch = _batch(data, np.arange(5))
    want = engine.serve(batch)
    n_items = engine.item_table_q.shape[0]
    padded = jnp.pad(engine.item_sigs, ((0, 3), (0, 0)))  # all-zero sigs
    qp = dataclasses.replace(engine, item_sigs=padded).shard(
        make_mesh((1,), ("qp",)), query_axis="qp")
    got = qp.serve(batch)
    assert (np.asarray(got.nns.indices) < n_items).all()
    np.testing.assert_array_equal(np.asarray(want.items),
                                  np.asarray(got.items))


def test_sharded_nns_with_padding_excludes_pad_rows(key):
    """n not divisible by shards: pad rows must never appear as candidates."""
    from repro.core.lsh import lsh_signature, make_lsh_projections

    proj = make_lsh_projections(key, 16, 64)
    x = jax.random.normal(jax.random.key(5), (37, 16))
    sigs = lsh_signature(x, proj)
    padded = jnp.pad(sigs, ((0, 3), (0, 0)))  # 40 rows, 3 pads
    mesh = make_mesh((1,), ("model",))
    local = fixed_radius_nns(sigs[:4], sigs, radius=28, max_candidates=12)
    shard = sharded_fixed_radius_nns(mesh, "model", sigs[:4], padded,
                                     radius=28, max_candidates=12, n_valid=37)
    np.testing.assert_array_equal(np.asarray(local.counts),
                                  np.asarray(shard.counts))
    assert (np.asarray(shard.indices) < 37).all()
    np.testing.assert_array_equal(
        np.sort(np.asarray(local.indices), -1),
        np.sort(np.asarray(shard.indices), -1))
