"""Pipelined serving: the AsyncServer ring, the staged serve split, and the
non-blocking scan entry are pure execution knobs — every configuration must
bit-match the synchronous path (items, scores, AND cache counters)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nns import fixed_radius_nns, fixed_radius_nns_async
from repro.data import synthetic
from repro.data.synthetic import serving_queries as _queries
from repro.models import recsys as rs
from repro.serving import (
    AsyncServer,
    MicroBatcher,
    ServerConfigError,
    RecSysEngine,
    lookup_step,
    rank_stage_step,
    scan_step,
    serve_step,
)
from repro.serving.hot_cache import CacheStats
from repro.launch.mesh import make_mesh


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


def _batch(data, idx):
    return {
        **{k: jnp.asarray(v[idx]) for k, v in data.user_feats.items()},
        "history": jnp.asarray(data.histories[idx]),
        "genre": jnp.asarray(data.genres[idx]),
    }


def _assert_same_stream(sync_out, async_out):
    assert len(sync_out) == len(async_out)
    for s, a in zip(sync_out, async_out):
        np.testing.assert_array_equal(s.items, a.items)
        np.testing.assert_array_equal(s.scores, a.scores)


# ---------------------------------------------------------------------------
# AsyncServer == MicroBatcher, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipelined_bitmatches_synchronous(served, depth):
    """Any ring depth serves exactly the synchronous results — items,
    scores, and the hot-cache counters (mixed full + padded-tail buckets)."""
    engine, data = served
    idx = np.arange(19) % 7  # 19 queries -> 8 + 8 + padded 4 at max_batch=8
    sync = MicroBatcher(engine, max_batch=8)
    pipe = AsyncServer(engine, max_batch=8, depth=depth)
    _assert_same_stream(sync.serve_many(_queries(data, idx)),
                        pipe.serve_many(_queries(data, idx)))
    assert pipe.in_flight == 0  # flush retires the whole ring
    assert (pipe.n_served, pipe.n_padded) == (sync.n_served, sync.n_padded)
    assert int(pipe._stats.hits) == int(sync._stats.hits)
    assert int(pipe._stats.lookups) == int(sync._stats.lookups)


def test_coalesced_bitmatches_synchronous(served):
    """coalesce > 1 fuses full buckets into one super-batch dispatch without
    changing a single result or counter; the tail still ships alone."""
    engine, data = served
    idx = np.arange(19) % 7
    sync = MicroBatcher(engine, max_batch=8)
    pipe = AsyncServer(engine, max_batch=8, depth=2, coalesce=2)
    _assert_same_stream(sync.serve_many(_queries(data, idx)),
                        pipe.serve_many(_queries(data, idx)))
    # 8 + 8 coalesced into one dispatch, padded 4-tail alone: counters still
    # count per-bucket batches
    assert pipe.n_batches == 3 and pipe.n_served == 19 and pipe.n_padded == 1
    assert int(pipe._stats.hits) == int(sync._stats.hits)
    assert int(pipe._stats.lookups) == int(sync._stats.lookups)


def test_routed_pipelined_bitmatches_synchronous(served):
    """An engine sharded over a query mesh axis auto-coalesces buckets onto
    the query shards; served results must not change."""
    engine, data = served
    mesh = make_mesh((1,), ("qp",))
    routed = engine.shard(mesh, query_axis="qp")
    pipe = AsyncServer(routed, max_batch=8, depth=2)
    assert pipe.coalesce == 1  # one device -> one query block per dispatch
    forced = AsyncServer(routed, max_batch=8, depth=2, coalesce=2)
    idx = np.arange(19) % 7
    sync_out = MicroBatcher(engine, max_batch=8).serve_many(
        _queries(data, idx))
    _assert_same_stream(sync_out, pipe.serve_many(_queries(data, idx)))
    _assert_same_stream(sync_out, forced.serve_many(_queries(data, idx)))


def test_pipelined_result_and_ticket_api(served):
    """submit/result redeem across an unflushed ring, in any order."""
    engine, data = served
    pipe = AsyncServer(engine, max_batch=4, depth=2)
    tickets = [pipe.submit(q) for q in _queries(data, np.arange(6))]
    direct = engine.serve(_batch(data, np.arange(6)))
    for t in reversed(tickets):  # out-of-order redemption
        np.testing.assert_array_equal(pipe.result(t).items,
                                      np.asarray(direct.items)[t])


def test_async_server_rejects_bad_knobs(served):
    engine, _ = served
    with pytest.raises(ServerConfigError, match="depth"):
        AsyncServer(engine, depth=0)
    with pytest.raises(ServerConfigError, match="coalesce"):
        AsyncServer(engine, coalesce=0)


# ---------------------------------------------------------------------------
# staged serve split == fused serve_step
# ---------------------------------------------------------------------------
def test_staged_steps_compose_to_serve_step(served):
    """lookup -> scan -> rank composes to exactly the fused serve_step:
    same items, same topk, same NNS candidates, same stats."""
    engine, data = served
    batch = _batch(data, np.arange(6))
    f_items, f_top, f_nns, f_stats = serve_step(engine, batch,
                                                CacheStats.zero())
    u, pooled, stats = lookup_step(engine, batch, CacheStats.zero())
    nns = scan_step(engine, u)
    items, top, stats = rank_stage_step(engine, batch, nns.indices, u,
                                        pooled, stats)
    np.testing.assert_array_equal(np.asarray(f_items), np.asarray(items))
    np.testing.assert_array_equal(np.asarray(f_top.scores),
                                  np.asarray(top.scores))
    np.testing.assert_array_equal(np.asarray(f_nns.indices),
                                  np.asarray(nns.indices))
    np.testing.assert_array_equal(np.asarray(f_nns.counts),
                                  np.asarray(nns.counts))
    assert (int(f_stats.hits), int(f_stats.lookups)) == (
        int(stats.hits), int(stats.lookups))


def test_staged_steps_respect_engine_knobs(served):
    """The stage split composes with the engine's execution knobs
    (streaming scan plan, bank-sharded mesh) without changing results."""
    engine, data = served
    batch = _batch(data, np.arange(5))
    base = engine.serve(batch)
    for eng in (
        dataclasses.replace(engine, scan_block=16),
        engine.shard(make_mesh((1,), ("model",)), "model"),
    ):
        u, pooled, stats = lookup_step(eng, batch, CacheStats.zero())
        nns = scan_step(eng, u)
        items, _, _ = rank_stage_step(eng, batch, nns.indices, u, pooled,
                                      stats)
        np.testing.assert_array_equal(np.asarray(base.items),
                                      np.asarray(items))


# ---------------------------------------------------------------------------
# non-blocking scan entry
# ---------------------------------------------------------------------------
def test_fixed_radius_nns_async_bitmatches(key):
    """The async entry is dispatch-only sugar: identical results to the
    blocking call on both execution plans, plus n_valid masking."""
    from repro.core.lsh import lsh_signature, make_lsh_projections

    proj = make_lsh_projections(key, 16, 64)
    x = jax.random.normal(jax.random.key(5), (37, 16))
    sigs = lsh_signature(x, proj)
    want = fixed_radius_nns(sigs[:4], sigs, radius=28, max_candidates=12)
    mask = np.arange(37) % 2 == 0
    for kw in ({}, {"scan_block": 16}, {"n_valid": 30},
               {"db_mask": jnp.asarray(mask)},
               {"scan_block": 16, "superblock": 16}):
        got = fixed_radius_nns_async(sigs[:4], sigs, 28, 12, **kw)
        ref = fixed_radius_nns(sigs[:4], sigs, 28, 12, **kw)
        np.testing.assert_array_equal(np.asarray(ref.indices),
                                      np.asarray(got.indices))
        np.testing.assert_array_equal(np.asarray(ref.distances),
                                      np.asarray(got.distances))
        np.testing.assert_array_equal(np.asarray(ref.counts),
                                      np.asarray(got.counts))
    assert (np.asarray(want.indices) == np.asarray(
        fixed_radius_nns_async(sigs[:4], sigs, 28, 12).indices)).all()
