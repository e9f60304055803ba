"""Live-catalog churn matrix: every update sequence must serve bit-identically
to an engine rebuilt from scratch with the final table — items, scores, AND
hot-cache counters (the cache is invalidated only for touched rows, and the
reference pins exactly the surviving hot set). Pre- and post-compaction,
through the synchronous batcher and the AsyncServer ring alike.

Runs in the CI pallas-interpret lane too: the masked streaming tests below
drive the real kernel body with the tombstone-mask operand.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nns import (
    EMPTY_ID,
    delta_aware_nns,
    delta_scan,
    fixed_radius_nns,
    merge_delta_candidates,
)
from repro.data import synthetic
from repro.data.synthetic import serving_queries as _queries
from repro.models import recsys as rs
from repro.serving import (
    AsyncServer,
    DeltaFullError,
    LiveCatalog,
    MicroBatcher,
    RecSysEngine,
    SchemaMismatchError,
    invalidate_rows,
    pin_rows,
)
from repro.serving.hot_cache import INVALID_ID, cached_rows
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


def _rows(rng, m, d):
    return rng.normal(size=(m, d)).astype(np.float32)


def _serve(engine, queries, max_batch=8):
    server = MicroBatcher(engine, max_batch=max_batch)
    out = server.serve_many(queries)
    return (np.stack([o.items for o in out]),
            np.stack([o.scores for o in out]),
            (int(server._stats.hits), int(server._stats.lookups)))


def _assert_matches_reference(cat, queries):
    """serve(live) == serve(rebuilt-from-final-table), bit for bit."""
    items, scores, stats = _serve(cat.engine, queries)
    r_items, r_scores, r_stats = _serve(cat.rebuild_reference(), queries)
    np.testing.assert_array_equal(items, r_items)
    np.testing.assert_array_equal(scores, r_scores)
    assert stats == r_stats
    return items, scores, stats


# ---------------------------------------------------------------------------
# kernel/NNS layer: tombstone masks + delta merge are exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [
    {"scan_block": 64}, {"scan_block": 200},
    {"scan_block": 64, "superblock": 256},
    {"scan_block": 64, "n_valid": 600},
])
def test_masked_streaming_matches_dense(kw):
    """db_mask (tombstones) on the streaming plan — any chunk/superblock —
    bit-matches the dense masked path (kernel + ref + interpret)."""
    rng = np.random.default_rng(0)
    db = jnp.asarray(rng.integers(0, 2**32, (700, 8), dtype=np.uint32))
    qs = jnp.asarray(rng.integers(0, 2**32, (9, 8), dtype=np.uint32))
    mask = jnp.asarray(rng.random(700) > 0.3)
    want = fixed_radius_nns(qs, db, 120, 16, db_mask=mask, scan_block=0,
                            n_valid=kw.get("n_valid"))
    got = fixed_radius_nns(qs, db, 120, 16, db_mask=mask, **kw)
    # the plans' results, not the streaming kernel's own counters
    for name in ("indices", "distances", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(want, name)),
                                      np.asarray(getattr(got, name)),
                                      err_msg=name)


@pytest.mark.parametrize("scan_block", [0, 64])
def test_delta_aware_nns_matches_rebuilt(scan_block):
    """base+delta+merge == one dense scan over the folded final table, for
    overwrites (ids interleave with base), new ids, and deletions."""
    rng = np.random.default_rng(1)
    n, words, D = 500, 8, 64
    db = rng.integers(0, 2**32, (n, words), dtype=np.uint32)
    qs = jnp.asarray(rng.integers(0, 2**32, (7, words), dtype=np.uint32))
    over = rng.choice(n, 30, replace=False)
    new = np.arange(n, n + 10)
    ids = np.sort(np.concatenate([over, new]).astype(np.int32))
    delta_ids = np.full(D, EMPTY_ID, np.int32)
    delta_ids[: len(ids)] = ids
    dsigs = rng.integers(0, 2**32, (D, words), dtype=np.uint32)
    deleted = rng.choice(np.setdiff1d(np.arange(n), over), 12, replace=False)
    alive = np.ones(n, bool)
    alive[over] = False
    alive[deleted] = False

    folded = np.zeros((n + 10, words), np.uint32)
    folded[:n] = db
    folded[ids] = dsigs[: len(ids)]
    folded_alive = np.concatenate([alive, np.zeros(10, bool)])
    folded_alive[ids] = True
    want = fixed_radius_nns(qs, jnp.asarray(folded), 120, 16,
                            db_mask=jnp.asarray(folded_alive), scan_block=0)
    got = delta_aware_nns(qs, jnp.asarray(db), jnp.asarray(dsigs),
                          jnp.asarray(delta_ids), 120, 16,
                          db_mask=jnp.asarray(alive), scan_block=scan_block)
    for name, a, b in zip(("indices", "distances", "counts"), want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_empty_delta_merge_is_identity():
    """An all-free delta shard changes nothing — the steady-state serve."""
    rng = np.random.default_rng(2)
    db = jnp.asarray(rng.integers(0, 2**32, (300, 8), dtype=np.uint32))
    qs = jnp.asarray(rng.integers(0, 2**32, (5, 8), dtype=np.uint32))
    base = fixed_radius_nns(qs, db, 120, 16)
    pend = delta_scan(qs, jnp.zeros((32, 8), jnp.uint32),
                      jnp.full((32,), EMPTY_ID, jnp.int32), 120, 16)
    assert int(jnp.sum(pend.counts)) == 0
    got = merge_delta_candidates(base, pend, 16)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_query_parallel_delta_scan_matches_replicated():
    """The query-sharded delta scan (size-1 mesh: exercises the shard_map
    spec path without multi-device) bit-matches the replicated scan."""
    from repro.core.nns import query_parallel_delta_scan

    rng = np.random.default_rng(5)
    qs = jnp.asarray(rng.integers(0, 2**32, (7, 8), dtype=np.uint32))
    dsigs = jnp.asarray(rng.integers(0, 2**32, (32, 8), dtype=np.uint32))
    dids = np.full(32, EMPTY_ID, np.int32)
    dids[:10] = np.sort(rng.choice(500, 10, replace=False))
    dids = jnp.asarray(dids)
    mesh = make_mesh((1,), ("qp",))
    want = delta_scan(qs, dsigs, dids, 120, 16)
    got = query_parallel_delta_scan(mesh, "qp", qs, dsigs, dids, 120, 16)
    for name, a, b in zip(("indices", "distances", "counts"), want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.slow
def test_query_parallel_delta_scan_two_devices_subprocess():
    """Regression: the delta-shard scan used to run fully replicated on
    query-sharded mesh plans (every device scanning every query). On 2
    fake CPU devices the query-sharded scan — odd query count, so the pad
    row is exercised — must bit-match the replicated path, and the
    query-routed engine must serve identically to the local one under a
    live delta."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.nns import (EMPTY_ID, delta_scan,
                                    query_parallel_delta_scan)
        from repro.launch.mesh import make_mesh
        rng = np.random.default_rng(0)
        qs = jnp.asarray(rng.integers(0, 2**32, (5, 8), dtype=np.uint32))
        dsigs = jnp.asarray(rng.integers(0, 2**32, (64, 8), dtype=np.uint32))
        dids = np.full(64, EMPTY_ID, np.int32)
        dids[:20] = np.sort(rng.choice(900, 20, replace=False))
        dids = jnp.asarray(dids)
        mesh = make_mesh((2,), ("qp",))
        want = delta_scan(qs, dsigs, dids, 110, 16)
        got = query_parallel_delta_scan(mesh, "qp", qs, dsigs, dids, 110, 16)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("MARKER delta qp ok", jax.device_count())
    """)
    import os
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, cwd=repo,
        env={"PYTHONPATH": str(repo / "src"),
             "HOME": os.environ.get("HOME", str(repo)),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "MARKER delta qp ok 2" in out.stdout


# ---------------------------------------------------------------------------
# churn scenario matrix (engine-level bit-match vs rebuilt frozen engine)
# ---------------------------------------------------------------------------
def test_upsert_new_rows_bitmatch(served):
    """Brand-new item ids extend the catalog through the delta and become
    retrievable immediately; serving bit-matches the rebuilt engine before
    and after compaction."""
    engine, data = served
    rng = np.random.default_rng(10)
    cat = LiveCatalog(engine, delta_capacity=16)
    queries = _queries(data, np.arange(25) % 60)
    cat.upsert(np.arange(90, 96), _rows(rng, 6, engine.cfg.embed_dim))
    assert cat.n_pending == 6 and cat.n_items == 96
    pre = _assert_matches_reference(cat, queries)
    cat.compact()
    assert cat.epoch == 1 and cat.n_pending == 0
    post = _assert_matches_reference(cat, queries)
    np.testing.assert_array_equal(pre[0], post[0])  # compaction moves no bit
    np.testing.assert_array_equal(pre[1], post[1])


def test_overwrite_hot_cached_rows_bitmatch(served):
    """Re-embedding rows pinned in the hot cache: the touched rows leave
    the hot set (stale pins can never serve), everything else stays pinned,
    and results + counters bit-match the reference."""
    engine, data = served
    rng = np.random.default_rng(11)
    cat = LiveCatalog(engine, delta_capacity=16)
    hot = np.asarray(engine.item_hot.hot_ids)[:4]
    assert (hot != INVALID_ID).all()
    queries = _queries(data, np.arange(25) % 60)
    base_stats = _serve(cat.engine, queries)[2]
    cat.upsert(hot, _rows(rng, len(hot), engine.cfg.embed_dim))
    live_ids = np.asarray(cat.engine.item_hot.hot_ids)
    assert not np.isin(hot, live_ids).any()  # evicted
    assert (live_ids == INVALID_ID).sum() == len(hot)  # only touched rows
    _, _, stats = _assert_matches_reference(cat, queries)
    # the touched ids are top-frequency history items: their pooling
    # lookups now miss the hot set (lookup counts themselves shift with
    # the changed candidate sets; the reference equality above is the
    # binding contract)
    assert stats[0] < base_stats[0]
    cat.compact()
    _assert_matches_reference(cat, queries)


def test_delete_then_readd_bitmatch(served):
    """delete -> (absent from every result) -> re-add same id -> rankable
    again with the new embedding; bit-match at every step."""
    engine, data = served
    rng = np.random.default_rng(12)
    cat = LiveCatalog(engine, delta_capacity=16)
    queries = _queries(data, np.arange(25) % 60)
    victim = np.asarray(_serve(cat.engine, queries)[0])
    victim = int(victim[victim >= 0].flat[0])  # an id that actually serves

    cat.delete([victim])
    items, _, _ = _assert_matches_reference(cat, queries)
    assert not (items == victim).any()  # tombstoned everywhere
    cat.upsert([victim], _rows(rng, 1, engine.cfg.embed_dim))
    assert cat.n_pending == 1
    _assert_matches_reference(cat, queries)
    cat.compact()
    items, _, _ = _assert_matches_reference(cat, queries)
    # post-compaction the id lives in the new base epoch
    assert bool(np.asarray(cat.engine.item_mask)[victim])


def test_delta_full_forces_compact(served):
    """Overflowing the bounded delta forces an epoch fold first (the update
    itself still lands); auto_compact=False surfaces DeltaFullError; a
    batch larger than the shard can never fit."""
    engine, data = served
    rng = np.random.default_rng(13)
    queries = _queries(data, np.arange(25) % 60)
    cat = LiveCatalog(engine, delta_capacity=4)
    cat.upsert([0, 1, 2], _rows(rng, 3, engine.cfg.embed_dim))
    assert cat.epoch == 0
    cat.upsert([3, 4], _rows(rng, 2, engine.cfg.embed_dim))  # 5 > 4: fold
    assert cat.epoch == 1 and cat.n_pending == 2
    _assert_matches_reference(cat, queries)

    frozen = LiveCatalog(engine, delta_capacity=4, auto_compact=False)
    frozen.upsert([0, 1, 2], _rows(rng, 3, engine.cfg.embed_dim))
    with pytest.raises(DeltaFullError):
        frozen.upsert([3, 4], _rows(rng, 2, engine.cfg.embed_dim))
    with pytest.raises(DeltaFullError):  # can never fit, even post-compact
        cat.upsert(np.arange(5), _rows(rng, 5, engine.cfg.embed_dim))


def test_compact_during_pipelined_serving_depth3(served):
    """Epoch swap under the AsyncServer ring at depth 3: buckets dispatched
    before the swap finish on the old epoch, buckets after serve the new
    one — every bucket is entirely one epoch, asserted bucket by bucket
    against the two rebuilt frozen references."""
    engine, data = served
    rng = np.random.default_rng(14)
    cat = LiveCatalog(engine, delta_capacity=16)
    cat.upsert(np.arange(90, 94), _rows(rng, 4, engine.cfg.embed_dim))
    old_ref = cat.rebuild_reference()

    pipe = AsyncServer(cat.engine, max_batch=8, depth=3)
    cat.attach(pipe)
    idx = np.arange(48) % 60
    tickets = [pipe.submit(q) for q in _queries(data, idx)]
    # dispatch the first two buckets onto the ring, then swap epochs
    for _ in range(2):
        pipe._ring.append(pipe._dispatch(pipe._take_parts()))
    assert pipe.in_flight == 2
    cat.upsert(np.arange(94, 98), _rows(rng, 4, engine.cfg.embed_dim))
    cat.compact()  # publishes the new epoch to the attached server
    new_ref = cat.rebuild_reference()
    pipe.flush()

    got = np.stack([pipe.result(t).items for t in tickets])
    want_old = _serve(old_ref, _queries(data, idx))[0]
    want_new = _serve(new_ref, _queries(data, idx))[0]
    np.testing.assert_array_equal(got[:16], want_old[:16])  # old epoch
    np.testing.assert_array_equal(got[16:], want_new[16:])  # new epoch
    # never stale once flushed: a fresh stream is pure new-epoch
    out = pipe.serve_many(_queries(data, idx))
    np.testing.assert_array_equal(np.stack([o.items for o in out]), want_new)


def test_snapshot_restore_roundtrip(served, tmp_path):
    """Epoch-numbered snapshot through the fault-tolerant checkpointer:
    restore reproduces the exact engine (delta + tombstones + caches) and
    serves bit-identically."""
    engine, data = served
    rng = np.random.default_rng(15)
    queries = _queries(data, np.arange(17) % 60)
    cat = LiveCatalog(engine, delta_capacity=16)
    cat.upsert([5, 6, 90], _rows(rng, 3, engine.cfg.embed_dim))
    cat.compact()
    cat.delete([7])
    cat.upsert([8], _rows(rng, 1, engine.cfg.embed_dim))
    want = _serve(cat.engine, queries)
    cat.snapshot(tmp_path)

    other = LiveCatalog(cat.engine, delta_capacity=16)  # structural template
    other.restore(tmp_path)
    assert other.epoch == 1
    got = _serve(other.engine, queries)
    np.testing.assert_array_equal(want[0], got[0])
    np.testing.assert_array_equal(want[1], got[1])
    assert want[2] == got[2]


def test_live_serving_on_mesh_plans(served):
    """The delta path composes with the bank-sharded / query-parallel NNS
    routes (tombstone mask rides the banks) without changing one bit."""
    engine, data = served
    rng = np.random.default_rng(16)
    cat = LiveCatalog(engine, delta_capacity=16)
    cat.upsert([0, 1, 90], _rows(rng, 3, engine.cfg.embed_dim))
    cat.delete([2])
    queries = _queries(data, np.arange(9) % 60)
    want = _serve(cat.engine, queries)
    mesh = make_mesh((1,), ("banks",))
    qmesh = make_mesh((1,), ("qp",))
    for live in (cat.engine.shard(mesh, "banks"),
                 cat.engine.shard(qmesh, query_axis="qp"),
                 cat.engine.compact().shard(mesh, "banks")):
        got = _serve(live, queries)
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])


def test_retired_new_id_in_history_bitmatch(served):
    """A beyond-base id that was launched then retired can linger in user
    histories; pooling must resolve it identically (the canonical zero
    row) on the live engine, its compaction, AND the reference rebuild —
    whose base tables are different sizes (clamped gathers would diverge).
    """
    engine, data = served
    rng = np.random.default_rng(17)
    cat = LiveCatalog(engine, delta_capacity=16)
    cat.upsert([95, 99], _rows(rng, 2, engine.cfg.embed_dim))
    cat.delete([95])  # gap id: dead, below n_total on the rebuilt table

    idx = np.arange(6)
    hist = data.histories[idx].copy()
    hist[:, 0] = 95  # retired new-id still in everyone's history
    batch = {
        **{k: jnp.asarray(v[idx]) for k, v in data.user_feats.items()},
        "history": jnp.asarray(hist), "genre": jnp.asarray(data.genres[idx]),
    }
    live = cat.engine.serve(batch)
    ref = cat.rebuild_reference().serve(batch)
    np.testing.assert_array_equal(np.asarray(live.items),
                                  np.asarray(ref.items))
    np.testing.assert_array_equal(np.asarray(live.topk.scores),
                                  np.asarray(ref.topk.scores))
    post = cat.engine.compact().serve(batch)
    np.testing.assert_array_equal(np.asarray(live.items),
                                  np.asarray(post.items))


# ---------------------------------------------------------------------------
# units: hot-row invalidation + epoch swap guards
# ---------------------------------------------------------------------------
def test_invalidate_and_pin_rows_units(served):
    engine, _ = served
    cache = engine.item_hot
    victims = np.asarray(cache.hot_ids)[[1, 3]]
    out = invalidate_rows(cache, victims)
    assert out.capacity == cache.capacity
    ids = np.asarray(out.hot_ids)
    assert not np.isin(victims, ids[ids != INVALID_ID]).any()
    assert (np.diff(ids) >= 0).all()  # searchsorted contract survives
    assert (np.asarray(out.hot_rows)[ids == INVALID_ID] == 0).all()
    # untouched ids still hit, with identical pinned rows
    keep = ids[ids != INVALID_ID][:4]
    rows, st = cached_rows(out, engine.item_table_q, jnp.asarray(keep))
    ref, _ = cached_rows(cache, engine.item_table_q, jnp.asarray(keep))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref))
    assert int(st.hits) == len(keep)
    # no-op invalidation returns the same cache object
    assert invalidate_rows(cache, np.asarray([10**9])) is cache
    # pin_rows reproduces an invalidated cache's surviving set exactly
    repin = pin_rows(engine.item_table_q, ids[ids != INVALID_ID],
                     cache.capacity)
    np.testing.assert_array_equal(np.asarray(repin.hot_ids), ids)
    np.testing.assert_array_equal(np.asarray(repin.hot_rows),
                                  np.asarray(out.hot_rows))


def test_swap_engine_rejects_schema_change(served):
    engine, _ = served
    server = MicroBatcher(engine, max_batch=8)
    cfg = engine.cfg._replace(user_features={"user_id": 10})
    with pytest.raises(SchemaMismatchError, match="schema"):
        server.swap_engine(dataclasses.replace(engine, cfg=cfg))


def test_frozen_engine_stays_frozen(served):
    """A frozen engine (delta=None) refuses updates with a pointer to the
    catalog, and an empty live view serves bit-identically to frozen."""
    engine, data = served
    with pytest.raises(ValueError, match="delta"):
        engine.apply_updates(upsert_ids=[0], upsert_rows=np.zeros((1, 32)))
    queries = _queries(data, np.arange(9) % 60)
    frozen = _serve(engine, queries)
    live = _serve(engine.live(8), queries)
    np.testing.assert_array_equal(frozen[0], live[0])
    np.testing.assert_array_equal(frozen[1], live[1])
    assert frozen[2] == live[2]


# ---------------------------------------------------------------------------
# property: ANY churn interleaving bit-matches the cold rebuild (hypothesis)
# ---------------------------------------------------------------------------
def test_any_churn_interleaving_bitmatches_cold_rebuild(served):
    """Property over the whole op space the matrix above samples: for any
    interleaving of upsert / delete / compact batches (ids overlapping,
    beyond-base, re-deleted; delta overflow auto-compacting mid-sequence)
    the live catalog serves bit-identically to a cold rebuild of the
    final table. Row payloads are a deterministic function of (id, salt),
    so every example is exactly reproducible from its shrunk form."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    engine, data = served
    d = engine.cfg.embed_dim
    queries = list(_queries(data, np.arange(15) % 60))

    ids_st = st.lists(st.integers(0, 99), min_size=1, max_size=4,
                      unique=True)
    op_st = st.one_of(
        st.tuples(st.just("upsert"), ids_st, st.integers(0, 2**16)),
        st.tuples(st.just("delete"), ids_st, st.just(0)),
        st.tuples(st.just("compact"), st.just([]), st.just(0)),
    )

    def row(gid, salt):
        return np.random.default_rng([int(gid), int(salt)]).normal(
            size=(d,)).astype(np.float32)

    @settings(max_examples=10, deadline=None)
    @given(ops=st.lists(op_st, min_size=1, max_size=6))
    def run(ops):
        cat = LiveCatalog(engine, delta_capacity=8)
        for kind, ids, salt in ops:
            if kind == "upsert":
                cat.upsert(ids, np.stack([row(g, salt) for g in ids]))
            elif kind == "delete":
                cat.delete(ids)
            else:
                cat.compact()
        _assert_matches_reference(cat, queries)

    run()
