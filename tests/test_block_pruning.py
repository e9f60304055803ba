"""Block-summary pruning: sound bounds, bit-identical pruned scans.

The contract under test (docs/KERNELS.md): `summary_block_bounds` is a
sound lower bound on the Hamming distance from any query to any eligible
row of a DB block, so a pruned streaming scan — on any plan (streaming,
sharded, query-parallel, delta-aware), masked or not — returns the exact
bits of the unpruned scan, while `blocks_touched` reports how much of the
catalog each query actually admitted.

Runs in the CI pallas-interpret lane too: the pruned streaming tests drive
the real kernel body with the per-cell scan/skip operand.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nns import (
    BIG_DIST,
    BlockSummary,
    build_block_summary,
    delta_aware_nns,
    fixed_radius_nns,
    query_parallel_nns,
    sharded_fixed_radius_nns,
    summary_block_bounds,
    update_block_summary,
)
from repro.kernels.ref import hamming_distance_ref
from repro.launch.mesh import make_mesh

WORDS = 8
K = 16
BR = 128  # smallest legal summary granularity: one Pallas lane tile


def _uniform(rng, n):
    return rng.integers(0, 2**32, size=(n, WORDS), dtype=np.uint32)


def _clustered(rng, n_clusters=4, rows_per=BR, flip_positions=20):
    """Blocked clusters: rows of block b are small perturbations of center
    b, with flips confined to `flip_positions` designated bit positions so
    the block OR/AND stays tight (the layout pruning is designed for)."""
    centers = _uniform(rng, n_clusters)
    pos = rng.choice(256, size=flip_positions, replace=False)
    rows = np.repeat(centers, rows_per, axis=0)
    for i in range(rows.shape[0]):
        for p in rng.choice(pos, size=rng.integers(0, 6), replace=False):
            rows[i, p // 32] ^= np.uint32(1) << np.uint32(p % 32)
    queries = centers.copy()
    for i in range(queries.shape[0]):
        p = rng.choice(pos, size=2, replace=False)
        for q in p:
            queries[i, q // 32] ^= np.uint32(1) << np.uint32(q % 32)
    return queries, rows


def _assert_same(pruned, unpruned):
    np.testing.assert_array_equal(np.asarray(pruned.indices),
                                  np.asarray(unpruned.indices))
    np.testing.assert_array_equal(np.asarray(pruned.distances),
                                  np.asarray(unpruned.distances))
    np.testing.assert_array_equal(np.asarray(pruned.counts),
                                  np.asarray(unpruned.counts))


# ---------------------------------------------------------------------------
# the bound itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["uniform", "clustered"])
@pytest.mark.parametrize("masked", [False, True])
def test_bound_is_sound(layout, masked):
    """bound(q, b) <= min over eligible rows r in b of d(q, r) — always."""
    rng = np.random.default_rng(3)
    if layout == "uniform":
        db = _uniform(rng, 4 * BR)
        queries = _uniform(rng, 8)
    else:
        queries, db = _clustered(rng)
    mask = rng.random(db.shape[0]) > 0.3 if masked else None
    summary = build_block_summary(db, BR, db_mask=mask)
    bounds = np.asarray(summary_block_bounds(jnp.asarray(queries), summary))
    d = np.asarray(hamming_distance_ref(queries, db))
    elig = np.ones(db.shape[0], bool) if mask is None else mask
    for b in range(summary.n_blocks):
        sel = elig[b * BR:(b + 1) * BR]
        db_blk = d[:, b * BR:(b + 1) * BR][:, sel]
        true_min = (db_blk.min(axis=1) if db_blk.shape[1]
                    else np.full(d.shape[0], BIG_DIST))
        assert np.all(bounds[:, b] <= true_min), (b, bounds[:, b], true_min)


def test_empty_block_bounds_to_big():
    """A fully-tombstoned block bounds to BIG: always pruned, never wrong."""
    rng = np.random.default_rng(4)
    db = _uniform(rng, 3 * BR)
    mask = np.ones(db.shape[0], bool)
    mask[BR:2 * BR] = False  # block 1 fully dead
    summary = build_block_summary(db, BR, db_mask=mask)
    assert int(summary.n_alive[1]) == 0
    bounds = np.asarray(summary_block_bounds(jnp.asarray(db[:2]), summary))
    assert np.all(bounds[:, 1] == BIG_DIST)
    pruned = fixed_radius_nns(jnp.asarray(db[:2]), jnp.asarray(db), 64, K,
                              db_mask=jnp.asarray(mask), scan_block=24,
                              summary=summary)
    plain = fixed_radius_nns(jnp.asarray(db[:2]), jnp.asarray(db), 64, K,
                             db_mask=jnp.asarray(mask), scan_block=24)
    _assert_same(pruned, plain)


def test_builder_rejects_unaligned_block_rows():
    rng = np.random.default_rng(5)
    with pytest.raises(ValueError, match="multiple of 128"):
        build_block_summary(_uniform(rng, 256), 100)


def test_update_matches_cold_rebuild():
    """The upsert/delete maintenance rule: recomputed blocks bit-match a
    from-scratch build over the same (sigs, mask)."""
    rng = np.random.default_rng(6)
    db = _uniform(rng, 4 * BR + 40)  # ragged tail block
    mask = np.ones(db.shape[0], bool)
    summary = build_block_summary(db, BR, db_mask=mask)
    touched = np.asarray([0, 5, BR + 1, 3 * BR, db.shape[0] - 1])
    db[touched] = _uniform(rng, touched.size)
    mask[[5, 3 * BR]] = False  # tombstones must tighten, not loosen
    upd = update_block_summary(summary, db, mask, touched)
    cold = build_block_summary(db, BR, db_mask=mask)
    for f in ("or_sigs", "and_sigs", "min_pc", "max_pc", "n_alive"):
        np.testing.assert_array_equal(np.asarray(getattr(upd, f)),
                                      np.asarray(getattr(cold, f)),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# pruned == unpruned, bit for bit, across the plan matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scan_block", [24, 256])
@pytest.mark.parametrize("superblock", [None, 256])
@pytest.mark.parametrize("masked", [False, True])
def test_pruned_streaming_bit_matches(scan_block, superblock, masked):
    rng = np.random.default_rng(7)
    queries, db = _clustered(rng)
    n = db.shape[0]
    mask = jnp.asarray(rng.random(n) > 0.25) if masked else None
    n_valid = n - 37
    summary = build_block_summary(db, BR, db_mask=mask, n_valid=n_valid)
    kw = dict(db_mask=mask, scan_block=scan_block, superblock=superblock,
              n_valid=n_valid)
    pruned = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 12, K,
                              summary=summary, **kw)
    plain = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 12, K,
                             **kw)
    _assert_same(pruned, plain)
    assert plain.blocks_touched is None
    touched = np.asarray(pruned.blocks_touched)
    assert touched.shape == (queries.shape[0],)
    assert np.all((touched >= 1) & (touched <= summary.n_blocks))
    # clustered layout + tight radius: each query admits its own block only
    assert np.all(touched < summary.n_blocks)


def test_prune_false_disables_and_drops_counter():
    """Pruning is decided by the summary alone: the same streaming scan
    with a summary prunes and counts blocks, without one it scans every
    block, drops the counter, and returns the same bits."""
    rng = np.random.default_rng(8)
    queries, db = _clustered(rng)
    summary = build_block_summary(db, BR)
    res = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 12, K,
                           scan_block=24, summary=summary)
    assert res.blocks_touched is not None
    plain = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 12, K,
                             scan_block=24, summary=None)
    assert plain.blocks_touched is None
    _assert_same(res, plain)


def test_all_but_one_block_prunes():
    """Adversarial best case: every query matches exactly one cluster —
    every other block's bound exceeds the radius."""
    rng = np.random.default_rng(9)
    queries, db = _clustered(rng, n_clusters=8, flip_positions=12)
    summary = build_block_summary(db, BR)
    pruned = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 8, K,
                              scan_block=BR, summary=summary)
    plain = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 8, K,
                             scan_block=BR)
    _assert_same(pruned, plain)
    assert np.all(np.asarray(pruned.blocks_touched) == 1)


def test_no_block_prunes_on_uniform_noise():
    """Adversarial worst case: uniform random rows saturate the block OR
    (or ~ all-ones, and ~ all-zeros) so no block prunes — outputs still
    match and the counter honestly reports a full scan."""
    rng = np.random.default_rng(10)
    db = _uniform(rng, 4 * BR)
    queries = _uniform(rng, 6)
    summary = build_block_summary(db, BR)
    pruned = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 120, K,
                              scan_block=24, summary=summary)
    plain = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 120, K,
                             scan_block=24)
    _assert_same(pruned, plain)
    assert np.all(np.asarray(pruned.blocks_touched) == summary.n_blocks)


def test_dense_plan_ignores_summary():
    """scan_block=0 forces the dense plan: no pruning, no counter."""
    rng = np.random.default_rng(11)
    queries, db = _clustered(rng)
    summary = build_block_summary(db, BR)
    res = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db), 12, K,
                           scan_block=0, summary=summary)
    assert res.blocks_touched is None


def test_pruned_delta_aware_bit_matches():
    rng = np.random.default_rng(12)
    queries, db = _clustered(rng)
    n = db.shape[0]
    mask = jnp.asarray(rng.random(n) > 0.2)
    summary = build_block_summary(db, BR, db_mask=mask)
    cap = 32
    d_sigs = np.full((cap, WORDS), 0xFFFFFFFF, np.uint32)
    d_ids = np.full((cap,), 2**31 - 1, np.int32)
    d_sigs[:3] = queries[:3]
    d_ids[:3] = np.asarray([n + 5, n + 9, n + 11], np.int32)
    kw = dict(db_mask=mask, scan_block=24)
    pruned = delta_aware_nns(jnp.asarray(queries), jnp.asarray(db),
                             jnp.asarray(d_sigs), jnp.asarray(d_ids),
                             12, K, summary=summary, **kw)
    plain = delta_aware_nns(jnp.asarray(queries), jnp.asarray(db),
                            jnp.asarray(d_sigs), jnp.asarray(d_ids),
                            12, K, **kw)
    _assert_same(pruned, plain)
    assert pruned.blocks_touched is not None


@pytest.mark.parametrize("path", ["sharded", "query_parallel"])
def test_pruned_distributed_bit_matches(path):
    rng = np.random.default_rng(13)
    queries, db = _clustered(rng)
    summary = build_block_summary(db, BR)
    if path == "sharded":
        mesh = make_mesh((1,), ("banks",))
        run = lambda **kw: sharded_fixed_radius_nns(  # noqa: E731
            mesh, "banks", jnp.asarray(queries), jnp.asarray(db), 12, K,
            scan_block=24, **kw)
    else:
        mesh = make_mesh((1,), ("qp",))
        run = lambda **kw: query_parallel_nns(  # noqa: E731
            mesh, "qp", jnp.asarray(queries), jnp.asarray(db), 12, K,
            scan_block=24, **kw)
    pruned = run(summary=summary)
    plain = run()
    _assert_same(pruned, plain)
    touched = np.asarray(pruned.blocks_touched)
    assert np.all((touched >= 1) & (touched <= summary.n_blocks))


def test_sharded_misaligned_summary_falls_back_unpruned():
    """per_shard not a multiple of block_rows: pruning silently disables
    (results match, no counter) instead of mis-mapping blocks to shards."""
    rng = np.random.default_rng(14)
    queries, db = _clustered(rng, n_clusters=3)  # n=384; summary at 256
    summary = build_block_summary(db, 256)
    mesh = make_mesh((1,), ("banks",))
    pruned = sharded_fixed_radius_nns(
        mesh, "banks", jnp.asarray(queries), jnp.asarray(db[:300]), 12, K,
        scan_block=24, summary=summary)
    plain = sharded_fixed_radius_nns(
        mesh, "banks", jnp.asarray(queries), jnp.asarray(db[:300]), 12, K,
        scan_block=24)
    _assert_same(pruned, plain)
    assert pruned.blocks_touched is None


# ---------------------------------------------------------------------------
# randomized property (hypothesis, where available)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(n_rows=st.integers(1, 500), n_queries=st.integers(1, 8),
           radius=st.integers(0, 256), scan_block=st.sampled_from([24, 200]),
           masked=st.booleans(), seed=st.integers(0, 2**16))
    def test_pruned_equals_unpruned_property(n_rows, n_queries, radius,
                                             scan_block, masked, seed):
        rng = np.random.default_rng(seed)
        db = _uniform(rng, n_rows)
        queries = _uniform(rng, n_queries)
        mask = jnp.asarray(rng.random(n_rows) > 0.3) if masked else None
        summary = build_block_summary(db, BR, db_mask=mask)
        kw = dict(db_mask=mask, scan_block=scan_block)
        pruned = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db),
                                  radius, K, summary=summary, **kw)
        plain = fixed_radius_nns(jnp.asarray(queries), jnp.asarray(db),
                                 radius, K, **kw)
        _assert_same(pruned, plain)
        touched = np.asarray(pruned.blocks_touched)
        assert np.all((touched >= 0) & (touched <= summary.n_blocks))
except ImportError:  # pragma: no cover - hypothesis is in the CI image
    pass


# ---------------------------------------------------------------------------
# engine level: a summary prunes without changing a bit
# ---------------------------------------------------------------------------
def test_engine_prune_knob_serves_identically():
    from repro.data import synthetic
    from repro.models import recsys as rs
    from repro.serving import LiveCatalog, MicroBatcher, RecSysEngine

    data = synthetic.make_movielens(n_users=40, n_items=80, history_len=6)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=6)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=16,
                                top_k=5, hot_rows=32)
    assert engine.block_summary is not None

    cat = LiveCatalog(engine, delta_capacity=64)
    rng = np.random.default_rng(0)
    d = cat.engine.item_table_q.shape[1]
    cat.upsert(np.arange(200, 206, dtype=np.int32),
               rng.normal(size=(6, d)).astype(np.float32))
    cat.delete(np.asarray([2, 9], np.int32))
    eng = cat.engine

    # maintained summary bit-matches a cold rebuild over the base table
    cold = build_block_summary(np.asarray(eng.item_sigs),
                               eng.block_summary.block_rows,
                               db_mask=np.asarray(eng.item_mask))
    for f in ("or_sigs", "and_sigs", "min_pc", "max_pc", "n_alive"):
        np.testing.assert_array_equal(np.asarray(getattr(eng.block_summary,
                                                         f)),
                                      np.asarray(getattr(cold, f)),
                                      err_msg=f)

    streaming = dataclasses.replace(eng, scan_block=24)
    queries = synthetic.serving_queries(data, range(12))
    base = None
    for summary in (None, streaming.block_summary):
        e = dataclasses.replace(streaming, block_summary=summary)
        out = MicroBatcher(e, max_batch=6).serve_many(queries)
        items = np.stack([o.items for o in out])
        scores = np.stack([o.scores for o in out])
        if base is None:
            base = (items, scores)
        else:
            np.testing.assert_array_equal(items, base[0])
            np.testing.assert_array_equal(scores, base[1])


@pytest.mark.parametrize("mode", ["sync", "pipelined", "concurrent"])
def test_kernel_counters_reach_the_registry(monkeypatch, mode):
    """A pruned streaming-plan serving run through the Pallas kernel body
    (interpret mode) writes the kernel's cells and merge rounds as the
    `nns.scan_cells` / `nns.merge_cells` / `nns.merge_rounds` counters,
    beside `nns.blocks_touched`, on every front end."""
    from repro.data import synthetic
    from repro.models import recsys as rs
    from repro.serving import RecSysEngine, make_server

    monkeypatch.setenv("REPRO_PALLAS_STREAMING_NNS", "interpret")
    data = synthetic.make_movielens(n_users=30, n_items=300, history_len=5)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=5)
    # the backend is chosen at trace time: drop programs traced with ref
    jax.clear_caches()
    engine = RecSysEngine.build(rs.init_youtubednn(jax.random.key(3), cfg),
                                cfg, radius=109, n_candidates=13, top_k=4,
                                hot_rows=16)
    engine = dataclasses.replace(engine, scan_block=128)
    server = make_server(engine, mode, max_batch=8)
    try:
        server.serve_many(synthetic.serving_queries(data, range(20)))
    finally:
        server.close()
    snap = server.snapshot()
    cells, merges, rounds = (snap.get(f"nns.{name}", 0) for name in
                             ("scan_cells", "merge_cells", "merge_rounds"))
    # buckets of 8, 8 and 4 queries, one kernel tile each, 3 blocks a scan
    assert snap["nns.block_scan_queries"] == 20
    assert 0 < merges <= cells <= 3 * 3
    assert merges <= rounds <= 13 * merges


def test_summary_is_pytree_with_static_block_rows():
    rng = np.random.default_rng(15)
    summary = build_block_summary(_uniform(rng, 2 * BR), BR)
    leaves, treedef = jax.tree.flatten(summary)
    assert len(leaves) == 5
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, BlockSummary)
    assert rebuilt.block_rows == BR  # static metadata survives the pytree
