"""Scenario test matrix for the filtering-stage NNS execution plans.

Every (path x scenario) cell is checked bit-for-bit against an independent
numpy oracle (threshold + lexicographic (distance, row) sort) — not against
another jax path — so a shared bug between plans cannot hide. The matrix
runs under whatever backend `REPRO_PALLAS` selects: the CI pallas-interpret
job replays it through the real Pallas kernel bodies, the fast lane through
the jnp oracles.

Paths: dense (q, n) matrix | streaming scan (superblock-split wide keys) |
db-sharded shard_map | query-parallel shard_map.
Scenarios: the edges that historically break bounded-candidate scans —
empty n_valid prefix, a single-row DB/shard, non-lane-aligned row counts,
duplicate signatures (distance ties), and a radius admitting every row
(candidate-buffer overflow).

Deterministic wide-key boundary tests (superblock offsets, tie order,
threshold inclusivity, beyond-cap scan blocks) live here too so they run
even where hypothesis is unavailable; the randomized versions are in
tests/test_properties.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nns import (
    fixed_radius_nns,
    query_parallel_nns,
    sharded_fixed_radius_nns,
)
from repro.kernels.ref import hamming_distance_ref
from repro.kernels.streaming_nns import (
    BIG_DIST,
    big_key,
    max_streamable_items,
    pack_key,
    unpack_key,
)
from repro.launch.mesh import make_mesh

WORDS = 2
K = 16


def _oracle(queries, db, radius, k, n_valid=None):
    """Brute-force numpy fixed-radius NNS: the matrix's ground truth."""
    d = np.asarray(hamming_distance_ref(queries, db))
    n = db.shape[0]
    nv = n if n_valid is None else n_valid
    rows = np.arange(n)
    idxs, dists, cnts = [], [], []
    for i in range(queries.shape[0]):
        within = (d[i] <= radius) & (rows < nv)
        m = np.nonzero(within)[0]
        m = m[np.lexsort((m, d[i][m]))][:k]  # (distance, row) ascending
        pad = k - len(m)
        idxs.append(np.concatenate([m, np.full(pad, -1)]).astype(np.int32))
        dists.append(np.concatenate(
            [d[i][m], np.full(pad, BIG_DIST)]).astype(np.int32))
        cnts.append(within.sum())
    return (np.stack(idxs), np.stack(dists), np.asarray(cnts, np.int32))


def _scenario(name):
    """-> (queries, db, radius, n_valid)."""
    rng = np.random.default_rng(17)

    def sigs(n):
        return rng.integers(0, 2**32, size=(n, WORDS), dtype=np.uint32)

    if name == "n_valid_zero":
        db = sigs(96)
        return db[:4], db, 30, 0
    if name == "single_row_shard":
        db = sigs(1)  # one row total: a 1-device mesh sees a 1-row shard
        return sigs(3), db, 64, None
    if name == "non_aligned_n":
        db = sigs(300)  # not a multiple of the 128-lane row tile
        return db[:5], db, 28, 211
    if name == "duplicate_signatures":
        db = np.tile(sigs(5), (8, 1))  # 40 rows, every distance 8-way tied
        return db[:3], db, 40, None
    if name == "radius_overflow":
        # every row within radius (max dist = 64 at words=2): the bounded
        # candidate buffer overflows and must keep the best K by (dist, row)
        db = sigs(200)
        return db[:4], db, 32 * WORDS, None
    raise AssertionError(name)


SCENARIOS = ("n_valid_zero", "single_row_shard", "non_aligned_n",
             "duplicate_signatures", "radius_overflow")
PATHS = ("dense", "streaming", "sharded", "query_parallel")


def _run(path, queries, db, radius, n_valid):
    queries, db = jnp.asarray(queries), jnp.asarray(db)
    if path == "dense":
        return fixed_radius_nns(queries, db, radius, K, scan_block=0,
                                n_valid=n_valid)
    if path == "streaming":
        # superblock < n in the bigger scenarios: exercises the wide-key
        # split + host-side merge inside the matrix
        return fixed_radius_nns(queries, db, radius, K, scan_block=24,
                                n_valid=n_valid, superblock=128)
    if path == "sharded":
        mesh = make_mesh((1,), ("banks",))
        return sharded_fixed_radius_nns(
            mesh, "banks", queries, db, radius, K, n_valid=n_valid,
            scan_block=16)
    if path == "query_parallel":
        mesh = make_mesh((1,), ("qp",))
        return query_parallel_nns(mesh, "qp", queries, db, radius, K,
                                  scan_block=16, n_valid=n_valid)
    raise AssertionError(path)


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("path", PATHS)
def test_nns_matrix(path, scenario):
    queries, db, radius, n_valid = _scenario(scenario)
    want_idx, want_dist, want_cnt = _oracle(queries, db, radius, K, n_valid)
    res = _run(path, queries, db, radius, n_valid)
    np.testing.assert_array_equal(np.asarray(res.indices), want_idx,
                                  err_msg=f"{path}/{scenario} indices")
    np.testing.assert_array_equal(np.asarray(res.distances), want_dist,
                                  err_msg=f"{path}/{scenario} distances")
    np.testing.assert_array_equal(np.asarray(res.counts), want_cnt,
                                  err_msg=f"{path}/{scenario} counts")


# ---------------------------------------------------------------------------
# deterministic wide-key boundary checks
# ---------------------------------------------------------------------------
def test_key_capacity_boundary_is_exact():
    """Row 2**22-1 packs at words=8; row 2**22 must NOT round-trip in one
    key (it aliases dist+1, row 0) — which is exactly why DBs past the
    capacity scan as offset superblocks."""
    cap = max_streamable_items(8)
    assert cap == 1 << 22
    assert unpack_key(pack_key(0, cap - 1, 8), 8) == (0, cap - 1)
    assert unpack_key(pack_key(0, cap, 8), 8) == (1, 0)  # the alias
    assert pack_key(32 * 8, cap - 1, 8) < big_key(8) < 2**31


def test_degenerate_superblocks_equal_dense():
    """1- and 2-row superblocks (every row its own candidate buffer)."""
    rng = np.random.default_rng(11)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(7, 2), dtype=np.uint32))
    dense = fixed_radius_nns(codes[:2], codes, 30, 4, scan_block=0)
    for sb in (1, 2):
        wide = fixed_radius_nns(codes[:2], codes, 30, 4, scan_block=3,
                                superblock=sb)
        np.testing.assert_array_equal(
            np.asarray(dense.indices), np.asarray(wide.indices))
        np.testing.assert_array_equal(
            np.asarray(dense.counts), np.asarray(wide.counts))


def test_superblock_boundary_ties_keep_global_order():
    """Duplicate signatures straddling a superblock boundary: equal
    distances must come back in ascending GLOBAL row order even though the
    local key of the later superblock's row is smaller."""
    sb = 16
    row = np.asarray([0xdeadbeef, 0x1234], np.uint32)
    db = np.zeros((40, 2), np.uint32)
    db[sb - 1] = row  # local key sb-1 in superblock 0
    db[sb] = row      # local key 0 in superblock 1 — smaller local key!
    db[2 * sb] = row  # superblock 2
    res = fixed_radius_nns(jnp.asarray(row[None]), jnp.asarray(db),
                           radius=0, max_candidates=4, scan_block=4,
                           superblock=sb)
    np.testing.assert_array_equal(np.asarray(res.indices[0]),
                                  [sb - 1, sb, 2 * sb, -1])
    assert int(res.counts[0]) == 3


def test_radius_threshold_is_inclusive_at_the_boundary():
    """dist == radius matches, dist == radius+1 does not — across a
    superblock split so the threshold compare is exercised in the wide
    merge too."""
    base = np.asarray([0, 0], np.uint32)
    db = np.zeros((24, 2), np.uint32)
    db[5] = [0b111, 0]       # dist 3
    db[17] = [0b1111, 0]     # dist 4 (superblock 2 at sb=8)
    res = fixed_radius_nns(jnp.asarray(base[None]), jnp.asarray(db),
                           radius=3, max_candidates=24, scan_block=4,
                           superblock=8, n_valid=18)
    idx = set(int(i) for i in np.asarray(res.indices[0]) if i >= 0)
    assert 5 in idx and 17 not in idx  # 17 is outside the radius
    zeros = {i for i in range(18)} - {5, 17}
    assert zeros <= idx  # every dist-0 row within n_valid matched


def test_streaming_equals_dense_beyond_old_scan_block_cap():
    """scan_block larger than the old 4.19M-row packed-key cap: the chunk
    padding overflows the per-superblock row budget and must still decode
    exactly (masked pad rows never pack keys)."""
    rng = np.random.default_rng(3)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(64, 8), dtype=np.uint32))
    dense = fixed_radius_nns(codes[:2], codes, 100, 8, scan_block=0)
    stream = fixed_radius_nns(codes[:2], codes, 100, 8,
                              scan_block=(1 << 22) + 17)
    np.testing.assert_array_equal(
        np.asarray(dense.indices), np.asarray(stream.indices))
    np.testing.assert_array_equal(
        np.asarray(dense.distances), np.asarray(stream.distances))
    np.testing.assert_array_equal(
        np.asarray(dense.counts), np.asarray(stream.counts))


# ---------------------------------------------------------------------------
# benchmark artifact row schema (benchmarks/bench_io.py)
# ---------------------------------------------------------------------------
def test_bench_row_schema_accepts_uniform_rows():
    from benchmarks.bench_io import check_row_schema, csv_rows_to_json

    rows = csv_rows_to_json([
        ("nns_scale/streaming/n1024", 1.5, "qps=100.0;mem_lt_10pct_dense=True"),
        ("nns_scale/streaming/n2048", 2.5, "qps=50.0;mem_lt_10pct_dense=False"),
        ("nns_scale/dense/n4096", 0.0, "status=skipped_oom_guard;dense_bytes=1"),
    ])
    check_row_schema(rows, required=("qps",),
                     within=("nns_scale/streaming/", "nns_scale/dense/"))


def test_bench_row_schema_rejects_dropped_metric():
    """The satellite-2 regression shape: one cell of a sweep silently
    missing a metric its mates emit (mem_lt_10pct_dense used to appear on
    the `streaming` path only) must fail the schema gate."""
    from benchmarks.bench_io import check_row_schema, csv_rows_to_json

    rows = csv_rows_to_json([
        ("b/stream/n1", 1.0, "qps=9.0;mem_lt_10pct_dense=True"),
        ("b/stream/n2", 1.0, "qps=8.0"),  # metric silently dropped
    ])
    with pytest.raises(ValueError, match="inconsistent derived schemas"):
        check_row_schema(rows, within=("b/stream/",))
    # failed cells are exempt from group consistency
    rows[1]["derived"] = "status=failed"
    check_row_schema(rows, within=("b/stream/",))


def test_bench_row_schema_rejects_malformed_rows():
    from benchmarks.bench_io import check_row_schema

    with pytest.raises(ValueError, match="not key=value"):
        check_row_schema([{"name": "x", "us_per_call": 1.0,
                           "derived": "qps100"}])
    with pytest.raises(ValueError, match="keys"):
        check_row_schema([{"name": "x", "us_per_call": 1.0}])
    with pytest.raises(ValueError, match="missing required"):
        check_row_schema([{"name": "x", "us_per_call": 1.0,
                           "derived": "qps=1.0"}], required=("rss_delta",))


def test_nns_scale_rows_carry_memory_metric_on_all_streaming_cells():
    """`_derived` + `_cell` row schema: the zipf cells emit the same
    memory metric as the plain streaming cell (the fixed asymmetry)."""
    from benchmarks.nns_scale import _derived

    row = {"qps": 10.0, "rss_peak_delta_bytes": 5, "dense_matrix_bytes": 100,
           "mem_lt_10pct_dense": True}
    assert "mem_lt_10pct_dense=True" in _derived(row)
