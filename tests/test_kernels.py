"""Per-kernel allclose tests: Pallas (interpret=True) vs ref.py oracles,
swept across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import quantize_rowwise
from repro.kernels import ops, ref
from repro.kernels.embedding_pool import embedding_pool_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hamming_nns import hamming_distances_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.streaming_nns import streaming_nns_pallas


def _sig_pair(key, q, n, words):
    kq, kd = jax.random.split(key)
    queries = jax.random.randint(kq, (q, words), 0, 2**31 - 1).astype(jnp.uint32)
    db = jax.random.randint(kd, (n, words), 0, 2**31 - 1).astype(jnp.uint32)
    return queries, db


# ---------------------------------------------------------------------------
# hamming_nns
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,n,words", [(1, 16, 8), (8, 100, 8), (5, 1025, 4), (3, 2048, 1)])
def test_hamming_kernel_vs_ref(key, q, n, words):
    queries, db = _sig_pair(key, q, n, words)
    want = ref.hamming_distance_ref(queries, db)
    got = hamming_distances_pallas(queries, db, block_q=4, block_n=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hamming_block_sizing_never_rounds_past_lane_padding(key):
    """Regression: n=300 used to get a 512 block via next-pow2 rounding;
    the block must stay within the 128-lane-aligned row count."""
    assert ops._hamming_block_n(300) == 384
    assert ops._hamming_block_n(100) == 128
    assert ops._hamming_block_n(1) == 128
    assert ops._hamming_block_n(5000) == 1024
    for n in (1, 100, 130, 300, 1023):
        block = ops._hamming_block_n(n)
        assert block % 128 == 0
        assert block - n < 128 or n < 128  # never a whole wasted lane-row
    # and the sized interpret path still matches the oracle at n=300
    queries, db = _sig_pair(key, 3, 300, 8)
    want = ref.hamming_distance_ref(queries, db)
    got = hamming_distances_pallas(
        queries, db, block_n=ops._hamming_block_n(300), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# streaming_nns
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,n,words,radius,K,block_n", [
    (3, 100, 8, 110, 8, 32),    # matches overflow the buffer
    (5, 1000, 4, 60, 16, 128),  # multi-block, ~40% match rate
    (2, 257, 1, 12, 300, 64),   # K > n, blocks don't divide n
    (4, 37, 8, 128, 12, 7),     # db smaller than one lane row
    (1, 64, 8, 0, 4, 64),       # radius 0: only exact duplicates
])
def test_streaming_nns_kernel_vs_ref(key, q, n, words, radius, K, block_n):
    """Pallas interpret path == lax.scan oracle, bit-exact, all fields."""
    queries, db = _sig_pair(key, q, n, words)
    want = ref.streaming_nns_ref(queries, db, radius, K, scan_block=block_n)
    got = streaming_nns_pallas(
        queries, db, jnp.int32(n), radius=radius, max_candidates=K,
        block_q=4, block_n=block_n, interpret=True)
    for g, w, name in zip(got, want, ("indices", "distances", "counts")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_streaming_nns_kernel_n_valid_masks_tail(key):
    """Dynamic n_valid: rows >= n_valid never match, in kernel and oracle."""
    queries, db = _sig_pair(key, 2, 96, 2)
    want = ref.streaming_nns_ref(queries, db, 40, 10, scan_block=32,
                                 n_valid=61)
    got = streaming_nns_pallas(
        queries, db, jnp.int32(61), radius=40, max_candidates=10,
        block_n=32, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert (np.asarray(got[0]) < 61).all()


def test_streaming_past_packed_key_capacity():
    """Regression for the 4.19M-row cap: DBs beyond the packed-key index
    capacity used to raise; they now scan as multiple superblocks (wide
    keys) in both the oracle and the kernel — shape-level check here, value
    equivalence in the superblock tests below and the benchmark sweep."""
    from repro.kernels.streaming_nns import max_streamable_items

    assert max_streamable_items(8) == 1 << 22  # 256-bit sigs: 4.19M rows/sb
    wide = jax.ShapeDtypeStruct(((1 << 22) + 129, 8), jnp.uint32)
    q = jax.ShapeDtypeStruct((2, 8), jnp.uint32)
    idx, dist, cnt = jax.eval_shape(
        lambda qq, d: ref.streaming_nns_ref(qq, d, 10, 4), q, wide)
    assert idx.shape == (2, 4) and cnt.shape == (2,)
    idx, dist, cnt, stats = jax.eval_shape(
        lambda qq, d: streaming_nns_pallas(
            qq, d, jnp.int32(d.shape[0]), radius=10, max_candidates=4),
        q, wide)
    assert idx.shape == (2, 4) and idx.dtype == jnp.int32
    assert stats.shape == (3,) and stats.dtype == jnp.int32


@pytest.mark.parametrize("superblock,block_n", [(256, 128), (512, 256),
                                                (384, 128)])
def test_streaming_nns_kernel_superblocks_vs_ref(key, superblock, block_n):
    """Wide-key path: multiple superblocks with host-side merge must
    bit-match the oracle run at a DIFFERENT superblock split (results are
    superblock-invariant) and at the default (single-superblock) split."""
    queries, db = _sig_pair(key, 5, 1111, 8)
    want = ref.streaming_nns_ref(queries, db, 105, 12, scan_block=256)
    for sb_ref in (None, 128):
        got_ref = ref.streaming_nns_ref(queries, db, 105, 12, scan_block=96,
                                        superblock=sb_ref)
        for g, w in zip(got_ref, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    got = streaming_nns_pallas(
        queries, db, jnp.int32(1111), radius=105, max_candidates=12,
        block_q=4, block_n=block_n, superblock=superblock, interpret=True)
    for g, w, name in zip(got, want, ("indices", "distances", "counts")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_streaming_nns_kernel_n_valid_across_superblocks(key):
    """Dynamic n_valid landing mid-superblock masks the tail exactly."""
    queries, db = _sig_pair(key, 3, 700, 8)
    want = ref.streaming_nns_ref(queries, db, 110, 8, scan_block=64,
                                 n_valid=389)
    got = streaming_nns_pallas(
        queries, db, jnp.int32(389), radius=110, max_candidates=8,
        block_n=128, superblock=256, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert (np.asarray(got[0]) < 389).all()


def _at_distances(query, dists, rng):
    """Rows at exactly Hamming distance `dists[i]` from `query` (words,)."""
    words = query.shape[0]
    rank = rng.random((len(dists), 32 * words)).argsort(1).argsort(1)
    flips = (rank < np.asarray(dists)[:, None]).reshape(len(dists), words, 32)
    packed = (flips.astype(np.uint64) << np.arange(32, dtype=np.uint64))
    return query[None, :] ^ packed.sum(-1).astype(np.uint32)


def _edge_dists(k, entering):
    """Block 0 fills a K = `k` buffer at distance 50, block 1 brings
    `entering` keys below it, block 2 one more, block 3 none (128 rows a
    block, 200 = no match)."""
    blocks = [[50] * k, [10] * entering, [5], []]
    return np.concatenate([b + [200] * (128 - len(b)) for b in blocks])


# name: (row distances from the base query, K, block_n, superblock, extra,
#        expected stats for 8 identical queries or None)
_MERGE_CASES = {
    # the gate shuts after the first block: no later key is below the K-th
    "ascending": (lambda r: np.sort(r.integers(0, 100, 1024)), 50, 128,
                  None, False, (8, 1, 50)),
    # every block brings 128 entering keys > K: K insertion rounds a block
    "descending": (lambda r: np.sort(r.integers(0, 100, 1024))[::-1], 50,
                   128, None, False, (8, 8, 400)),
    # later rows at the K-th distance never enter
    "ties_at_kth": (lambda r: np.r_[[20] * 40, [30] * 984], 50, 128, None,
                    False, (8, 1, 50)),
    "superblocks": (lambda r: r.integers(0, 130, 1000), 12, 128, 256, False,
                    None),
    "masked_pruned_n_valid": (
        lambda r: np.where(np.arange(2048) // 256 % 3 == 1, 180,
                           r.integers(0, 120, 2048)), 50, 128, None, True,
        None),
    "k1": (lambda r: r.integers(0, 130, 1500), 1, 256, None, False, None),
    "k50": (lambda r: r.integers(0, 130, 1500), 50, 256, None, False, None),
    # K fills the 128 buffer lanes: no slot past K to push keys into
    "k128": (lambda r: r.integers(0, 130, 1500), 128, 256, None, False,
             None),
    # entering counts at the round cap's edges: m = 1 and m = K take m
    # rounds, m = K + 1 takes K (its largest key cannot reach the top K)
    "edge_at_k": (lambda r: _edge_dists(8, 8), 8, 128, None, False,
                  (4, 3, 17)),
    "edge_past_k": (lambda r: _edge_dists(8, 9), 8, 128, None, False,
                    (4, 3, 17)),
}


@pytest.mark.parametrize("case", sorted(_MERGE_CASES))
@pytest.mark.parametrize("perturbed", [False, True],
                         ids=["same_queries", "perturbed_queries"])
def test_streaming_nns_merge_vs_ref(case, perturbed):
    """The gated insertion merge bit-matches the oracle on row orders that
    drive each of its paths; with 8 identical queries (one full tile)
    its cells / merges / rounds counters are exact."""
    dist_fn, k, block_n, superblock, extra, want_stats = _MERGE_CASES[case]
    rng = np.random.default_rng(sorted(_MERGE_CASES).index(case))
    base = rng.integers(0, 2**32, 8, dtype=np.uint32)
    queries = np.repeat(base[None], 8, axis=0)
    if perturbed:  # rows see slightly different distances
        queries[4:] = _at_distances(base, [3, 5, 1, 7], rng)
    dists = dist_fn(rng)
    db = _at_distances(base, dists, rng)
    n = db.shape[0]
    radius, n_valid, mask, prune, prune_rows = 100, n, None, None, None
    if extra:  # tombstones, a short prefix, and a sound prune mask
        n_valid = n - 77
        mask = rng.random(n) < 0.8
        d = np.asarray(ref.hamming_distance_ref(queries, db))
        hit = (d <= radius) & mask & (np.arange(n) < n_valid)
        prune_rows = 2 * block_n
        prune = ~hit.reshape(8, -1, prune_rows).any(-1)
        assert prune.all(0).any() and not prune.all()
        mask = jnp.asarray(mask)
    queries, db = jnp.asarray(queries), jnp.asarray(db)
    want = ref.streaming_nns_ref(
        queries, db, radius, k, scan_block=block_n, n_valid=n_valid,
        superblock=superblock, db_mask=mask, prune_blocks=prune,
        prune_block_rows=prune_rows)
    got = streaming_nns_pallas(
        queries, db, jnp.int32(n_valid), mask, radius=radius,
        max_candidates=k, block_n=block_n, superblock=superblock,
        prune_blocks=None if prune is None else jnp.asarray(prune),
        prune_block_rows=prune_rows, interpret=True)
    for g, w, name in zip(got, want, ("indices", "distances", "counts")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    cells, merges, rounds = (int(v) for v in np.asarray(got[3]))
    assert merges <= cells and merges <= rounds <= k * merges
    if want_stats is not None and not perturbed:
        assert (cells, merges, rounds) == want_stats
    if prune is not None:  # pruned cells are not scanned
        assert cells < n // block_n


# ---------------------------------------------------------------------------
# ops registry dispatch
# ---------------------------------------------------------------------------
def test_registry_contents_and_modes(monkeypatch):
    assert set(ops.registered_kernels()) >= {
        "hamming_distances", "embedding_pool", "int8_matmul",
        "flash_attention", "streaming_nns"}
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    monkeypatch.setenv("REPRO_PALLAS_HAMMING_DISTANCES", "interpret")
    assert ops.kernel_mode("hamming_distances") == "interpret"
    assert ops.kernel_mode("int8_matmul") == "ref"
    monkeypatch.delenv("REPRO_PALLAS")
    monkeypatch.setenv("REPRO_PALLAS_HAMMING_DISTANCES", "bogus")
    assert ops.kernel_mode("hamming_distances") in ("pallas", "ref")  # auto


def test_registry_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        ops.register_kernel("hamming_distances", ref=lambda: None)


def test_per_op_interpret_override_dispatches_pallas(key, monkeypatch):
    """REPRO_PALLAS_<OP>=interpret runs the real kernel via the interpreter
    and must agree with the ref path bit-for-bit."""
    queries, db = _sig_pair(key, 4, 300, 8)
    want = ops.hamming_distances(queries, db)  # default CPU mode: ref
    monkeypatch.setenv("REPRO_PALLAS_HAMMING_DISTANCES", "interpret")
    got = ops.hamming_distances(queries, db)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# embedding_pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,B,L,block_d", [
    (64, 128, 4, 3, 128),
    (100, 256, 2, 7, 128),
    (16, 512, 3, 2, 512),
])
def test_embedding_pool_kernel_vs_ref(key, n, d, B, L, block_d):
    kt, ki, kw = jax.random.split(key, 3)
    table = quantize_rowwise(jax.random.normal(kt, (n, d)))
    ids = jax.random.randint(ki, (B, L), -1, n)
    weights = jax.random.normal(kw, (B, L))
    want = ref.embedding_pool_ref(table.values, table.scales, ids, weights)
    valid = (ids >= 0).astype(jnp.float32)
    got = embedding_pool_pallas(
        table.values, table.scales, ids, weights * valid,
        block_d=block_d, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_embedding_pool_all_padding(key):
    table = quantize_rowwise(jax.random.normal(key, (8, 128)))
    ids = jnp.full((2, 3), -1, dtype=jnp.int32)
    got = embedding_pool_pallas(
        table.values, table.scales, ids, jnp.zeros((2, 3)), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), 0.0)


# ---------------------------------------------------------------------------
# int8_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (128, 256, 128), (100, 130, 50)])
def test_int8_matmul_kernel_vs_ref(key, m, k, n):
    kx, kw = jax.random.split(key)
    x = jax.random.randint(kx, (m, k), -127, 128).astype(jnp.int8)
    w = jax.random.randint(kw, (k, n), -127, 128).astype(jnp.int8)
    sx = jnp.abs(jax.random.normal(jax.random.key(5), (m, 1))) + 0.01
    sw = jnp.abs(jax.random.normal(jax.random.key(6), (1, n))) + 0.01
    want = ref.int8_matmul_ref(x, w, sx, sw)
    got = int8_matmul_pallas(x, w, sx, sw, block_m=64, block_n=64, block_k=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,sq,sk,d,causal", [
    (2, 128, 128, 64, True),
    (1, 64, 192, 64, True),   # sk > sq, block padding
    (2, 100, 100, 32, False), # non-multiple of block
])
def test_flash_attention_vs_oracle(key, bh, sq, sk, d, causal, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, sq, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (bh, sk, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (bh, sk, d), dtype=jnp.float32)
    want = ref.attention_ref(
        q[None].swapaxes(0, 1), k[None].swapaxes(0, 1), v[None].swapaxes(0, 1),
        causal=causal, q_offset=sk - sq if causal else 0,
    )[:, 0]
    got = flash_attention_pallas(
        q.astype(dtype), k.astype(dtype), v.astype(dtype),
        causal=causal, block_q=64, block_k=64,
        q_offset=sk - sq if causal else 0, interpret=True,
    ).astype(jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_blocked_ref_matches_full_ref(key):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2, 37, 16))
    k = jax.random.normal(kk, (1, 2, 53, 16))
    v = jax.random.normal(kv, (1, 2, 53, 16))
    want = ref.attention_ref(q, k, v, causal=True, q_offset=53 - 37)
    got = ref.blocked_attention_ref(q, k, v, causal=True, q_offset=53 - 37, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
