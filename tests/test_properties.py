"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.lsh import pack_bits, unpack_bits
from repro.core.nns import fixed_radius_nns
from repro.core.quantization import (
    dequantize_blockwise,
    dequantize_rowwise,
    quantize_blockwise,
    quantize_rowwise,
)
from repro.core.topk import threshold_topk
from repro.kernels.ref import hamming_distance_ref
from repro.kernels.streaming_nns import (
    big_key,
    key_shift,
    max_streamable_items,
    pack_key,
    unpack_key,
)

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


@given(
    rows=st.integers(1, 20),
    dim=st.integers(1, 40),
    seed=st.integers(0, 2**16),
    scale=st.floats(1e-3, 1e3),
)
def test_rowwise_quant_error_invariant(rows, dim, seed, scale):
    """|x - dq(q(x))| <= scale/2 elementwise, for any magnitude."""
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=(rows, dim)) * scale,
        dtype=jnp.float32,
    )
    q = quantize_rowwise(x)
    err = jnp.abs(x - dequantize_rowwise(q))
    assert bool(jnp.all(err <= q.scales / 2 + 1e-5 * scale))


@given(
    n=st.integers(1, 300),
    block=st.sampled_from([8, 32, 256]),
    seed=st.integers(0, 2**16),
)
def test_blockwise_roundtrip_shape_invariant(n, block, seed):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n,)), jnp.float32)
    q = quantize_blockwise(x, block=block)
    xd = dequantize_blockwise(q)
    assert xd.shape == x.shape
    assert bool(jnp.all(jnp.abs(x - xd) <= jnp.max(q.scales) / 2 + 1e-6))


@given(words=st.integers(1, 8), n=st.integers(1, 64), seed=st.integers(0, 2**16))
def test_hamming_metric_axioms(words, n, seed):
    """identity, symmetry, triangle inequality on packed codes."""
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(n, words), dtype=np.uint32))
    d = np.asarray(hamming_distance_ref(codes, codes))
    assert (np.diagonal(d) == 0).all()
    assert (d == d.T).all()
    if n <= 16:  # triangle on a subset (O(n^3))
        for i in range(n):
            for j in range(n):
                assert (d[i, j] <= d[i][:, None] + d[:, j][None]).all() or True
                assert d[i, j] <= (d[i] + d[:, j]).min() + 2 * words * 32  # loose
        # exact triangle check
        assert (d[:, :, None] <= d[:, None, :] + d[None, :, :] + 1e-9).all()


@given(
    n=st.integers(2, 100),
    radius=st.integers(0, 64),
    seed=st.integers(0, 2**16),
)
def test_fixed_radius_monotone_in_radius(n, radius, seed):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
    q = codes[:1]
    r1 = fixed_radius_nns(q, codes, radius, max_candidates=8)
    r2 = fixed_radius_nns(q, codes, radius + 5, max_candidates=8)
    assert int(r2.counts[0]) >= int(r1.counts[0])


@given(
    n=st.integers(1, 120),
    q=st.integers(1, 4),
    radius=st.integers(0, 64),
    k=st.integers(1, 40),
    scan_block=st.integers(1, 150),
    seed=st.integers(0, 2**16),
)
def test_streaming_nns_equals_dense_property(n, q, radius, k, scan_block, seed):
    """Streaming NNS returns the identical NNSResult to the dense path for
    any scan_block — including blocks that don't divide n, exceed n, or are
    degenerate (1) — any radius, and any candidate bound."""
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
    queries = jnp.asarray(
        rng.integers(0, 2**32, size=(q, 2), dtype=np.uint32))
    dense = fixed_radius_nns(queries, codes, radius, k, scan_block=0)
    stream = fixed_radius_nns(queries, codes, radius, k,
                              scan_block=scan_block)
    np.testing.assert_array_equal(
        np.asarray(dense.indices), np.asarray(stream.indices))
    np.testing.assert_array_equal(
        np.asarray(dense.distances), np.asarray(stream.distances))
    np.testing.assert_array_equal(
        np.asarray(dense.counts), np.asarray(stream.counts))


# ---------------------------------------------------------------------------
# streaming-NNS packed-key encoding (kernels/streaming_nns.py)
# ---------------------------------------------------------------------------
_WORDS = st.integers(1, 8)


@st.composite
def _dist_row(draw, words):
    """(dist, row) for `words`-word keys, row hitting the capacity
    boundaries often."""
    cap = max_streamable_items(words)
    dist = draw(st.integers(0, 32 * words))
    row = draw(st.one_of(
        st.integers(0, cap - 1),
        st.sampled_from([0, 1, cap // 2, cap - 2, cap - 1])))
    return dist, row


@st.composite
def _key_pairs(draw):
    """(words, dist, row)."""
    words = draw(_WORDS)
    return (words, *draw(_dist_row(words)))


@given(_key_pairs())
def test_key_roundtrip_and_sentinel(pair):
    """pack/unpack round-trips exactly and every valid key is < big_key —
    including the boundary rows 0 and capacity-1 (2**22-1 at words=8)."""
    words, dist, row = pair
    key = pack_key(dist, row, words)
    assert unpack_key(key, words) == (dist, row)
    assert 0 <= key < big_key(words)
    assert key < 2**31  # stays an int32


@given(st.data())
def test_key_total_order_matches_lexicographic(data):
    """key(a) < key(b) iff (dist, row)_a < (dist, row)_b — the packed int32
    compare IS the dense path's (distance, index) tie-break order. Both
    keys share one `words` (one encoding), drawn once."""
    words = data.draw(_WORDS)
    da, ra = data.draw(_dist_row(words))
    db_, rb = data.draw(_dist_row(words))
    assert (pack_key(da, ra, words) < pack_key(db_, rb, words)) == (
        (da, ra) < (db_, rb))


@given(
    n=st.integers(2, 220),
    q=st.integers(1, 3),
    radius=st.integers(0, 64),
    k=st.integers(1, 24),
    scan_block=st.integers(1, 96),
    superblock=st.sampled_from([16, 64, 128]),
    seed=st.integers(0, 2**16),
)
def test_streaming_superblocks_equal_dense_property(n, q, radius, k,
                                                    scan_block, superblock,
                                                    seed):
    """Wide-key invariant: any superblock split (degenerate 1-row
    superblocks included) x any scan_block must return the identical
    NNSResult to the dense path — shard-offset edges, cross-superblock
    distance ties, and buffer overflow all land in this space."""
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
    queries = jnp.asarray(
        rng.integers(0, 2**32, size=(q, 2), dtype=np.uint32))
    dense = fixed_radius_nns(queries, codes, radius, k, scan_block=0)
    wide = fixed_radius_nns(queries, codes, radius, k, scan_block=scan_block,
                            superblock=superblock)
    np.testing.assert_array_equal(
        np.asarray(dense.indices), np.asarray(wide.indices))
    np.testing.assert_array_equal(
        np.asarray(dense.distances), np.asarray(wide.distances))
    np.testing.assert_array_equal(
        np.asarray(dense.counts), np.asarray(wide.counts))


@given(
    k=st.integers(1, 10),
    n=st.integers(1, 50),
    thresh=st.floats(-2, 2),
    seed=st.integers(0, 2**16),
)
def test_threshold_topk_invariants(k, n, thresh, seed):
    scores = jnp.asarray(np.random.default_rng(seed).normal(size=(1, n)), jnp.float32)
    res = threshold_topk(scores, thresh, k)
    s = np.asarray(res.scores[0])
    idx = np.asarray(res.indices[0])
    valid = idx >= 0
    # all returned scores >= threshold and sorted descending
    assert (s[valid] >= thresh).all()
    assert (np.diff(s[valid]) <= 1e-6).all()
    # count consistency
    assert int(res.counts[0]) == int((np.asarray(scores[0]) >= thresh).sum())
    assert valid.sum() == min(k, int(res.counts[0]))


@given(
    bits_n=st.sampled_from([32, 64, 128, 256]),
    rows=st.integers(1, 10),
    seed=st.integers(0, 2**16),
)
def test_pack_unpack_property(bits_n, rows, seed):
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, size=(rows, bits_n)), jnp.int32)
    assert bool(jnp.all(unpack_bits(pack_bits(bits), bits_n) == bits))
