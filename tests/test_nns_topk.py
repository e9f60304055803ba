import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nns
from repro.core.lsh import lsh_signature, make_lsh_projections
from repro.core.nns import (
    BIG,
    cosine_topk,
    fixed_radius_nns,
    query_parallel_nns,
    sharded_fixed_radius_nns,
)
from repro.core.topk import threshold_topk
from repro.launch.mesh import make_mesh


def _sigs(key, n, dim=16, n_bits=128):
    proj = make_lsh_projections(key, dim, n_bits)
    x = jax.random.normal(jax.random.key(7), (n, dim))
    return x, lsh_signature(x, proj)


def _assert_same_result(a, b):
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    np.testing.assert_array_equal(
        np.asarray(a.distances), np.asarray(b.distances))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))


def test_fixed_radius_exact_semantics(key):
    x, sigs = _sigs(key, 100)
    q = sigs[:3]
    res = fixed_radius_nns(q, sigs, radius=20, max_candidates=50)
    # brute force oracle
    from repro.kernels.ref import hamming_distance_ref

    d = np.asarray(hamming_distance_ref(q, sigs))
    for i in range(3):
        want = set(np.nonzero(d[i] <= 20)[0].tolist())
        got = set(int(j) for j in np.asarray(res.indices[i]) if j >= 0)
        assert int(res.counts[i]) == len(want)
        if len(want) <= 50:
            assert got == want
        # returned distances are within radius and sorted ascending
        dist = np.asarray(res.distances[i])
        valid = dist < 2**30
        assert (dist[valid] <= 20).all()
        assert (np.diff(dist[valid]) >= 0).all()


def test_fixed_radius_self_match(key):
    _, sigs = _sigs(key, 32)
    res = fixed_radius_nns(sigs, sigs, radius=0, max_candidates=4)
    # each item matches at least itself at distance 0
    assert (np.asarray(res.counts) >= 1).all()
    assert (np.asarray(res.distances[:, 0]) == 0).all()


def test_big_sentinel_exported_and_used(key):
    """Invalid candidate slots carry the one exported BIG sentinel."""
    _, sigs = _sigs(key, 20)
    res = fixed_radius_nns(sigs[:2], sigs, radius=0, max_candidates=8)
    invalid = np.asarray(res.indices) < 0
    assert invalid.any()
    assert (np.asarray(res.distances)[invalid] == int(BIG)).all()
    assert int(BIG) == 2**30 and nns._BIG is BIG  # backwards alias


@pytest.mark.parametrize("scan_block", [7, 64, 100, 512])
def test_streaming_matches_dense(key, scan_block):
    """Any scan_block (dividing n or not, larger than n or not) must return
    the identical NNSResult to the dense (q, n) path."""
    _, sigs = _sigs(key, 300)
    q = sigs[:5]
    dense = fixed_radius_nns(q, sigs, radius=30, max_candidates=24,
                             scan_block=0)
    stream = fixed_radius_nns(q, sigs, radius=30, max_candidates=24,
                              scan_block=scan_block)
    _assert_same_result(dense, stream)


def test_streaming_matches_dense_with_n_valid(key):
    _, sigs = _sigs(key, 128)
    dense = fixed_radius_nns(sigs[:3], sigs, radius=28, max_candidates=16,
                             scan_block=0, n_valid=77)
    stream = fixed_radius_nns(sigs[:3], sigs, radius=28, max_candidates=16,
                              scan_block=32, n_valid=77)
    _assert_same_result(dense, stream)
    assert (np.asarray(stream.indices) < 77).all()


def test_auto_routing_by_db_size(key, monkeypatch):
    """scan_block=None picks dense below STREAM_MIN_ITEMS and streaming at or
    above it — verified by spying on the streaming op — and both plans
    agree."""
    from repro.kernels import ops

    calls = []
    real = ops.streaming_nns
    monkeypatch.setattr(
        ops, "streaming_nns",
        lambda *a, **kw: calls.append(kw) or real(*a, **kw))

    _, sigs = _sigs(key, 200)
    q = sigs[:3]
    dense = fixed_radius_nns(q, sigs, radius=30, max_candidates=16)
    assert not calls  # 200 < STREAM_MIN_ITEMS: dense plan
    monkeypatch.setattr(nns, "STREAM_MIN_ITEMS", 64)
    monkeypatch.setattr(nns, "DEFAULT_SCAN_BLOCK", 96)
    auto = fixed_radius_nns(q, sigs, radius=30, max_candidates=16)
    assert len(calls) == 1 and calls[0]["scan_block"] == 96
    _assert_same_result(dense, auto)


def test_streaming_accepts_arbitrary_db_mask(key):
    """The streaming plan carries arbitrary row masks (live-catalog
    tombstones) since PR 5 — bit-matching the dense masked path."""
    _, sigs = _sigs(key, 64)
    mask = jnp.arange(64) % 2 == 0
    want = fixed_radius_nns(sigs[:3], sigs, radius=30, max_candidates=4,
                            db_mask=mask, scan_block=0)
    got = fixed_radius_nns(sigs[:3], sigs, radius=30, max_candidates=4,
                           db_mask=mask, scan_block=16)
    _assert_same_result(want, got)


def test_sharded_matches_unsharded(key):
    """1-device mesh: the sharded path must equal the local path exactly."""
    mesh = make_mesh((1,), ("model",))
    x, sigs = _sigs(key, 64)
    q = sigs[:2]
    local = fixed_radius_nns(q, sigs, radius=25, max_candidates=16)
    shard = sharded_fixed_radius_nns(mesh, "model", q, sigs, radius=25,
                                     max_candidates=16)
    np.testing.assert_array_equal(np.asarray(local.counts), np.asarray(shard.counts))
    np.testing.assert_array_equal(
        np.sort(np.asarray(local.indices), -1), np.sort(np.asarray(shard.indices), -1)
    )


def test_sharded_composes_with_streaming(key):
    """Sharding over devices + streaming within the shard == dense local."""
    mesh = make_mesh((1,), ("model",))
    _, sigs = _sigs(key, 96)
    q = sigs[:3]
    local = fixed_radius_nns(q, sigs, radius=25, max_candidates=16,
                             scan_block=0)
    shard = sharded_fixed_radius_nns(mesh, "model", q, sigs, radius=25,
                                     max_candidates=16, scan_block=17)
    _assert_same_result(local, shard)


def test_query_parallel_matches_local(key):
    """Query-sharded scan (db replicated) == the plain local scan exactly,
    for dense and streaming plans."""
    mesh = make_mesh((1,), ("qp",))
    _, sigs = _sigs(key, 80)
    q = sigs[:5]
    for scan_block in (0, 13, None):
        local = fixed_radius_nns(q, sigs, radius=25, max_candidates=16,
                                 scan_block=scan_block)
        par = query_parallel_nns(mesh, "qp", q, sigs, radius=25,
                                 max_candidates=16, scan_block=scan_block)
        _assert_same_result(local, par)


def test_query_parallel_respects_n_valid(key):
    mesh = make_mesh((1,), ("qp",))
    _, sigs = _sigs(key, 64)
    local = fixed_radius_nns(sigs[:3], sigs, radius=25, max_candidates=8,
                             scan_block=16, n_valid=41)
    par = query_parallel_nns(mesh, "qp", sigs[:3], sigs, radius=25,
                             max_candidates=8, scan_block=16, n_valid=41)
    _assert_same_result(local, par)
    assert (np.asarray(par.indices) < 41).all()


def test_sharded_composes_with_query_axis(key):
    """(query block x bank) 2D partition == the plain local scan."""
    mesh = make_mesh((1, 1), ("qp", "model"))
    _, sigs = _sigs(key, 96)
    q = sigs[:5]
    local = fixed_radius_nns(q, sigs, radius=25, max_candidates=16,
                             scan_block=0)
    both = sharded_fixed_radius_nns(mesh, "model", q, sigs, radius=25,
                                    max_candidates=16, scan_block=17,
                                    query_axis="qp")
    _assert_same_result(local, both)


@pytest.mark.slow
def test_query_parallel_multi_device_subprocess():
    """Real 8-fake-device run: query axis 4 x bank axis 2, query count not
    divisible by the query axis (pad rows sliced off), vs the local scan."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.nns import (fixed_radius_nns, query_parallel_nns,
                                    sharded_fixed_radius_nns)
        from repro.launch.mesh import make_mesh
        rng = np.random.default_rng(0)
        sigs = jnp.asarray(rng.integers(0, 2**32, (256, 8), dtype=np.uint32))
        q = sigs[:10]  # 10 % 4 != 0: exercises query padding
        local = fixed_radius_nns(q, sigs, radius=100, max_candidates=16,
                                 scan_block=0)
        mesh = make_mesh((4,), ("qp",))
        par = query_parallel_nns(mesh, "qp", q, sigs, radius=100,
                                 max_candidates=16, scan_block=32)
        mesh2 = make_mesh((4, 2), ("qp", "banks"))
        both = sharded_fixed_radius_nns(mesh2, "banks", q, sigs, radius=100,
                                        max_candidates=16, scan_block=32,
                                        query_axis="qp")
        for got in (par, both):
            np.testing.assert_array_equal(np.asarray(local.indices),
                                          np.asarray(got.indices))
            np.testing.assert_array_equal(np.asarray(local.distances),
                                          np.asarray(got.distances))
            np.testing.assert_array_equal(np.asarray(local.counts),
                                          np.asarray(got.counts))
        print("MARKER qp ok", jax.device_count())
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
    assert "MARKER qp ok 8" in out.stdout


def test_cosine_topk_oracle(key):
    x = jax.random.normal(key, (50, 8))
    q = x[:2] + 0.01
    vals, idx = cosine_topk(q, x, k=1)
    np.testing.assert_array_equal(np.asarray(idx[:, 0]), np.array([0, 1]))


def test_threshold_topk(key):
    scores = jnp.array([[0.1, 0.9, 0.5, 0.95, 0.2]])
    res = threshold_topk(scores, threshold=0.4, k=3)
    assert int(res.counts[0]) == 3
    np.testing.assert_array_equal(np.asarray(res.indices[0]), [3, 1, 2])
    res2 = threshold_topk(scores, threshold=0.99, k=3)
    assert int(res2.counts[0]) == 0
    assert (np.asarray(res2.indices[0]) == -1).all()
