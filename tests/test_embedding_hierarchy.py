import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import (
    embedding_bag,
    init_table,
    lookup,
    multi_table_pool,
    table_to_dense,
)
from repro.core.hierarchy import hierarchical_psum, sharded_embedding_bag, tree_sum
from repro.kernels.ref import embedding_pool_ref
from repro.launch.mesh import make_mesh


def test_lookup_matches_dense(key):
    t = init_table(key, 100, 32)
    dense = table_to_dense(t)
    ids = jnp.array([3, 0, 99, -1])
    out = lookup(t, ids)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(dense[jnp.array([3, 0, 99])]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)


def test_bag_sum_and_mean(key):
    t = init_table(key, 50, 16)
    dense = np.asarray(table_to_dense(t))
    ids = jnp.array([[1, 2, 3, -1], [5, -1, -1, -1]])
    out = np.asarray(embedding_bag(t, ids, mode="sum"))
    want0 = dense[1] + dense[2] + dense[3]
    np.testing.assert_allclose(out[0], want0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[1], dense[5], rtol=1e-5, atol=1e-6)
    mean = np.asarray(embedding_bag(t, ids, mode="mean"))
    np.testing.assert_allclose(mean[0], want0 / 3, rtol=1e-5, atol=1e-6)


def test_weighted_bag(key):
    t = init_table(key, 20, 8)
    dense = np.asarray(table_to_dense(t))
    ids = jnp.array([[0, 1]])
    w = jnp.array([[2.0, -1.0]])
    out = np.asarray(embedding_bag(t, ids, weights=w))
    np.testing.assert_allclose(out[0], 2 * dense[0] - dense[1], rtol=1e-5, atol=1e-6)


def test_multi_table_concat_and_sum(key):
    k1, k2 = jax.random.split(key)
    tables = {"a": init_table(k1, 10, 4), "b": init_table(k2, 10, 4)}
    feats = {"a": jnp.array([[1, -1]]), "b": jnp.array([[2, 3]])}
    cat = multi_table_pool(tables, feats, combine="concat")
    assert cat.shape == (1, 8)
    s = multi_table_pool(tables, feats, combine="sum")
    np.testing.assert_allclose(np.asarray(s), np.asarray(cat[:, :4] + cat[:, 4:]), rtol=1e-6)


def test_tree_sum_matches_sum_any_fanin(key):
    x = jax.random.normal(key, (13, 7))
    for fan in (2, 4, 8):
        np.testing.assert_allclose(
            np.asarray(tree_sum(x, fan)), np.asarray(x.sum(0)), rtol=1e-5, atol=1e-5
        )


def test_hierarchical_psum_single_device(key):
    mesh = make_mesh((1,), ("model",))

    def f(x):
        return hierarchical_psum(x, ("model",))

    y = jax.shard_map(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(), check_vma=False)(
        jnp.ones((4,))
    )
    np.testing.assert_array_equal(np.asarray(y), 1.0)


def test_sharded_embedding_bag_matches_local(key):
    mesh = make_mesh((1,), ("model",))
    t = init_table(key, 64, 16)
    ids = jnp.array([[1, 5, 63, -1], [0, -1, -1, -1]])
    want = embedding_pool_ref(t.values, t.scales, ids)
    got = sharded_embedding_bag(mesh, "model", t, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
