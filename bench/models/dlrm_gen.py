"""Seeded inputs of the DLRM-DCNv2 configuration.

Everything the cell serves is made here from the configuration's
``model_seed``: the dense weights, the float rows of the 26 tables (in
blocks of ``row_block`` rows over the tables back to back), and the pool
of ranking requests. The module imports nothing of the program under
test, so the plain reference (`dlrm_ref.py`) remakes the same rows
without reading anything the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import subseed

ROW_STD = 0.05  # float rows are N(0, ROW_STD ** 2) before quantizing
CDF_ONE = 2 ** 31 - 1  # Zipf CDFs are held as int32 fixed point


def sizes(cfg: dict) -> dict:
    """The widths every part needs, from configuration `cfg`."""
    d = cfg["embed_dim"]
    width = (len(cfg["bag_sizes"]) + 1) * d
    return {"d": d, "width": width, "rank": cfg["cross_rank"],
            "bottom": [cfg["dense_features"], *cfg["bottom_dims"]],
            "top": [width, *cfg["top_dims"]],
            "layers": cfg["cross_layers"], "bags": list(cfg["bag_sizes"]),
            "rows": list(cfg["held_rows"])}


def _fan_in(key, a: int, b: int):
    return a ** -0.5 * jax.random.normal(key, (a, b), jnp.float32)


def make_weights(cfg: dict) -> dict:
    """Fan-in scaled float weights, zero biases, from ``model_seed``:
    {"bottom": [{w, b}], "cross": [{V, W, b}], "top": [{w, b}]} as host
    arrays (a cross layer's V maps the concatenation to the rank, W back)."""
    s = sizes(cfg)
    key = jax.random.key(subseed(cfg["model_seed"], 31))

    def mlp(tag, dims):
        return [{"w": _fan_in(jax.random.fold_in(key, tag * 100 + i), a, b),
                 "b": jnp.zeros((b,), jnp.float32)}
                for i, (a, b) in enumerate(zip(dims, dims[1:]))]

    cross = [{"V": _fan_in(jax.random.fold_in(key, 200 + 2 * i), s["width"],
                           s["rank"]),
              "W": _fan_in(jax.random.fold_in(key, 201 + 2 * i), s["rank"],
                           s["width"]),
              "b": jnp.zeros((s["width"],), jnp.float32)}
             for i in range(s["layers"])]
    out = {"bottom": mlp(1, s["bottom"]), "cross": cross,
           "top": mlp(3, s["top"])}
    return jax.tree.map(np.asarray, out)


def rows_key(cfg: dict):
    return jax.random.key(subseed(cfg["model_seed"], 33))


@functools.partial(jax.jit, static_argnames=("block", "d"))
def row_block(key, k, *, block: int, d: int):
    """Float rows k * block .. (k + 1) * block of the tables back to back."""
    return ROW_STD * jax.random.normal(jax.random.fold_in(key, k), (block, d),
                                       jnp.float32)


def n_blocks(cfg: dict) -> int:
    return -(-sum(cfg["held_rows"]) // cfg["row_block"])


def row_blocks(cfg: dict):
    """Yield (first row, float rows) for every row block of the tables."""
    key, block = rows_key(cfg), cfg["row_block"]
    for k in range(n_blocks(cfg)):
        yield k * block, row_block(key, k, block=block, d=cfg["embed_dim"])


def _zipf_cdf(n: int, a: float) -> np.ndarray:
    """Ranks 0..n-1 drawn with weight (rank + 1) ** -a, as an int32
    fixed-point CDF (the last entry is `CDF_ONE`)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(w)
    return np.minimum(np.round(cdf / cdf[-1] * CDF_ONE), CDF_ONE).astype(
        np.int32)


@functools.partial(jax.jit, static_argnames=("shape",))
def _draw_ids(key, cdf, *, shape):
    """Zipf ranks through the CDF, scattered over the rows by a seeded
    permutation."""
    ku, kp = jax.random.split(key)
    u = jax.random.randint(ku, shape, 0, CDF_ONE, jnp.int32)
    rank = jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)
    perm = jax.random.permutation(kp, cdf.shape[0]).astype(jnp.int32)
    return perm[rank]


def make_pool(cfg: dict) -> list[dict]:
    """``pool_requests`` ranking requests: each ``dense`` (C, 13) float32
    and ``sparse`` (C, 214) int32 ids, table after table, each id within
    its table's rows held."""
    s = sizes(cfg)
    n, c = cfg["pool_requests"], cfg["candidates"]
    key = jax.random.key(subseed(cfg["model_seed"], 35))
    cols = []
    for t, (rows, bag) in enumerate(zip(s["rows"], s["bags"])):
        cdf = jnp.asarray(_zipf_cdf(rows, cfg["id_zipf"]))
        cols.append(np.asarray(_draw_ids(jax.random.fold_in(key, t), cdf,
                                         shape=(n, c, bag))))
    sparse = np.concatenate(cols, axis=2)
    counts = jnp.floor(cfg["dense_count_mean"] * jax.random.exponential(
        jax.random.fold_in(key, 1000), (n, c, cfg["dense_features"])))
    dense = np.asarray(jnp.log1p(counts), np.float32)
    return [{"dense": dense[i], "sparse": sparse[i]} for i in range(n)]
