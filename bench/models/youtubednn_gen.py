"""Seeded inputs of the YoutubeDNN configurations.

Everything a run serves is made here from the run's seed: synthetic
MovieLens-1M users and watch histories, the float weights (initialised and
trained in one jitted call on the device), the rows of a grown catalog, and
the live-catalog update batch. The module imports nothing of the program
under test, so the plain reference (`youtubednn_ref.py`) rebuilds the same
inputs without touching anything the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import subseed

GENRES = 18
LATENT = 16
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# users
# ---------------------------------------------------------------------------
def synth_movielens(cards: dict, n_items: int, history_len: int,
                    seed: int) -> dict:
    """MovieLens-1M-shaped users: latent genre archetypes, top-biased noisy
    watch histories, next-item labels, demographic features.

    Returns host arrays: one (U,) int32 array per user feature,
    ``history`` (U, H), ``label`` (U,), ``genre`` (U,).
    """
    n_users = int(cards["user_id"])
    rng = np.random.default_rng(subseed(seed, 11))
    centers = rng.normal(size=(GENRES, LATENT))
    item_f = (centers[rng.integers(0, GENRES, n_items)]
              + 0.6 * rng.normal(size=(n_items, LATENT)))
    user_genre = rng.integers(0, GENRES, n_users)
    user_f = centers[user_genre] + 0.5 * rng.normal(size=(n_users, LATENT))
    scores = user_f @ item_f.T + 1.5 * rng.gumbel(size=(n_users, n_items))
    top = np.argpartition(-scores, history_len, axis=1)[:, :history_len + 1]
    order = np.argsort(-np.take_along_axis(scores, top, 1), axis=1)
    seq = np.take_along_axis(top, order, 1).astype(np.int32)
    out = {"history": seq[:, :history_len], "label": seq[:, history_len],
           "genre": user_genre.astype(np.int32)}
    for name, card in sorted(cards.items()):
        out[name] = (np.arange(n_users, dtype=np.int32) if name == "user_id"
                     else rng.integers(0, card, n_users).astype(np.int32))
    return out


def queries(users: dict, idx) -> list[dict]:
    """One serving query dict per user index: the feature scalars, the
    history vector and the genre."""
    names = [k for k in users if k != "label"]
    return [{k: users[k][i] for k in names} for i in np.asarray(idx)]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _mlp_init(key, dims):
    return [{"w": a ** -0.5 * jax.random.normal(jax.random.fold_in(key, i),
                                                (a, b), jnp.float32),
             "b": jnp.zeros((b,), jnp.float32)}
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]


def _mlp(layers, x):
    for i, p in enumerate(layers):
        x = jnp.dot(x, p["w"], precision=HIGHEST) + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def user_tower(params, batch):
    """Float filtering tower (training and catalog centres only)."""
    feats = [params["tables"][k][batch[k]] for k in sorted(params["tables"])]
    hist = batch["history"]
    valid = (hist >= 0).astype(jnp.float32)
    rows = params["item_table"][jnp.maximum(hist, 0)] * valid[..., None]
    feats.append(rows.sum(1) / jnp.maximum(valid.sum(1, keepdims=True), 1.0))
    return _mlp(params["filter_mlp"], jnp.concatenate(feats, -1))


@functools.partial(jax.jit, static_argnames=("sizes",))
def make_weights(users: dict, key, sizes: tuple):
    """Initialise the YoutubeDNN weights and train the filtering tower
    (full-softmax next-item loss, Adam at lr 3e-3), all in one call.

    `sizes` = ((feature, cardinality) pairs, n_items, embed_dim,
    filter_dims, rank_dims, train_steps, train_batch), hashable.
    """
    cards, n_items, d, fdims, rdims, steps, batch = sizes
    k = jax.random.split(key, 6)
    params = {
        "tables": {name: 0.05 * jax.random.normal(
            jax.random.fold_in(k[0], i), (card, d), jnp.float32)
            for i, (name, card) in enumerate(cards)},
        "item_table": 0.05 * jax.random.normal(k[1], (n_items, d)),
        "genre_table": 0.05 * jax.random.normal(k[2], (GENRES, d)),
        "filter_mlp": _mlp_init(k[3], ((len(cards) + 1) * d,) + fdims),
        "rank_mlp": _mlp_init(k[4], (4 * d,) + rdims),
    }
    n_users = users["label"].shape[0]

    def loss(p, b):
        logits = jnp.dot(user_tower(p, b), p["item_table"].T,
                         precision=HIGHEST)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     b["label"][:, None], 1)
        return -jnp.mean(picked)

    def step(carry, i):
        p, m, v = carry
        idx = jax.random.randint(jax.random.fold_in(k[5], i), (batch,), 0,
                                 n_users)
        g = jax.grad(loss)(p, jax.tree.map(lambda a: a[idx], users))
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        t = (i + 1).astype(jnp.float32)
        c1, c2 = 1 - 0.9 ** t, 1 - 0.999 ** t
        p = jax.tree.map(
            lambda a, mm, vv: a - 3e-3 * (mm / c1) / (jnp.sqrt(vv / c2)
                                                      + 1e-8), p, m, v)
        return (p, m, v), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros),
                                     jnp.arange(steps))
    return params


# ---------------------------------------------------------------------------
# a grown catalog and its update batch
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "per_cluster"))
def tail_rows(centers, key, lo, *, chunk: int, per_cluster: int,
              noise: float = 0.25):
    """Float rows `lo .. lo + chunk` of the catalog's grown tail: each the
    embedding of a prototype user plus seeded noise, clusters contiguous
    in id (the catalog generator of the tiered-catalog benchmark)."""
    d = centers.shape[1]
    ids = lo + jnp.arange(chunk)
    c = centers[jnp.minimum(ids // per_cluster, centers.shape[0] - 1)]
    scale = noise * jnp.linalg.norm(c, axis=1, keepdims=True) / d ** 0.5
    return c + scale * jax.random.normal(jax.random.fold_in(key, lo),
                                         (chunk, d))


def catalog_plan(n0: int, n_items: int, clusters: int, chunk: int):
    """(per_cluster, [lo, ...]) of the tail rows `n0 .. n_items`."""
    per_cluster = -(-(n_items - n0) // clusters)
    return per_cluster, list(range(0, n_items - n0, chunk))


def update_batch(centers: np.ndarray, n_items: int, n_updates: int,
                 seed: int):
    """The live catalog's pending batch: a quarter re-embeds of existing
    ids, a quarter new ids, half deletes. Returns (upsert_ids, upsert_rows
    f32, delete_ids)."""
    rng = np.random.default_rng(subseed(seed, 13))
    k = n_updates // 4
    existing = rng.choice(n_items, 3 * k, replace=False)
    upsert_ids = np.concatenate([existing[:k], n_items + np.arange(k)])
    c = centers[rng.integers(0, len(centers), 2 * k)]
    scale = 0.25 * np.linalg.norm(c, axis=1, keepdims=True) / c.shape[1] ** .5
    rows = c + scale * rng.standard_normal(c.shape)
    return upsert_ids, rows.astype(np.float32), existing[k:]
