"""The paper's two RecSys instances: YoutubeDNN (filtering + ranking) and
Facebook DLRM (ranking). Sec. II-A / Fig. 1. DLRM's config also states
DLRM-DCNv2, the MLPerf recommendation model: multi-hot bags per table and
a low-rank cross network in place of the pairwise dots.

Training uses dense fp32 embedding tables; serving quantizes every table to
int8 (core.quantization) and runs lookups/pooling through the fused kernel
path (core.embedding) plus LSH+Hamming NNS for the filtering stage — exactly
the paper's deployment flow (Sec. III-B).
"""
from __future__ import annotations

from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import fold_key

EMBED_DIM = 32  # the paper's ET dimension (32 x int8 = one 256-bit CMA row)


def _mlp_init(key, dims, dtype=jnp.float32):
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        k = jax.random.fold_in(key, i)
        layers.append({
            "w": (a**-0.5 * jax.random.normal(k, (a, b))).astype(dtype),
            "b": jnp.zeros((b,), dtype),
        })
    return layers


def _mlp_apply(layers, x, final_act=False):
    for i, p in enumerate(layers):
        x = x @ p["w"] + p["b"]
        if i < len(layers) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


# ---------------------------------------------------------------------------
# YoutubeDNN
# ---------------------------------------------------------------------------
class YoutubeDNNConfig(NamedTuple):
    n_items: int = 3000
    user_features: Mapping[str, int] = None  # name -> cardinality
    history_len: int = 20
    filter_dims: tuple = (128, 64, 32)  # paper Table I
    rank_dims: tuple = (128, 1)
    embed_dim: int = EMBED_DIM


def default_youtubednn_config() -> YoutubeDNNConfig:
    return YoutubeDNNConfig(
        user_features={
            "user_id": 6040, "gender": 3, "age": 7, "occupation": 21,
            "zip_bucket": 250,
        },
    )


def init_youtubednn(key, cfg: YoutubeDNNConfig) -> dict:
    p = {"tables": {}, "genre_table": None}
    for name, card in sorted(cfg.user_features.items()):
        k = fold_key(key, "table", name)
        p["tables"][name] = 0.05 * jax.random.normal(k, (card, cfg.embed_dim))
    p["item_table"] = 0.05 * jax.random.normal(
        fold_key(key, "items"), (cfg.n_items, cfg.embed_dim))
    # ranking-only UIET (genre) — Table I: 6 ranking UIETs, 5 shared
    p["genre_table"] = 0.05 * jax.random.normal(
        fold_key(key, "genre"), (18, cfg.embed_dim))
    n_feats = len(cfg.user_features) + 1  # + pooled history
    p["filter_mlp"] = _mlp_init(
        fold_key(key, "fmlp"), (n_feats * cfg.embed_dim,) + cfg.filter_dims)
    # ranking input: user emb (32) + item emb (32) + genre (32) + ctx -> 128
    p["rank_mlp"] = _mlp_init(
        fold_key(key, "rmlp"), (4 * cfg.embed_dim,) + cfg.rank_dims)
    return p


def user_tower(p, cfg: YoutubeDNNConfig, batch: dict) -> jax.Array:
    """Filtering stage DNN: returns the user embedding u_i (B, 32)."""
    feats = []
    for name in sorted(cfg.user_features.keys()):
        feats.append(p["tables"][name][batch[name]])  # (B, d)
    hist = batch["history"]  # (B, H) item ids, -1 padded
    valid = (hist >= 0).astype(jnp.float32)
    rows = p["item_table"][jnp.maximum(hist, 0)] * valid[..., None]
    pooled = rows.sum(1) / jnp.maximum(valid.sum(1, keepdims=True), 1.0)
    feats.append(pooled)
    x = jnp.concatenate(feats, axis=-1)
    return _mlp_apply(p["filter_mlp"], x)


def filtering_loss(p, cfg: YoutubeDNNConfig, batch: dict) -> jax.Array:
    """Full softmax over the item vocabulary against the next-watched item."""
    u = user_tower(p, cfg, batch)  # (B, d)
    logits = u @ p["item_table"].T  # (B, n_items)
    return -jnp.mean(
        jax.nn.log_softmax(logits)[jnp.arange(u.shape[0]), batch["label"]]
    )


def rank_tower(p, cfg: YoutubeDNNConfig, batch: dict,
               item_ids: jax.Array) -> jax.Array:
    """Ranking stage: CTR logits for each (user, candidate) pair.

    item_ids: (B, N) candidate ids. Returns (B, N) logits.
    """
    u = user_tower(p, cfg, batch)  # (B, d)
    items = p["item_table"][item_ids]  # (B, N, d)
    genre = p["genre_table"][batch["genre"]]  # (B, d)
    hist = batch["history"]
    valid = (hist >= 0).astype(jnp.float32)
    rows = p["item_table"][jnp.maximum(hist, 0)] * valid[..., None]
    pooled = rows.sum(1) / jnp.maximum(valid.sum(1, keepdims=True), 1.0)
    B, N = item_ids.shape
    ctx = jnp.concatenate([u, genre, pooled], axis=-1)  # (B, 3d)
    x = jnp.concatenate(
        [jnp.broadcast_to(ctx[:, None], (B, N, ctx.shape[-1])), items], -1)
    return _mlp_apply(p["rank_mlp"], x)[..., 0]  # (B, N)


def ranking_loss(p, cfg: YoutubeDNNConfig, batch: dict) -> jax.Array:
    logits = rank_tower(p, cfg, batch, batch["cand_items"])  # (B, N)
    labels = batch["cand_labels"].astype(jnp.float32)  # (B, N) clicks
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


# ---------------------------------------------------------------------------
# DLRM (ranking on Criteo)
# ---------------------------------------------------------------------------
class DLRMConfig(NamedTuple):
    """DLRM's shape. The defaults are the paper's (Table I): 26 one-hot
    tables of 28,000 rows at dim 32 and the pairwise-dot interaction.
    DLRM-DCNv2 (`bench/configs/dlrm-dcnv2-criteo1tb.json`) sets rows and
    bag sizes per table and ``interaction="cross"``: `cross_layers`
    low-rank layers of rank `cross_rank` over the concatenation of the
    bottom MLP's output and the pooled bags (arXiv 2008.13535)."""

    n_dense: int = 13
    n_sparse: int = 26
    cardinality: int = 28000  # rows of every ET (Table I), unless table_rows
    embed_dim: int = EMBED_DIM
    bottom_dims: tuple = (256, 128, 32)  # paper Table I
    top_dims: tuple = (256, 64, 1)
    table_rows: tuple | None = None  # rows of each ET
    bag_sizes: tuple | None = None  # ids sum-pooled from each ET (1: one-hot)
    interaction: str = "dot"  # "dot" (pairwise dots) | "cross" (DCNv2)
    cross_layers: int = 3
    cross_rank: int = 512

    @property
    def rows(self) -> tuple:
        return self.table_rows or (self.cardinality,) * self.n_sparse

    @property
    def bags(self) -> tuple:
        return self.bag_sizes or (1,) * self.n_sparse


def init_dlrm_dense(key, cfg: DLRMConfig) -> dict:
    """The bottom MLP, the interaction's weights and the top MLP, fan-in
    scaled (`_mlp_init`); a cross layer's V maps the concatenation to the
    rank, W back, with a zero bias."""
    bottom = _mlp_init(fold_key(key, "bottom"),
                       (cfg.n_dense,) + cfg.bottom_dims)
    if cfg.interaction == "cross":
        width = (cfg.n_sparse + 1) * cfg.embed_dim
        if cfg.bottom_dims[-1] != cfg.embed_dim:
            raise ValueError("a cross interaction needs the bottom MLP to "
                             "end at embed_dim")
        cross = []
        for i in range(cfg.cross_layers):
            v, w = _mlp_init(fold_key(key, "cross", str(i)),
                             (width, cfg.cross_rank, width))
            cross.append({"V": v["w"], "W": w["w"], "b": w["b"]})
        top_in = width
    else:
        n_vec = cfg.n_sparse + 1
        cross = []
        top_in = n_vec * (n_vec - 1) // 2 + cfg.bottom_dims[-1]
    return {"bottom": bottom, "cross": cross,
            "top": _mlp_init(fold_key(key, "top"), (top_in,) + cfg.top_dims)}


def init_dlrm(key, cfg: DLRMConfig) -> dict:
    tables = {}
    for i, rows in enumerate(cfg.rows):
        tables[f"cat_{i:02d}"] = 0.05 * jax.random.normal(
            fold_key(key, "dlrm", str(i)), (rows, cfg.embed_dim))
    return {"tables": tables, **init_dlrm_dense(key, cfg)}


def pool_bags(tables: dict, cfg: DLRMConfig, sparse: jax.Array) -> list:
    """Sum-pool each table's bag: `sparse` (..., sum(bags)) holds table i's
    ids at its slice, in table order -> one (..., d) array a table."""
    out, lo = [], 0
    for i, n in enumerate(cfg.bags):
        rows = tables[f"cat_{i:02d}"][sparse[..., lo:lo + n]]  # (..., n, d)
        out.append(rows.sum(-2))
        lo += n
    return out


def cross_net(layers: list, x0: jax.Array) -> jax.Array:
    """DCNv2's low-rank cross network: x_{l+1} = x0 * (W_l (V_l x_l) + b_l)
    + x_l, over the last axis."""
    x = x0
    for p in layers:
        x = x0 * ((x @ p["V"]) @ p["W"] + p["b"]) + x
    return x


def dlrm_forward(p, cfg: DLRMConfig, batch: dict) -> jax.Array:
    """batch: dense (..., 13) float, sparse (..., sum(bags)) int32 ids ->
    CTR logits (...). Plain jax.numpy: per-table sum-pooled bags, then the
    configured interaction and the top MLP. The dot interaction takes one
    leading batch axis."""
    dense = _mlp_apply(p["bottom"], batch["dense"], final_act=True)
    embs = pool_bags(p["tables"], cfg, batch["sparse"])
    if cfg.interaction == "cross":
        x = cross_net(p["cross"], jnp.concatenate([dense] + embs, axis=-1))
        return _mlp_apply(p["top"], x)[..., 0]
    vecs = jnp.stack([dense] + embs, axis=1)  # (B, 27, d)
    inter = jnp.einsum("bid,bjd->bij", vecs, vecs)  # pairwise dots
    iu, ju = jnp.triu_indices(vecs.shape[1], k=1)
    flat = inter[:, iu, ju]  # (B, 351)
    x = jnp.concatenate([flat, dense], axis=-1)
    return _mlp_apply(p["top"], x)[..., 0]


def dlrm_loss(p, cfg: DLRMConfig, batch: dict) -> jax.Array:
    logits = dlrm_forward(p, cfg, batch)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
