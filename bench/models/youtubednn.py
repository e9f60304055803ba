"""Build a YoutubeDNN serving engine through the program, from the seed.

The benchmark makes the inputs (`youtubednn_gen`): users, trained float
weights, the rows of a grown catalog and an update batch. The program does
everything a deployment does with them: `RecSysEngine.build` quantizes the
tables and signs the items, a grown catalog's rows go through the same
ingestion (`quantize_rowwise`, `lsh_signature`, `build_block_summary`),
and a live catalog takes its pending updates through `apply_updates`.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import subseed
from bench.models import youtubednn_gen as gen

LSH_BITS = 256
# the jitted serve stages by a substring of their program names, and the
# Pallas kernels by a substring of their op names, in the profiler trace
TRACE_PROGRAMS = {"lookup": "_lookup_stage", "scan": "_scan_stage",
                  "rank": "_rank_stage"}
TRACE_KERNELS = {"streaming_nns": "streaming_nns",
                 "hamming_distances": "hamming",
                 "embedding_pool": "embedding_pool"}


def program_config(cfg: dict):
    """The program's `YoutubeDNNConfig` for configuration file `cfg`."""
    from repro.models.recsys import YoutubeDNNConfig

    return YoutubeDNNConfig(
        n_items=cfg["trained_items"], user_features=dict(cfg["user_features"]),
        history_len=cfg["history_len"], filter_dims=tuple(cfg["filter_dims"]),
        rank_dims=tuple(cfg["rank_dims"]), embed_dim=cfg["embed_dim"])


def build(cfg: dict, log) -> dict:
    """-> {engine, pool, n_users, reference_inputs, setup parts (s)}.

    Everything comes from the configuration's ``model_seed``: every run
    of a cell serves the same model and catalog, and the run's own seed
    draws only its traffic, so that seeds change the order of the work
    and not the work.
    """
    seed = cfg["model_seed"]
    from repro.core.lsh import lsh_signature
    from repro.core.nns import build_block_summary
    from repro.core.quantization import (
        QuantizedTensor,
        dequantize_rowwise,
        quantize_rowwise,
    )
    from repro.serving import RecSysEngine

    parts = {}
    t = time.perf_counter()
    cards = tuple(sorted(cfg["user_features"].items()))
    users = gen.synth_movielens(dict(cards), cfg["trained_items"],
                                cfg["history_len"], seed)
    parts["users_s"] = time.perf_counter() - t

    t = time.perf_counter()
    sizes = (cards, cfg["trained_items"], cfg["embed_dim"],
             tuple(cfg["filter_dims"]), tuple(cfg["rank_dims"]),
             cfg["train_steps"], cfg["train_batch"])
    params = gen.make_weights(
        {k: jnp.asarray(v) for k, v in users.items()},
        jax.random.key(subseed(seed, 15)), sizes)
    params = jax.tree.map(np.asarray, params)  # waits for the device
    parts["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    lsh_seed = subseed(seed, 17)
    hist = users["history"][users["history"] >= 0]
    engine = RecSysEngine.build(
        params, program_config(cfg), lsh_bits=LSH_BITS,
        radius=cfg["radius"], n_candidates=cfg["n_candidates"],
        top_k=cfg["top_k"], hot_rows=cfg["hot_rows"],
        item_freqs=np.bincount(hist, minlength=cfg["trained_items"]),
        key=jax.random.key(lsh_seed))
    jax.block_until_ready(engine.item_sigs)
    parts["build_s"] = time.perf_counter() - t

    n_items = cfg["catalog_items"]
    ref = {"params": params, "lsh_seed": lsh_seed, "lsh_bits": LSH_BITS,
           "n_items": n_items, "radius": cfg["radius"],
           "n_candidates": cfg["n_candidates"], "top_k": cfg["top_k"],
           "upsert_ids": np.zeros(0, np.int64),
           "upsert_rows": np.zeros((0, cfg["embed_dim"]), np.float32),
           "delete_ids": np.zeros(0, np.int64)}
    if n_items > cfg["trained_items"]:
        t = time.perf_counter()
        rng = np.random.default_rng(subseed(seed, 19))
        protos = rng.choice(len(users["label"]), cfg["clusters"],
                            replace=False)
        centers = np.asarray(gen.user_tower(
            jax.tree.map(jnp.asarray, params),
            {k: jnp.asarray(v[protos]) for k, v in users.items()}))
        catalog_seed = int(rng.integers(2 ** 31))
        chunk = cfg["catalog_chunk"]
        per, los = gen.catalog_plan(cfg["trained_items"], n_items,
                                    cfg["clusters"], chunk)
        key = jax.random.key(catalog_seed)

        @jax.jit
        def ingest(centers, key, proj, lo):
            q = quantize_rowwise(gen.tail_rows(centers, key, lo, chunk=chunk,
                                               per_cluster=per))
            return q.values, q.scales, lsh_signature(dequantize_rowwise(q),
                                                     proj)

        tails = [ingest(jnp.asarray(centers), key, engine.lsh_proj, lo)
                 for lo in los]
        values, scales, sigs = (
            jnp.concatenate([base] + [p[i] for p in tails])[:n_items]
            for i, base in enumerate((engine.item_table_q.values,
                                      engine.item_table_q.scales,
                                      engine.item_sigs)))
        del tails
        jax.block_until_ready(sigs)
        parts["catalog_s"] = time.perf_counter() - t
        t = time.perf_counter()
        engine = dataclasses.replace(
            engine, item_table_q=QuantizedTensor(values=values, scales=scales),
            item_sigs=sigs,
            block_summary=build_block_summary(np.asarray(sigs)))
        parts["summary_s"] = time.perf_counter() - t
        ref.update(centers=centers, catalog_seed=catalog_seed,
                   catalog_chunk=chunk)
    if cfg.get("live"):
        t = time.perf_counter()
        up_ids, up_rows, del_ids = gen.update_batch(
            ref.get("centers", params["item_table"]), n_items,
            cfg["updates"], seed)
        engine = engine.live(cfg["delta_capacity"]).apply_updates(
            upsert_ids=up_ids, upsert_rows=up_rows, delete_ids=del_ids)
        jax.block_until_ready(engine.item_mask)
        parts["live_s"] = time.perf_counter() - t
        ref.update(upsert_ids=up_ids, upsert_rows=up_rows, delete_ids=del_ids)
    log(f"engine: {n_items} items, radius {engine.radius}, "
        f"parts {({k: round(v, 3) for k, v in parts.items()})}")
    n_users = len(users["label"])
    return {"engine": engine, "pool": gen.queries(users, range(n_users)),
            "n_users": n_users, "reference_inputs": ref,
            "setup_parts": parts}


def work(cfg: dict, engine) -> dict:
    """The sizes the work counts need."""
    summary = engine.block_summary
    return {"bits": LSH_BITS, "rows": int(engine.item_sigs.shape[0]),
            "block_rows": None if summary is None else summary.block_rows,
            "embed_dim": cfg["embed_dim"],
            "features": len(cfg["user_features"]),
            "history_len": cfg["history_len"],
            "filter_dims": cfg["filter_dims"], "rank_dims": cfg["rank_dims"],
            "n_candidates": cfg["n_candidates"]}


def run_stages(server, engine, queries: list[dict], bucket: int):
    """Drive `queries` through the timed path's compiled stage programs at
    the `bucket` batch shape, exactly as the ring dispatches a bucket.

    -> dict of host arrays: u, cand, dist, counts, items, scores.
    """
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    host = server._inner._stack_np(queries, bucket)
    batch = {k: jnp.asarray(v) for k, v in host.items()}
    u, pooled, stats = rse.lookup_step(engine, batch, CacheStats.zero())
    nns = rse.scan_step(engine, u)
    items, top, _ = rse.rank_stage_step(engine, batch, nns.indices, u,
                                        pooled, stats)
    n = len(queries)
    return {"u": np.asarray(u)[:n], "cand": np.asarray(nns.indices)[:n],
            "dist": np.asarray(nns.distances)[:n],
            "counts": np.asarray(nns.counts)[:n],
            "items": np.asarray(items)[:n],
            "scores": np.asarray(top.scores)[:n]}
