"""Build a DLRM-DCNv2 ranking engine through the program, from the seed.

The benchmark makes the inputs (`dlrm_gen`): the dense weights, the float
rows of the tables in fixed-size blocks, and the pool of requests. The
program does what a deployment does with them: `DLRMEngine.from_row_blocks`
quantizes each block row-wise into its int8 tables as it arrives
(`quantize_rowwise`), and `make_server` serves the engine.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.models import dlrm_gen as gen

# the jitted serve stages by a substring of their program names in the
# profiler trace; no Pallas kernel runs on this path (the lookup is XLA's
# gather and sum)
TRACE_PROGRAMS = {"lookup": "_dlrm_lookup_stage", "rank": "_dlrm_rank_stage"}
TRACE_KERNELS = {}


def program_config(cfg: dict):
    """The program's `DLRMConfig` for configuration file `cfg`: the rows
    held of each table, not the published rows."""
    from repro.models.recsys import DLRMConfig

    return DLRMConfig(
        n_dense=cfg["dense_features"], n_sparse=len(cfg["bag_sizes"]),
        embed_dim=cfg["embed_dim"], bottom_dims=tuple(cfg["bottom_dims"]),
        top_dims=tuple(cfg["top_dims"]),
        table_rows=tuple(cfg["held_rows"]), bag_sizes=tuple(cfg["bag_sizes"]),
        interaction=cfg["interaction"], cross_layers=cfg["cross_layers"],
        cross_rank=cfg["cross_rank"])


def build(cfg: dict, log) -> dict:
    """-> {engine, pool, n_users, reference_inputs, setup parts (s)}.

    Everything comes from the configuration's ``model_seed``; the run's
    own seed orders the traffic (`n_users` counts the pool's requests,
    which the load generator draws as it draws users).
    """
    from repro.serving import DLRMEngine

    parts = {}
    t = time.perf_counter()
    dense = gen.make_weights(cfg)
    parts["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    engine = DLRMEngine.from_row_blocks(
        jax.tree.map(jax.numpy.asarray, dense), program_config(cfg),
        gen.row_blocks(cfg), candidates=cfg["candidates"],
        top_k=cfg["top_k"])
    jax.block_until_ready(engine.table_q.values)
    parts["tables_s"] = time.perf_counter() - t

    t = time.perf_counter()
    pool = gen.make_pool(cfg)
    parts["pool_s"] = time.perf_counter() - t
    log(f"engine: {sum(cfg['held_rows'])} rows held "
        f"({engine.table_q.values.nbytes + engine.table_q.scales.nbytes} B), "
        f"parts {({k: round(v, 3) for k, v in parts.items()})}")
    return {"engine": engine, "pool": pool, "n_users": len(pool),
            "reference_inputs": {"cfg": cfg, "weights": dense},
            "setup_parts": parts}


def work(cfg: dict, engine) -> dict:
    """The sizes the work counts need."""
    return {"config": cfg}


def run_stages(server, engine, queries: list[dict], bucket: int):
    """Drive `queries` through the timed path's compiled stage programs at
    the `bucket` batch shape, exactly as the ring dispatches a bucket.

    -> dict of host arrays: pooled (n, C, tables, d), logits (n, C),
    items and scores (n, top_k).
    """
    from repro.serving import dlrm_engine as de

    host = server._inner._stack_np(queries, bucket)
    batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
    pooled = de.dlrm_lookup_step(engine, batch)
    items, scores, logits, _ = de.dlrm_rank_step(engine, batch, pooled)
    n = len(queries)
    return {"pooled": np.asarray(pooled)[:n],
            "logits": np.asarray(logits)[:n],
            "items": np.asarray(items)[:n], "scores": np.asarray(scores)[:n]}
