#!/usr/bin/env python3
"""Bring-up check: serve YoutubeDNN on one TPU chip through `make_server`.

    python chip_smoke.py              # one chip: the paper and catalog phases
    python chip_smoke.py --chips 4    # four chips: the sharded catalog only

Everything runs in this one process (a child could not reach the chip this
process holds), from seeded generated data; nothing is downloaded.

* **Paper phase.** YoutubeDNN at the widths of `default_youtubednn_config()`
  (the ML-1M user cardinalities, filter MLP 128/64/32, history 20, the
  default item count): a few `make_recsys_train_step` steps on seeded
  `data.synthetic` data, `RecSysEngine.build`, then a few hundred requests
  through `make_server` in `pipelined` and in `concurrent` mode.
* **Catalog phase.** The same engine with its catalog grown to 2**23 items
  (seeded rows clustered around real user embeddings, as the tiered-catalog
  benchmark builds its tail) — two superblocks of the streaming kernel.
  The engine goes live with a small upsert/delete batch (tombstone mask on)
  and keeps its block summary (pruned kernel on); a few batches are served
  and `blocks_touched` printed. Each kernel variant's scan is also checked.
* **Checks.** Every dispatched op must be in `pallas` mode, and its
  compiled program must hold a Mosaic kernel. Each result is compared with
  the same stage compiled with the `kernels/ref.py` oracles (a program
  checked to hold no Pallas kernel): NNS candidates, distances, counts,
  the genre bag and served items must match bit for bit; the largest
  served-score difference is printed. No ticket may come back `error` or
  `shed` (the concurrent front-end turns a failed chunk into error tickets
  and keeps going, so a kernel failure would otherwise pass silently).
* **Four chips** (`--chips 4`). The catalog-phase engine served on device 0
  is compared, bit for bit, with the same engine bank-sharded
  (`shard(mesh, "banks")`) and query-parallel (`shard(mesh,
  query_axis="qp")`) over a 4-device mesh; per-device memory and where
  each part of the engine lands are printed.

Earlier lines are informational (device, set-up and phase seconds, peak
device memory); the last line is the only result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero without it when JAX's first device is not a TPU,
when a `REPRO_PALLAS` override is set, or when any check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import sys
import time
from typing import NamedTuple

SRC = pathlib.Path(__file__).resolve().parent / "src"
OPS_USED = ("hamming_distances", "embedding_pool", "streaming_nns")


class SmokeFailure(RuntimeError):
    """A check failed: the script exits non-zero and prints no result."""


class Sizes(NamedTuple):
    """Run sizes. 300 steps take the filtering model's loss from 8.0 to
    ~1.5; after 20 the user embeddings are still nearly parallel (a
    median 9 of 256 signature bits apart), so every grown cluster would
    match every query."""

    train_steps: int = 300
    train_batch: int = 256
    paper_requests: int = 512
    serve_batch: int = 256
    catalog_items: int = 1 << 23
    clusters: int = 96
    catalog_requests: int = 1024
    catalog_radius: int = 72
    delta_capacity: int = 1024
    updates: int = 64


def log(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


# ---------------------------------------------------------------------------
# kernels and references
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def ref_kernels():
    """Trace with every op on its `kernels/ref.py` oracle (the documented
    `REPRO_PALLAS` override, set only while a reference program traces)."""
    prev = os.environ.get("REPRO_PALLAS")
    os.environ["REPRO_PALLAS"] = "ref"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_PALLAS"]
        else:
            os.environ["REPRO_PALLAS"] = prev


def n_kernels(compiled) -> int:
    """Mosaic kernels in a compiled TPU program (0 on the CPU)."""
    return compiled.as_text().count("tpu_custom_call")


def reference(fn, *args):
    """`fn` compiled afresh with the ref oracles; refuses a program that
    still holds a Pallas kernel."""
    import jax

    with ref_kernels():
        compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    if n_kernels(compiled):
        raise SmokeFailure(f"reference {fn.__name__} holds a Pallas kernel")
    return compiled


def require_kernel(step, *args, on_chip: bool) -> None:
    """The engine's jitted `step` must hold a Pallas kernel on the chip."""
    if on_chip and not n_kernels(step.lower(*args).compile()):
        raise SmokeFailure(f"{step.__name__} compiled without a Pallas "
                           "kernel: an op fell back off the chip path")


def same(name: str, got, want) -> None:
    """Bit-for-bit equality, or a failure naming how far apart they are."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise SmokeFailure(f"{name}: shape {got.shape} != {want.shape}")
    if not np.array_equal(got, want):
        raise SmokeFailure(
            f"{name}: {int(np.sum(got != want))} of {want.size} values "
            f"differ from the reference (max |diff| {max_diff(got, want)})")


def max_diff(got, want) -> float:
    """Largest absolute difference (0.0 for bit-equal arrays)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    finite = np.isfinite(got) & np.isfinite(want)
    same_inf = (got == want) | finite
    if not same_inf.all():
        return float("inf")
    return float(np.max(np.abs(got[finite] - want[finite]), initial=0.0))


# ---------------------------------------------------------------------------
# the paper engine
# ---------------------------------------------------------------------------
def train_and_build(cfg, data, sizes: Sizes, seed: int):
    """A few filtering-model steps, then the quantized serving engine."""
    import jax
    import numpy as np

    from repro.data import synthetic
    from repro.distributed.training import (
        init_recsys_train_state,
        make_recsys_train_step,
    )
    from repro.models import recsys as rs
    from repro.serving import RecSysEngine

    state = init_recsys_train_state(rs.init_youtubednn(jax.random.key(seed),
                                                       cfg))
    step = make_recsys_train_step(cfg)
    losses = []
    for batch in synthetic.movielens_batches(data, sizes.train_batch,
                                             sizes.train_steps, seed=seed):
        state, loss = step(state, batch)
        losses.append(float(loss))
    if not np.all(np.isfinite(losses)):
        raise SmokeFailure(f"training loss not finite: {losses}")
    hist = data.histories[data.histories >= 0]
    engine = RecSysEngine.build(
        state.params, cfg, n_candidates=50, top_k=10, hot_rows=256,
        item_freqs=np.bincount(hist, minlength=cfg.n_items),
        key=jax.random.key(seed + 1))
    return engine, losses


def stacked(engine, queries, batch: int):
    """The padded device batches a server forms from `queries` (full
    `batch`-sized buckets, in submission order)."""
    from repro.serving import make_server

    shaper = make_server(engine, "sync", max_batch=batch, buckets=(batch,))
    return [shaper._stack(queries[i:i + batch], batch)
            for i in range(0, len(queries), batch)]


def serve(engine, mode: str, queries, batch: int, **knobs):
    """Serve `queries` through `make_server`; -> (items, scores, stats)."""
    import numpy as np

    from repro.serving import make_server

    knobs.update(max_batch=batch, buckets=(batch,))
    if mode == "concurrent":  # every request admitted before the drain
        knobs.update(queue_depth=None, autostart=False)
    server = make_server(engine, mode, **knobs)
    try:
        served = server.serve_many(queries)
        stats = server.stats()
        snap = server.snapshot()
    finally:
        server.close()
    statuses = [s.status for s in served]
    bad = {s: statuses.count(s) for s in ("error", "shed") if s in statuses}
    if bad or stats["n_errors"] or stats["n_shed"]:
        raise SmokeFailure(f"{mode}: tickets {bad}, stats errors="
                           f"{stats['n_errors']} shed={stats['n_shed']} "
                           f"(last error: {stats['last_error']})")
    stats["blocks_touched"] = int(snap.get("nns.blocks_touched", 0))
    stats["block_scan_queries"] = int(snap.get("nns.block_scan_queries", 0))
    return (np.stack([s.items for s in served]),
            np.stack([s.scores for s in served]), stats)


def reference_serve(engine, batches):
    """Items and scores of the staged serve path with the ref oracles."""
    import numpy as np

    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    zero = CacheStats.zero()
    lookup = reference(rse._lookup_stage, engine, batches[0], zero)
    u, pooled, _ = lookup(engine, batches[0], zero)
    scan = reference(rse._scan_stage, engine, u)
    nns = scan(engine, u)
    rank = reference(rse._rank_stage, engine, batches[0], nns.indices, u,
                     pooled, zero)
    items, scores = [], []
    for batch in batches:
        u, pooled, _ = lookup(engine, batch, zero)
        nns = scan(engine, u)
        final, top, _ = rank(engine, batch, nns.indices, u, pooled, zero)
        items.append(np.asarray(final))
        scores.append(np.asarray(top.scores))
    return np.concatenate(items), np.concatenate(scores)


def check_scan(name: str, engine, batch, *, on_chip: bool):
    """The engine's jitted scan vs its ref-oracle program on one batch."""
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    u, _, _ = rse.lookup_step(engine, batch, CacheStats.zero())
    require_kernel(rse.scan_step, engine, u, on_chip=on_chip)
    got = rse.scan_step(engine, u)
    want = reference(rse._scan_stage, engine, u)(engine, u)
    for field in ("indices", "distances", "counts", "blocks_touched"):
        g, w = getattr(got, field), getattr(want, field)
        if (g is None) != (w is None):
            raise SmokeFailure(f"{name}: {field} present on one side only")
        if g is not None:
            same(f"{name} nns.{field}", g, w)
    return got


def paper_phase(engine, data, sizes: Sizes, seed: int, *, on_chip: bool):
    import numpy as np

    from repro.data.synthetic import serving_queries
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    rng = np.random.default_rng(seed)
    queries = serving_queries(
        data, rng.integers(0, data.n_users, sizes.paper_requests))
    batches = stacked(engine, queries, sizes.serve_batch)
    t0 = time.perf_counter()
    nns = check_scan("paper", engine, batches[0], on_chip=on_chip)
    u, pooled, _ = rse.lookup_step(engine, batches[0], CacheStats.zero())
    require_kernel(rse.rank_stage_step, engine, batches[0], nns.indices, u,
                   pooled, CacheStats.zero(), on_chip=on_chip)
    want_items, want_scores = reference_serve(engine, batches)
    setup_s = time.perf_counter() - t0
    out = {"plan": "dense", "candidates_mean":
           float(np.mean(np.asarray(nns.counts))),
           "genre_pool": check_pool(engine, batches[0])}
    for mode in ("pipelined", "concurrent"):
        t = time.perf_counter()
        items, scores, stats = serve(engine, mode, queries,
                                     sizes.serve_batch)
        out[f"{mode}_s"] = time.perf_counter() - t
        same(f"paper {mode} items", items, want_items)
        out[f"{mode}_scores_max_diff"] = max_diff(scores, want_scores)
        out[f"{mode}_served"] = stats["n_served"]
    out["setup_s"] = setup_s
    return out


def check_pool(engine, batch) -> str:
    """The ranking path's genre bag (one slot a bag) through the kernel
    vs the ref oracle: one product per output, so bit-equal."""
    import jax

    from repro.kernels import ops, ref

    table, ids = engine.genre_table_q, batch["genre"][:, None]
    got = jax.jit(ops.embedding_pool)(table.values, table.scales, ids)
    want = jax.jit(ref.embedding_pool_ref)(table.values, table.scales, ids)
    same("genre embedding_pool", got, want)
    return "bitmatch"


# ---------------------------------------------------------------------------
# the grown catalog
# ---------------------------------------------------------------------------
def zipf_pick(rng, k: int, size: int):
    import numpy as np

    w = np.arange(1, k + 1, dtype=np.float64) ** -1.1
    return rng.choice(k, size=size, p=w / w.sum())


def grow_catalog(engine, protos, n_items: int, seed: int,
                 noise: float = 0.25, chunk: int = 1 << 20):
    """`engine` with its item catalog grown to `n_items` rows.

    The trained rows stay first; the tail is generated on the device in
    fixed chunks, each row a prototype user's embedding plus seeded noise
    (cluster-contiguous ids), quantized and signed exactly as
    `RecSysEngine.build` does. The block summary is rebuilt over the whole
    catalog.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lsh import lsh_signature
    from repro.core.nns import build_block_summary
    from repro.core.quantization import (
        QuantizedTensor,
        dequantize_rowwise,
        quantize_rowwise,
    )
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    u, _, _ = rse.lookup_step(engine, protos, CacheStats.zero())
    centers = u[: int(np.asarray(protos["valid"]).sum())]
    n0, d = engine.item_table_q.shape
    per_cluster = -(-(n_items - n0) // centers.shape[0])
    key = jax.random.key(seed)

    @jax.jit
    def tail(lo):
        ids = lo + jnp.arange(chunk)
        c = centers[jnp.minimum(ids // per_cluster, centers.shape[0] - 1)]
        scale = noise * jnp.linalg.norm(c, axis=1, keepdims=True) / d ** 0.5
        rows = c + scale * jax.random.normal(jax.random.fold_in(key, lo),
                                             (chunk, d))
        q = quantize_rowwise(rows)
        return q.values, q.scales, lsh_signature(dequantize_rowwise(q),
                                                 engine.lsh_proj)

    parts = [tail(lo) for lo in range(0, n_items - n0, chunk)]
    values, scales, sigs = (
        jnp.concatenate([base] + [p[i] for p in parts])[:n_items]
        for i, base in enumerate((engine.item_table_q.values,
                                  engine.item_table_q.scales,
                                  engine.item_sigs)))
    return dataclasses.replace(
        engine, item_table_q=QuantizedTensor(values=values, scales=scales),
        item_sigs=sigs, block_summary=build_block_summary(np.asarray(sigs)))


def make_live(engine, sizes: Sizes, seed: int):
    """Live view of `engine` plus one upsert/delete batch: re-embedded and
    new ids ride the delta shard, deleted and overwritten rows are
    tombstoned in the scan's `db_mask`."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n, d = engine.item_table_q.shape
    k = sizes.updates // 4
    existing = rng.choice(n, 3 * k, replace=False)
    upsert_ids = np.concatenate([existing[:k], n + np.arange(k)])
    donors = np.asarray(engine.item_table_q.values[
        rng.choice(n, 2 * k, replace=False)], np.float32)
    rows = donors / 127.0 + 0.01 * rng.standard_normal((2 * k, d))
    live = engine.live(sizes.delta_capacity)
    return live.apply_updates(upsert_ids=upsert_ids,
                              upsert_rows=rows.astype(np.float32),
                              delete_ids=existing[k:])


def catalog_setup(engine, data, sizes: Sizes, seed: int):
    """The grown (frozen) engine + a Zipf query stream over the prototype
    users its clusters are centred on."""
    import numpy as np

    from repro.data.synthetic import serving_queries

    rng = np.random.default_rng(seed + 2)
    users = rng.choice(data.n_users, sizes.clusters, replace=False)
    protos = serving_queries(data, users)
    proto_batch = stacked(engine, protos, len(protos))[0]
    grown = grow_catalog(engine, proto_batch, sizes.catalog_items, seed + 3)
    grown = dataclasses.replace(grown, radius=sizes.catalog_radius)
    pick = zipf_pick(rng, sizes.clusters, sizes.catalog_requests)
    return grown, [protos[int(i)] for i in pick]


def catalog_phase(grown, queries, sizes: Sizes, seed: int, *,
                  on_chip: bool):
    import numpy as np

    from repro.core.nns import _plan_streams
    from repro.serving.recsys_engine import n_summary_blocks

    if not _plan_streams(grown.item_sigs.shape[0], grown.scan_block):
        raise SmokeFailure("grown catalog does not take the streaming plan")
    t0 = time.perf_counter()
    batches = stacked(grown, queries, sizes.serve_batch)
    variants = {"pruned": grown,
                "plain": dataclasses.replace(grown, prune=False)}
    live = make_live(grown, sizes, seed + 4)
    variants["masked_pruned"] = live
    variants["masked"] = dataclasses.replace(live, prune=False)
    for name, eng in variants.items():
        nns = check_scan(f"catalog {name}", eng, batches[0], on_chip=on_chip)
    want_items, want_scores = reference_serve(live, batches)
    out = {"items": int(live.item_table_q.shape[0]),
           "superblocks": -(-int(live.item_sigs.shape[0]) // (1 << 22)),
           "summary_blocks": n_summary_blocks(live),
           "matches_mean": float(np.mean(np.asarray(nns.counts))),
           "setup_s": time.perf_counter() - t0}
    for mode in ("pipelined", "concurrent"):
        t = time.perf_counter()
        items, scores, stats = serve(live, mode, queries, sizes.serve_batch)
        out[f"{mode}_s"] = time.perf_counter() - t
        same(f"catalog {mode} items", items, want_items)
        out[f"{mode}_scores_max_diff"] = max_diff(scores, want_scores)
        out["blocks_touched_mean"] = (stats["blocks_touched"]
                                      / max(stats["block_scan_queries"], 1))
    return live, out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def placement(x) -> list[int]:
    return sorted(d.id for d in x.devices())


def device_memory(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[d.id] = {k: stats.get(k) for k in ("bytes_in_use",
                                                "peak_bytes_in_use")}
    return out


def four_chip_phase(engine, queries, sizes: Sizes, devices):
    """The one-device engine vs bank-sharded and query-parallel copies."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    batch = stacked(engine, queries, sizes.serve_batch)[0]
    u, _, _ = rse.lookup_step(engine, batch, CacheStats.zero())
    want_nns = rse.scan_step(engine, u)
    # a query-parallel server coalesces `coalesce` full buckets into one
    # routed batch; the one-device engine serves the same batch shapes
    want = {c: serve(engine, "pipelined", queries, sizes.serve_batch,
                     coalesce=c)[:2] for c in (1, len(devices))}
    out = {}
    for name, shard in (
            ("banks", lambda e: e.shard(make_mesh((4,), ("banks",),
                                                  devices=devices), "banks")),
            ("qp", lambda e: e.shard(make_mesh((4,), ("qp",),
                                               devices=devices),
                                     query_axis="qp"))):
        t = time.perf_counter()
        eng = shard(engine)
        got_nns = rse.scan_step(eng, u)
        for field in ("indices", "distances", "counts"):
            same(f"{name} nns.{field}", getattr(got_nns, field),
                 getattr(want_nns, field))
        items, scores, stats = serve(eng, "pipelined", queries,
                                     sizes.serve_batch)
        want_items, want_scores = want[stats["coalesce"]]
        same(f"{name} items", items, want_items)
        out[name] = {
            "s": time.perf_counter() - t,
            "scores_max_diff": max_diff(scores, want_scores),
            "item_sigs": placement(eng.item_sigs),
            "item_mask": placement(eng.item_mask),
            "item_table": placement(eng.item_table_q.values),
            "hot_cache": placement(eng.item_hot.hot_rows),
            "params": placement(jax.tree.leaves(eng.params)[0]),
            "batch": placement(batch["history"]),
            "memory": device_memory(devices),
        }
    return out


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded catalog check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    overrides = sorted(k for k in os.environ
                       if k == "REPRO_PALLAS" or k.startswith("REPRO_PALLAS_"))
    if overrides:
        raise SmokeFailure(f"kernel overrides set: {overrides}; the smoke "
                           "check runs the chip's own kernels only")
    if not (SRC / "repro").is_dir():
        raise SmokeFailure(f"no repro package under {SRC}: run this script "
                           "from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.launch.runtime import enable_compile_cache

    log("compile cache", enable_compile_cache())
    t_start = time.perf_counter()
    import jax

    from repro.data import synthetic
    from repro.kernels import ops
    from repro.models import recsys as rs

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SmokeFailure(f"JAX's first device is {first.platform} "
                           f"({first.device_kind}), not a TPU")
    if len(devices) < args.chips:
        raise SmokeFailure(f"--chips {args.chips} but JAX sees "
                           f"{len(devices)} device(s)")
    log(f"device kind={first.device_kind} count={len(devices)}")
    modes = {op: ops.kernel_mode(op) for op in OPS_USED}
    log("kernel modes", json.dumps(modes))
    if set(modes.values()) != {"pallas"}:
        raise SmokeFailure(f"ops off the pallas backend: {modes}")

    sizes = Sizes()
    cfg = rs.default_youtubednn_config()
    data = synthetic.make_movielens(n_users=cfg.user_features["user_id"],
                                    n_items=cfg.n_items,
                                    history_len=cfg.history_len,
                                    seed=args.seed)
    t = time.perf_counter()
    engine, losses = train_and_build(cfg, data, sizes, args.seed)
    log(f"train steps={len(losses)} loss first={losses[0]:.4f} "
        f"last={losses[-1]:.4f}; build s={time.perf_counter() - t:.1f}")

    if args.chips == 1:
        t = time.perf_counter()
        paper = paper_phase(engine, data, sizes, args.seed, on_chip=True)
        log("paper phase", json.dumps(paper),
            f"wall_s={time.perf_counter() - t:.1f} bitmatch=ok errors=0 "
            "shed=0")
    t = time.perf_counter()
    grown, queries = catalog_setup(engine, data, sizes, args.seed)
    log(f"catalog grown to {grown.item_sigs.shape[0]} rows "
        f"s={time.perf_counter() - t:.1f}")
    if args.chips == 1:
        t = time.perf_counter()
        _, catalog = catalog_phase(grown, queries, sizes, args.seed,
                                   on_chip=True)
        log("catalog phase", json.dumps(catalog),
            f"wall_s={time.perf_counter() - t:.1f} bitmatch=ok errors=0 "
            "shed=0")
    else:
        t = time.perf_counter()
        live = make_live(grown, sizes, args.seed + 4)
        four = four_chip_phase(live, queries, sizes, devices[:4])
        for name, row in four.items():
            log(f"four-chip {name}", json.dumps(row))
        log(f"four-chip phase wall_s={time.perf_counter() - t:.1f} "
            "bitmatch=ok (vs the one-chip engine on device 0)")
    peak = (first.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak} total_s="
        f"{time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
