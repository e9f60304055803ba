"""Catalog churn: serving throughput while the item catalog mutates live.

The frozen-catalog benchmarks measure the engine at rest; production
catalogs are re-embedded, extended, and pruned *while traffic is live*.
This benchmark serves one fixed query stream three ways over a 256k–1M item
catalog (the streaming-NNS operating point):

  * ``frozen``      — the baseline `RecSysEngine`, no delta machinery;
  * ``live_clean``  — the same engine wrapped in `LiveCatalog` with an
                      empty delta shard (the steady post-compaction state:
                      measures the pure overlay overhead);
  * ``live_churn``  — `dirty_frac` of the rows resident in the delta shard
                      and a continuous upsert stream applied between query
                      waves (re-embeds recycling the dirty set, so the
                      shard stays at its operating size).

and then exercises the epoch machinery:

  * compaction pause (the host-side fold; serving swaps epochs atomically
    between buckets, so this is *amortized* — not a serving stall);
  * post-compaction bit-match vs a **cold rebuild** from the final table
    (`rebuild_reference`), asserted over the whole probe stream;
  * block-summary soundness across churn: the incrementally-maintained
    `BlockSummary` must equal a cold `build_block_summary` over the final
    (sigs, mask) bitwise — before AND after compaction — and the
    post-compaction pruned scan must serve the exact bits of the same
    engine without a summary (asserted, not sampled);
  * an epoch swap under the `AsyncServer` ring at depth `--depth`:
    every query of the stream is asserted to equal exactly the epoch it
    was dispatched against — old epoch before the swap, new epoch after,
    never stale, never mixed (asserted, not sampled).

Acceptance gate: ``live_churn`` sustains >= 0.8x frozen qps at 256k items
with 1% dirty rows. The nightly lane runs the 1M cell.

  PYTHONPATH=src python -m benchmarks.catalog_churn
      [--sizes 262144] [--queries 1024] [--batch 256] [--dirty-frac 0.01]
      [--updates-per-wave 256] [--scan-block 4096] [--wave 256] [--depth 3]
      [--repeats 2] [--out DIR]

``--sizes``/``--repeats``/``--out`` are the flags every serving benchmark
shares (see tools/bench_compare.py); front-ends come from `make_server`.

Variance control mirrors benchmarks/async_serving.py: the Eigen
single-thread XLA flag is defaulted in before jax loads and every qps cell
reports the best of ``--repeats`` measured passes.

Emits BENCH_catalog_churn.json (see benchmarks/bench_io.py).
"""
from __future__ import annotations

import argparse
import time


def _setup(n_items: int, scan_block: int | None, history_len: int = 12,
           hot_rows: int = 256):
    import jax
    import numpy as np

    from repro.data import synthetic
    from repro.models import recsys as rs
    from repro.serving import RecSysEngine

    # user behavior over a small id prefix (synthetic histories are O(U*I));
    # the engine's item table/signature bank is the full `n_items` catalog
    data = synthetic.make_movielens(n_users=2000,
                                    n_items=min(n_items, 4096),
                                    history_len=history_len)
    cfg = rs.YoutubeDNNConfig(
        n_items=n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=history_len)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=50,
                                top_k=10, hot_rows=hot_rows, item_freqs=freqs,
                                scan_block=scan_block)
    return engine, data


def _serve_waves(server, queries, wave, updates=None):
    """Serve `queries` in waves, applying `updates` (a callable) between
    waves; returns (qps, items, n_updates, update_rate)."""
    import numpy as np

    served, n_updates = [], 0
    t0 = time.perf_counter()
    for lo in range(0, len(queries), wave):
        served.extend(server.serve_many(queries[lo: lo + wave]))
        if updates is not None and lo + wave < len(queries):
            n_updates += updates()
    dt = time.perf_counter() - t0
    return (len(queries) / dt, np.stack([s.items for s in served]),
            n_updates, n_updates / dt)


def _assert_stream_equal(got, want, label):
    import numpy as np

    if not (np.asarray(got) == np.asarray(want)).all():
        raise AssertionError(f"{label}: served stream diverged")


def _assert_summary_sound(engine, label):
    """The engine's incrementally-maintained BlockSummary must be bitwise
    identical to a cold rebuild over the same (sigs, tombstone mask) — the
    update_block_summary maintenance contract (docs/KERNELS.md)."""
    import numpy as np

    from repro.core.nns import build_block_summary

    cold = build_block_summary(np.asarray(engine.item_sigs),
                               engine.block_summary.block_rows,
                               db_mask=np.asarray(engine.item_mask))
    for f in ("or_sigs", "and_sigs", "min_pc", "max_pc", "n_alive"):
        if not (np.asarray(getattr(engine.block_summary, f))
                == np.asarray(getattr(cold, f))).all():
            raise AssertionError(
                f"{label}: summary field {f} diverged from cold rebuild")


def rows(items: int, n_queries: int, batch: int, wave: int,
         dirty_frac: float, updates_per_wave: int, scan_block: int | None,
         depth: int, repeats: int = 2):
    import dataclasses

    import numpy as np

    from repro.data.synthetic import serving_queries
    from repro.serving import LiveCatalog, make_server

    def sync_server(eng):
        return make_server(eng, "sync", max_batch=batch, buckets=(batch,))

    engine, data = _setup(items, scan_block)
    rng = np.random.default_rng(0)
    d = engine.cfg.embed_dim
    queries = serving_queries(data, rng.integers(0, data.n_users, n_queries))
    warm = serving_queries(data, rng.integers(0, data.n_users, wave))

    n_dirty = max(1, int(items * dirty_frac))
    dirty_ids = np.sort(rng.choice(items, n_dirty, replace=False))

    out = []

    def best(server, updates=None):
        # best of `repeats` passes (run 1 doubles as warmup on this noisy
        # 2-core host, same policy as benchmarks/async_serving.py)
        return max((_serve_waves(server, queries, wave, updates)
                    for _ in range(max(repeats, 1))), key=lambda r: r[0])

    # -- frozen baseline ------------------------------------------------
    frozen = sync_server(engine)
    frozen.serve_many(warm)  # compile off the clock
    qps_frozen, items_frozen, _, _ = best(frozen)
    out.append((f"serving/churn/frozen_{items}", 1e6 / qps_frozen,
                f"qps={qps_frozen:.0f};items={items}"))

    # -- live, empty delta (steady post-compaction state) ---------------
    cat = LiveCatalog(engine, delta_capacity=n_dirty)
    clean = sync_server(cat.engine)
    cat.attach(clean)
    clean.serve_many(warm)
    qps_clean, items_clean, _, _ = best(clean)
    _assert_stream_equal(items_clean, items_frozen, "live_clean vs frozen")
    out.append((f"serving/churn/live_clean_{items}", 1e6 / qps_clean,
                f"qps={qps_clean:.0f};overhead_vs_frozen="
                f"{qps_clean / qps_frozen:.2f}x"))

    # -- live churn: dirty delta + upserts between waves ----------------
    cat.upsert(dirty_ids, rng.normal(size=(n_dirty, d)).astype(np.float32))
    assert cat.n_pending == n_dirty

    def apply_updates():
        pick = rng.choice(dirty_ids, updates_per_wave)  # recycle dirty set
        cat.upsert(pick, rng.normal(
            size=(updates_per_wave, d)).astype(np.float32))
        return updates_per_wave

    churn = sync_server(cat.engine)
    cat.attach(churn)
    churn.serve_many(warm)
    qps_churn, _, n_up, up_rate = best(churn, apply_updates)
    sustain = qps_churn / qps_frozen
    ok = sustain >= 0.8
    out.append((
        f"serving/churn/live_churn_{items}", 1e6 / qps_churn,
        f"qps={qps_churn:.0f};sustain_vs_frozen={sustain:.2f}x"
        f"(target >=0.8x);ok={ok};dirty_rows={n_dirty};"
        f"upserts={n_up};upserts_per_s={up_rate:.0f}"))
    assert ok, (f"delta path sustained only {sustain:.2f}x of frozen qps "
                f"(target >= 0.8x)")

    # -- the delta path is exact (pre-compaction) -----------------------
    probe = queries[: min(len(queries), 2 * batch)]
    live_out = sync_server(cat.engine).serve_many(probe)
    ref_pre = sync_server(cat.rebuild_reference()).serve_many(probe)
    _assert_stream_equal(np.stack([s.items for s in live_out]),
                         np.stack([s.items for s in ref_pre]),
                         "delta path vs cold rebuild")
    _assert_summary_sound(cat.engine, "pre-compaction, churned")

    # -- compaction: pause + post-fold bit-match vs cold rebuild --------
    pause_s = cat.compact()
    post = sync_server(cat.engine).serve_many(probe)
    ref_post = sync_server(cat.rebuild_reference()).serve_many(probe)
    _assert_stream_equal(np.stack([s.items for s in post]),
                         np.stack([s.items for s in ref_post]),
                         "post-compaction vs cold rebuild")
    _assert_stream_equal(np.stack([s.items for s in post]),
                         np.stack([s.items for s in live_out]),
                         "compaction changed served bits")
    _assert_summary_sound(cat.engine, "post-compaction")
    # the post-compact pruned scan serves the exact unpruned bits
    unpruned = sync_server(dataclasses.replace(
        cat.engine, block_summary=None)).serve_many(probe)
    _assert_stream_equal(np.stack([s.items for s in post]),
                         np.stack([s.items for s in unpruned]),
                         "post-compaction pruned vs unpruned")
    out.append((
        f"serving/churn/compact_{items}", pause_s * 1e6,
        f"pause_ms={pause_s * 1e3:.1f};epoch={cat.epoch};"
        f"bitmatch_cold_rebuild=True;summary_bitmatch_cold=True;"
        f"pruned_eq_unpruned=True"))

    # -- epoch swap under the pipelined ring: never stale, never mixed --
    k = min(updates_per_wave, n_dirty)
    cat.upsert(dirty_ids[:k], rng.normal(size=(k, d)).astype(np.float32))
    old_ref = cat.rebuild_reference()
    pipe = make_server(cat.engine, "pipelined", max_batch=batch,
                       buckets=(batch,), depth=depth)
    cat.attach(pipe)
    pipe.serve_many(warm)
    tickets = [pipe.submit(q) for q in queries]
    n_pre = 0
    while pipe.in_flight < min(depth - 1, 1) or n_pre == 0:
        pipe._ring.append(pipe._dispatch(pipe._take_parts()))
        n_pre += batch
        if n_pre >= len(queries):
            break
    cat.compact()  # swaps the epoch under the loaded ring
    new_ref = cat.rebuild_reference()
    pipe.flush()
    got = np.stack([pipe.result(t).items for t in tickets])
    want_old = np.stack([s.items for s in
                         sync_server(old_ref).serve_many(queries)])
    want_new = np.stack([s.items for s in
                         sync_server(new_ref).serve_many(queries)])
    _assert_stream_equal(got[:n_pre], want_old[:n_pre],
                         "pre-swap buckets must serve the old epoch")
    _assert_stream_equal(got[n_pre:], want_new[n_pre:],
                         "post-swap buckets must serve the new epoch")
    out.append((
        f"serving/churn/epoch_swap_{items}", 0.0,
        f"depth={depth};buckets_old_epoch={n_pre // batch};"
        f"buckets_new_epoch={(len(queries) - n_pre) // batch};"
        f"stale_or_mixed=False(asserted over all {len(queries)} queries)"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated catalog sizes (unified flag; "
                         "default: --items)")
    ap.add_argument("--items", type=int, default=262_144,
                    help="catalog rows (256k default; nightly runs 1M; "
                         "--sizes wins when both are given)")
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--wave", type=int, default=256,
                    help="queries per serve_many call (updates land "
                         "between waves)")
    ap.add_argument("--dirty-frac", type=float, default=0.01,
                    help="fraction of rows resident in the delta shard")
    ap.add_argument("--updates-per-wave", type=int, default=256)
    ap.add_argument("--scan-block", type=int, default=4096,
                    help="engine scan_block (streaming plan); 0 = dense")
    ap.add_argument("--depth", type=int, default=3,
                    help="AsyncServer ring depth for the epoch-swap phase")
    ap.add_argument("--repeats", type=int, default=2,
                    help="measured passes per qps cell (first doubles as "
                         "warmup; best pass reported)")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    args = ap.parse_args()
    sizes = (tuple(int(s) for s in args.sizes.split(","))
             if args.sizes else (args.items,))

    from repro.launch.runtime import (
        SINGLE_THREAD_EIGEN,
        add_cpu_xla_flags,
        enable_compile_cache,
    )

    add_cpu_xla_flags(SINGLE_THREAD_EIGEN)  # before jax creates backends
    enable_compile_cache()

    from benchmarks.bench_io import csv_rows_to_json, write_bench_json

    out = []
    for n_items in sizes:
        out.extend(rows(n_items, args.queries, args.batch, args.wave,
                        args.dirty_frac, args.updates_per_wave,
                        args.scan_block, args.depth, args.repeats))
    for name, us, derived in out:
        print(f"{name},{us:.6f},{derived}")
    path = write_bench_json(
        "catalog_churn", csv_rows_to_json(out), out_dir=args.out,
        config={"sizes": sizes, "queries": args.queries,
                "batch": args.batch, "wave": args.wave,
                "dirty_frac": args.dirty_frac,
                "updates_per_wave": args.updates_per_wave,
                "scan_block": args.scan_block, "depth": args.depth,
                "repeats": args.repeats})
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
