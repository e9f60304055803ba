"""Pipelined vs synchronous serving: qps and latency through the same engine.

The synchronous `MicroBatcher` serializes host work (stacking/padding the
next bucket, converting and fanning out the previous bucket's results)
against device compute, and serializes consecutive buckets' NNS scans
behind each other — exactly the lookup/scan overlap iMARS builds into
hardware. The pipelined `AsyncServer` recovers both:

  * a ring of in-flight buckets dispatched through the staged serve
    pipeline (lookup -> scan -> rank) overlaps host prep and result
    fan-out with device compute (JAX async dispatch, no threads);
  * on an engine sharded with a query mesh axis, consecutive full buckets
    coalesce into one routed super-batch whose buckets scan **disjoint
    query blocks in parallel** (2 fake CPU devices here, same mechanism
    as the `streaming_qp2` cells in benchmarks/nns_scale.py).

This benchmark serves the *same* query stream through the synchronous
path, the pipelined-only path, and the pipelined+routed path on this host
and reports qps, per-wave p50/p99 wall latency, and the
pipelined-over-synchronous speedup at batch 256 with the >= 1.2x target
(acceptance gate; bit-for-bit equality with the synchronous path is
asserted here and in tests/test_async_serving.py — the pipeline may only
move time, never results).

The engine runs the *streaming* filtering plan by default (scan_block=4096
at a 16k catalog — the million-item operating point scaled to bench
runtime; `--scan-block 0` switches to the dense plan), so the scan
dominates exactly as it does at production scale.

  PYTHONPATH=src python -m benchmarks.async_serving
      [--sizes 16384] [--batch 256] [--queries 2048] [--scan-block 4096]
      [--depth 2] [--devices 2] [--wave 1024] [--repeats 2] [--out DIR]

``--sizes`` (comma-separated catalog sizes), ``--repeats``, and ``--out``
are the flags every serving benchmark shares, so tools/bench_compare.py
can diff any pair of artifacts without per-benchmark special cases.

Variance control on the CPU platform (``JAX_PLATFORMS=cpu``): unless the
caller already set it, ``--xla_cpu_multi_thread_eigen=false`` is appended
to XLA_FLAGS before jax loads (Eigen's intra-op thread pool thrashing a
small host's cores was the dominant run-to-run jitter), together with the
``--devices`` virtual CPU devices; neither flag is set on any other
platform, where the routed mesh takes the first ``--devices`` real
devices. ``--devices 4`` rehearses the four-chip query mesh on the CPU.
Every server is measured
``--repeats`` times with the best run reported — the first measured pass
doubles as a thermal/allocator warmup on top of the compile-off-the-clock
wave. Emits BENCH_async_serving.json (see benchmarks/bench_io.py).
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

    data = synthetic.make_movielens(n_users=2000, n_items=n_items,
                                    history_len=history_len)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=history_len)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=data.n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=50,
                                top_k=10, hot_rows=hot_rows, item_freqs=freqs,
                                scan_block=scan_block)
    return engine, data


def _measure(server, queries, wave: int):
    """Serve `queries` in `wave`-sized waves; (qps, p50_ms, p99_ms, items).

    A wave holds several buckets so the pipelined server's ring actually
    fills; the synchronous server drains the same waves bucket by bucket.
    """
    import numpy as np

    served, wave_ms = [], []
    t0 = time.perf_counter()
    for lo in range(0, len(queries), wave):
        w0 = time.perf_counter()
        served.extend(server.serve_many(queries[lo: lo + wave]))
        wave_ms.append((time.perf_counter() - w0) * 1e3)
    dt = time.perf_counter() - t0
    lat = np.percentile(wave_ms, [50, 99])
    return len(queries) / dt, lat[0], lat[1], np.stack(
        [s.items for s in served])


def rows(batch: int, n_queries: int, n_items: int, depth: int,
         n_devices: int, wave: int, scan_block: int | None,
         repeats: int = 2):
    import jax
    import numpy as np

    from repro.data.synthetic import serving_queries
    from repro.launch.mesh import make_mesh
    from repro.serving import make_server

    engine, data = _setup(n_items, scan_block)
    rng = np.random.default_rng(0)
    queries = serving_queries(data, rng.integers(0, data.n_users, n_queries))
    warm = serving_queries(data, rng.integers(0, data.n_users, wave))

    servers = [
        ("sync", make_server(engine, "sync", max_batch=batch,
                             buckets=(batch,))),
        ("pipelined", make_server(engine, "pipelined", max_batch=batch,
                                  buckets=(batch,), depth=depth)),
    ]
    if n_devices > 1 and jax.device_count() >= n_devices:
        mesh = make_mesh((n_devices,), ("qp",),
                         devices=jax.devices()[:n_devices])
        routed = engine.shard(mesh, query_axis="qp")
        servers.append((
            f"pipelined_routed_qp{n_devices}",
            make_server(routed, "pipelined", max_batch=batch,
                        buckets=(batch,), depth=depth)))

    out, qps, base_items = [], {}, None
    for name, server in servers:
        server.serve_many(warm)  # compile every wave shape off the clock
        # best of `repeats` measured passes: run 1 doubles as warmup, the
        # best run is the least-preempted one on this noisy 2-core host
        q, p50, p99, items = max(
            (_measure(server, queries, wave) for _ in range(max(repeats, 1))),
            key=lambda r: r[0])
        qps[name] = q
        if base_items is None:
            base_items = items
        bitmatch = bool((items == base_items).all())
        out.append((
            f"serving/async/{name}_batch{batch}_n{n_items}", 1e6 / q,
            f"qps={q:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f};"
            f"bitmatch_sync={bitmatch};"
            f"platform={jax.devices()[0].platform}",
        ))
        assert bitmatch, f"{name} diverged from the synchronous path"
    best = max(q for name, q in qps.items() if name != "sync")
    speedup = best / qps["sync"]
    out.append((
        f"serving/async/pipelined_speedup_n{n_items}", 0.0,
        f"pipelined_over_sync={speedup:.2f}x(target >=1.2x);"
        f"ok={speedup >= 1.2};batch={batch};items={n_items};depth={depth}",
    ))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated catalog sizes (unified flag; "
                         "default: --items)")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--items", type=int, default=16384,
                    help="catalog size (alias kept for back-compat; "
                         "--sizes wins when both are given)")
    ap.add_argument("--scan-block", type=int, default=4096,
                    help="engine scan_block: the streaming filtering plan "
                         "(the million-item operating point, scaled to "
                         "bench runtime); 0 forces the dense plan")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--devices", type=int, default=2,
                    help="devices of the routed query mesh (virtual CPU "
                         "devices under JAX_PLATFORMS=cpu; 1 disables "
                         "routing)")
    ap.add_argument("--wave", type=int, default=1024,
                    help="queries submitted per serve_many call")
    ap.add_argument("--repeats", type=int, default=2,
                    help="measured passes per server (first doubles as "
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

    # before jax creates its backends; on the CPU platform only
    add_cpu_xla_flags(SINGLE_THREAD_EIGEN, *(
        [f"--xla_force_host_platform_device_count={args.devices}"]
        if args.devices > 1 else []))
    enable_compile_cache()

    from benchmarks.bench_io import csv_rows_to_json, write_bench_json

    out = []
    for n_items in sizes:
        out.extend(rows(args.batch, args.queries, n_items, args.depth,
                        args.devices, args.wave, args.scan_block,
                        args.repeats))
    for name, us, derived in out:
        print(f"{name},{us:.6f},{derived}")
    path = write_bench_json(
        "async_serving", csv_rows_to_json(out), out_dir=args.out,
        config={"batch": args.batch, "queries": args.queries,
                "sizes": sizes, "scan_block": args.scan_block,
                "depth": args.depth, "devices": args.devices,
                "wave": args.wave, "repeats": args.repeats})
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
