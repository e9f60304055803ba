"""Serving throughput: queries/sec vs batch size and hot-cache size.

The iMARS claim is architectural (keep ET traffic inside the memory fabric);
the software image of the same win is (a) amortizing dispatch over micro-
batches and (b) serving hot ET rows from a dense f32 cache. This benchmark
measures both on the actual jitted pipeline of this host:

  * qps at batch sizes 1 / 8 / 64 / 256 through the synchronous front-end
    (compile excluded; the batch-256 row must be >= 5x the batch-1 row)
  * measured hot-cache hit rate at several cache capacities under the
    skewed synthetic MovieLens item popularity.

  PYTHONPATH=src python -m benchmarks.serving_throughput
      [--sizes 1,8,64,256] [--repeats 1] [--out DIR]

``--sizes`` here sweeps **batch** sizes (the quantity this benchmark
varies); ``--sizes``/``--repeats``/``--out`` are the flags every serving
benchmark shares, so tools/bench_compare.py can diff any pair of
artifacts without per-benchmark special cases. Front-ends come from
`make_server` (the unified Server API); cache counters come from
`stats()`. Emits BENCH_serving_throughput.json (see benchmarks/bench_io.py).
"""
import argparse
import time

import jax
import numpy as np

from repro.data import synthetic
from repro.data.synthetic import serving_queries as _queries
from repro.launch.runtime import enable_compile_cache
from repro.models import recsys as rs
from repro.serving import RecSysEngine, make_server

BATCH_SIZES = (1, 8, 64, 256)
CACHE_SIZES = (0, 64, 256)


def _setup(n_users=2000, n_items=1200, history_len=12, hot_rows=256):
    data = synthetic.make_movielens(n_users=n_users, n_items=n_items,
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
                                top_k=10, hot_rows=hot_rows, item_freqs=freqs)
    return engine, data, params, cfg, freqs


def _measure_qps(engine, data, batch: int, n_queries: int,
                 repeats: int = 1) -> tuple[float, float, dict]:
    """(queries/sec, hit_rate, telemetry snapshot) through the sync
    front-end at one bucket size; best of `repeats` measured passes."""
    rng = np.random.default_rng(0)
    server = make_server(engine, "sync", max_batch=batch, buckets=(batch,))
    # warmup: compile this bucket shape
    server.serve_many(_queries(data, rng.integers(0, data.n_users, batch)))
    idx = rng.integers(0, data.n_users, n_queries)
    queries = _queries(data, idx)
    best = 0.0
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        for lo in range(0, n_queries, batch):
            server.serve_many(queries[lo: lo + batch])
        best = max(best, n_queries / (time.perf_counter() - t0))
    snap = server.snapshot()
    return best, snap["cache.hits"] / max(snap["cache.lookups"], 1), snap


def rows(batch_sizes=BATCH_SIZES, repeats: int = 1):
    engine, data, params, cfg, freqs = _setup()
    out = []
    qps = {}
    telemetry = None
    for batch in batch_sizes:
        n = max(64, min(1024, batch * 4))
        q, hit, telemetry = _measure_qps(engine, data, batch, n, repeats)
        qps[batch] = q
        out.append((
            f"serving/throughput/batch{batch}", 1e6 / q,
            f"qps={q:.0f};hot_hit_rate={hit:.3f};host=CPU(container)",
        ))
    if 1 in qps and 256 in qps:
        speedup = qps[256] / qps[1]
        out.append((
            "serving/throughput/batched_speedup", 0.0,
            f"qps256_over_qps1={speedup:.1f}x(target >=5x);ok={speedup >= 5}",
        ))
    # hit rate vs cache capacity (same skewed popularity, batch 64)
    for cap in CACHE_SIZES:
        eng = RecSysEngine.build(params, cfg, radius=112, n_candidates=50,
                                 top_k=10, hot_rows=cap, item_freqs=freqs)
        _, hit, _ = _measure_qps(eng, data, 64, 256)
        out.append((
            f"serving/hot_cache/capacity{cap}", 0.0,
            f"hot_hit_rate={hit:.3f};items={data.n_items}",
        ))
    return out, telemetry


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str,
                    default=",".join(str(b) for b in BATCH_SIZES),
                    help="comma-separated batch sizes (unified flag)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="measured passes per cell (best pass reported)")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    args = ap.parse_args()
    batch_sizes = tuple(int(s) for s in args.sizes.split(","))

    from benchmarks.bench_io import (check_telemetry_schema,
                                     csv_rows_to_json, write_bench_json)

    out, telemetry = rows(batch_sizes, args.repeats)
    for name, us, derived in out:
        print(f"{name},{us:.6f},{derived}")
    check_telemetry_schema(telemetry, required=("serving.served",
                                                "cache.lookups"))
    path = write_bench_json(
        "serving_throughput", csv_rows_to_json(out), out_dir=args.out,
        config={"batch_sizes": batch_sizes, "cache_sizes": CACHE_SIZES,
                "repeats": args.repeats},
        telemetry=telemetry)
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
