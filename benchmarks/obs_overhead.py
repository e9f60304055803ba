"""Telemetry overhead gate: instrumented serving must keep >= 0.95x qps.

Observability that taxes the serving path gets turned off in production —
so the telemetry layer (src/repro/obs/: per-ticket stage spans, the
metrics registry, the exporters) carries an enforced overhead budget.
This benchmark drives the same query stream through each front-end twice
— ``trace=True`` (spans + registry histograms live) and ``trace=False``
(bare counters) — and **fails** (exit 1) unless, per mode:

  * instrumented qps >= 0.95x uninstrumented qps — judged on the best
    back-to-back traced/untraced pass pair out of ``--repeats``, so a
    noisy host phase (CI containers share cores) lands on both arms of
    a pair instead of reading as overhead — and
  * the per-ticket stage breakdown is *consistent*: the mean stage-span
    sum is within 10% of the mean measured submit->resolve latency on the
    traced pass (span chains are contiguous by construction, so this
    catches a front-end dropping or misordering a boundary).

Artifacts: ``BENCH_obs_overhead.json`` with ``overhead_frac=`` per row
(diffed lower-is-better by tools/bench_compare.py) and the traced pass's
full registry snapshot embedded as the top-level ``telemetry`` key
(schema-checked by `bench_io.check_telemetry_schema`); the traced
tickets as ``obs_trace_<mode>.jsonl`` next to it — the input of
``python tools/obs_report.py``.

  PYTHONPATH=src python -m benchmarks.obs_overhead
      [--smoke] [--sizes 64] [--repeats 5] [--out DIR]

``--smoke`` shrinks the cell for the CI fast lane; ``--sizes`` sweeps
batch sizes (the unified serving-benchmark flags).
"""
import argparse
import os
import sys
import time

import numpy as np

from repro.data.synthetic import serving_queries as _queries
from repro.launch.runtime import enable_compile_cache
from repro.obs import dump_trace
from repro.serving import make_server

from benchmarks.serving_throughput import _setup

MODES = ("sync", "pipelined")
GATE_MIN_RATIO = 0.95  # instrumented / uninstrumented qps floor
BREAKDOWN_TOL = 0.10  # |stage-sum mean - latency mean| / latency mean


def _pass_qps(server, queries, batch: int) -> float:
    """One timed pass of the full stream through `server`, as qps."""
    n = len(queries)
    t0 = time.perf_counter()
    for lo in range(0, n, batch):
        server.serve_many(queries[lo: lo + batch])
    return n / (time.perf_counter() - t0)


def _measure(engine, data, mode: str, batch: int, n_queries: int,
             repeats: int):
    """(best traced qps, best untraced qps, ratio, records, registry).

    The two arms run genuinely interleaved — traced pass, untraced pass,
    traced pass, ... with the order flipped every repeat — and the gate
    ratio is the best *per-repeat pair* (traced qps / untraced qps of
    two back-to-back passes). Intrinsic tracing cost taxes every pair,
    so a real regression drags the best pair down with it; a noisy
    neighbour on a shared CI core slows one pair but not all of them,
    and comparing across pairs (best-of-each-arm) would misread that
    noise as overhead.
    """
    rng = np.random.default_rng(0)
    servers = {
        arm: make_server(engine, mode, max_batch=batch, buckets=(batch,),
                         trace=arm)
        for arm in (True, False)
    }
    queries = _queries(data, rng.integers(0, data.n_users, n_queries))
    for server in servers.values():
        # warm off the clock: compile, fill the ring, settle allocators —
        # a full pass, not one chunk, or pass 1 still pays warmup and the
        # arm measured first reads as slower than it is
        _pass_qps(server, queries, batch)
        _pass_qps(server, queries, batch)
        server.take_trace()
    best = {True: 0.0, False: 0.0}
    best_ratio = 0.0
    records: list = []
    for r in range(max(repeats, 1)):
        pair = {}
        for arm in ((True, False) if r % 2 == 0 else (False, True)):
            servers[arm].take_trace()
            pair[arm] = qps = _pass_qps(servers[arm], queries, batch)
            if qps > best[arm]:
                best[arm] = qps
                if arm:
                    records = servers[arm].take_trace()
        if pair[False]:
            best_ratio = max(best_ratio, pair[True] / pair[False])
    return (best[True], best[False], best_ratio, records,
            servers[True].registry)


def _breakdown_gap(records) -> tuple[float, dict]:
    """Fractional gap between mean stage-sum and mean measured latency."""
    from tools.obs_report import stage_breakdown

    bd = stage_breakdown(records, status="ok")
    lat = bd["latency_s"]["mean"]
    if not lat:
        return float("inf"), bd
    return abs(bd["stage_sum_mean_s"] - lat) / lat, bd


def run(batch_sizes, repeats: int, smoke: bool, out_dir):
    engine, data, _, _, _ = _setup()
    n_queries = 256 if smoke else 1024
    rows, telemetry, failures = [], None, []
    for mode in MODES:
        for batch in batch_sizes:
            qps_on, qps_off, ratio, records, registry = _measure(
                engine, data, mode, batch, n_queries, repeats)
            overhead = max(0.0, 1.0 - ratio)
            gap, bd = _breakdown_gap(records)
            ok = ratio >= GATE_MIN_RATIO and gap <= BREAKDOWN_TOL
            if ratio < GATE_MIN_RATIO:
                failures.append(
                    f"{mode}/batch{batch}: best traced/untraced pair is "
                    f"{ratio:.3f}x (floor {GATE_MIN_RATIO}x; best qps "
                    f"{qps_on:.0f} traced / {qps_off:.0f} untraced)")
            if gap > BREAKDOWN_TOL:
                failures.append(
                    f"{mode}/batch{batch}: stage-sum vs latency gap "
                    f"{gap:.1%} exceeds {BREAKDOWN_TOL:.0%}")
            rows.append((
                f"obs/overhead/{mode}/batch{batch}", 1e6 / qps_on,
                f"qps={qps_on:.0f};qps_untraced={qps_off:.0f};"
                f"overhead_frac={overhead:.4f};breakdown_gap={gap:.4f};"
                f"ok={ok}",
            ))
            if telemetry is None:
                telemetry = registry.snapshot()  # first traced cell
            if out_dir is not None and batch == batch_sizes[0]:
                n = dump_trace(records, os.path.join(
                    out_dir, f"obs_trace_{mode}.jsonl"))
                print(f"# dumped {n} traced tickets for mode={mode}")
    return rows, telemetry, failures


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default="64",
                    help="comma-separated batch sizes (unified flag)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="interleaved passes per arm (best reported)")
    ap.add_argument("--smoke", action="store_true",
                    help="small cell for the CI fast lane")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    args = ap.parse_args()
    batch_sizes = tuple(int(s) for s in args.sizes.split(","))
    out_dir = args.out or os.environ.get("BENCH_OUT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)

    from benchmarks.bench_io import (check_row_schema,
                                     check_telemetry_schema,
                                     csv_rows_to_json, write_bench_json)

    rows, telemetry, failures = run(batch_sizes, args.repeats, args.smoke,
                                    out_dir)
    for name, us, derived in rows:
        print(f"{name},{us:.6f},{derived}")
    json_rows = csv_rows_to_json(rows)
    check_row_schema(json_rows, ("qps", "overhead_frac"),
                     within=("obs/overhead/",))
    check_telemetry_schema(telemetry, required=(
        "serving.served", "serving.ticket_latency_s.count",
        "cache.lookups"))
    path = write_bench_json(
        "obs_overhead", json_rows, out_dir=out_dir,
        config={"batch_sizes": batch_sizes, "repeats": args.repeats,
                "smoke": args.smoke, "gate_min_ratio": GATE_MIN_RATIO},
        telemetry=telemetry)
    print(f"# wrote {path}")
    if failures:
        print("OVERHEAD GATE FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
