"""Offered-load sweep: p50/p99 latency per tenant + qps at a fixed p99 SLO.

Peak qps (benchmarks/serving_throughput.py, async_serving.py) is a
closed-loop number: the client stops offering load while the server
works. The number a datacenter actually provisions against is open-loop —
"at what offered rate does the p99 still meet the SLO?" — because beyond
saturation an open-loop queue grows without bound and p99 collapses
first. This benchmark drives the concurrent multi-tenant front-end
(`make_server(engine, mode="concurrent")`: bounded per-tenant queues +
admission control + load shedding over the `AsyncServer` ring) with the
open-loop generator (`serving/load_gen.py`: Poisson arrivals, Zipf query
popularity, optional bursty phases) and reports, per catalog size:

  * measured closed-loop capacity (the load scale's 1.0x anchor);
  * per-load-fraction, per-tenant p50/p99 latency, achieved goodput, and
    shed fraction — the latency-vs-offered-load curve;
  * ``qps_at_slo`` — the largest achieved goodput among loads whose
    admitted p99 meets the SLO: the provisioning number;
  * the overload contract at the top load (>= 2x capacity): the
    front-end **sheds** (rejects are accounted per tenant, errors are
    zero, every submit is accounted), admitted p99 stays **bounded** (by
    queue depth / capacity — not by the offered rate), and admitted
    results **bit-match** synchronous serving of the same stream
    (asserted here; shedding moves admission, never the bits).

  PYTHONPATH=src python -m benchmarks.load_sweep
      [--sizes 16384] [--batch 64] [--tenants 4] [--queue-depth N]
      [--duration 4.0] [--loads 0.25,0.5,0.75,1.0,1.5,2.0]
      [--slo-ms MS] [--zipf-a 1.1] [--burst PERIOD,DUTY,MULT]
      [--pool 512] [--depth 2] [--repeats 2] [--out DIR] [--smoke]

``--smoke`` is the CI fast-lane preset: 2 tenants, a ~2-second 2-point
sweep (0.6x and 2.5x) on a small dense-plan catalog, fixed seed. The
nightly lane runs the full sweep and uploads the artifact. Variance
control mirrors benchmarks/async_serving.py (Eigen single-thread XLA
flag, best-of-``--repeats`` by lowest p99). Emits BENCH_load_sweep.json.
"""
from __future__ import annotations

import argparse
import sys
import time

DEFAULT_LOADS = (0.25, 0.5, 0.75, 1.0, 1.5, 2.0)
SMOKE = dict(sizes=(4096,), loads=(0.6, 2.5), duration=1.2, tenants=2,
             batch=32, pool=256, repeats=1, scan_block=0)


def _measure_capacity(engine, pool, batch: int, repeats: int) -> float:
    """Closed-loop qps through the synchronous front-end (the 1.0x anchor)."""
    from repro.serving import make_server

    n = max(4 * batch, 256)
    queries = [pool[i % len(pool)] for i in range(n)]
    server = make_server(engine, "sync", max_batch=batch, buckets=(batch,))
    server.serve_many(queries[:batch])  # compile off the clock
    best = 0.0
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        server.serve_many(queries)
        best = max(best, n / (time.perf_counter() - t0))
    return best


def _run_load(engine, pool, *, rate, duration, tenants, queue_depth, batch,
              depth, zipf_a, burst, seed, repeats):
    """One open-loop cell; returns (summary, replay, results, telemetry
    snapshot) of the best (lowest admitted p99) of `repeats` passes."""
    from repro.serving import LoadGen, make_server, summarize_trace

    best = None
    for rep in range(max(repeats, 1)):
        server = make_server(engine, "concurrent", tenants=tenants,
                             queue_depth=queue_depth, max_batch=batch,
                             buckets=(batch,), depth=depth)
        # compile / warm every tenant path off the clock, then clear trace
        for t in range(tenants):
            server.serve_many(pool[:batch], tenant=t)
        server.take_trace()
        gen = LoadGen(rate_qps=rate, duration_s=duration, tenants=tenants,
                      pool_size=len(pool), zipf_a=zipf_a, burst=burst,
                      seed=seed)  # same seed every pass: identical offers
        replay = gen.replay(server, pool)
        server.flush()
        trace = server.take_trace()
        results = {t: server.result(t) for (t, _, _) in replay}
        snap = server.snapshot()
        server.close()
        summary = summarize_trace(trace, duration)
        key = summary.p99_ms if summary.p99_ms == summary.p99_ms else 1e12
        if best is None or key < best[0]:
            best = (key, summary, replay, results, snap)
    return best[1], best[2], best[3], best[4]


def _assert_bitmatch(engine, pool, replay, results, batch: int) -> int:
    """Admitted results == synchronous serving of the admitted stream."""
    import numpy as np

    from repro.serving import make_server

    admitted = [(qi, results[t]) for (t, _, qi) in replay
                if results[t].status == "ok"]
    if not admitted:
        return 0
    ref = make_server(engine, "sync", max_batch=batch,
                      buckets=(batch,)).serve_many(
                          [pool[qi] for qi, _ in admitted])
    for (qi, got), want in zip(admitted, ref):
        if not (np.array_equal(got.items, want.items)
                and np.array_equal(got.scores, want.scores)):
            raise AssertionError(
                f"admitted query (pool index {qi}) diverged from "
                f"synchronous serving")
    return len(admitted)


def rows(args):
    import numpy as np  # noqa: F401  (summaries carry numpy scalars)

    from benchmarks.async_serving import _setup
    from repro.data.synthetic import serving_queries

    out = []
    telemetry = None
    for n_items in args.sizes:
        engine, data = _setup(n_items, args.scan_block or None)
        rng_pool = min(args.pool, data.n_users)
        pool = serving_queries(data, range(rng_pool))
        cap = _measure_capacity(engine, pool, args.batch, args.repeats)
        slo_ms = args.slo_ms or max(25.0, 8e3 * args.batch / cap)
        # queue sized so a full queue drains in ~one SLO: the structural
        # bound on admitted latency under any overload
        queue_depth = args.queue_depth or max(
            args.batch, int(cap * slo_ms / 1e3 / args.tenants))
        bound_ms = 3e3 * queue_depth * args.tenants / cap + 3 * slo_ms
        out.append((f"load_sweep/capacity_n{n_items}", 1e6 / cap,
                    f"qps={cap:.0f};batch={args.batch};closed_loop=True"))

        qps_at_slo, sweep = 0.0, []
        for i, frac in enumerate(args.loads):
            summary, replay, results, telemetry = _run_load(
                engine, pool, rate=frac * cap, duration=args.duration,
                tenants=args.tenants, queue_depth=queue_depth,
                batch=args.batch, depth=args.depth, zipf_a=args.zipf_a,
                burst=args.burst, seed=args.seed + i, repeats=args.repeats)
            sweep.append((frac, summary, replay, results))
            meets = summary.p99_ms <= slo_ms
            if meets:
                qps_at_slo = max(qps_at_slo, summary.achieved_qps)
            out.append((
                f"load_sweep/load{frac:g}x_n{n_items}",
                summary.p99_ms * 1e3,
                f"p50_ms={summary.p50_ms:.1f};p99_ms={summary.p99_ms:.1f};"
                f"offered_qps={summary.offered_qps:.0f};"
                f"achieved_qps={summary.achieved_qps:.0f};"
                f"shed_frac={summary.shed_frac:.3f};meets_slo={meets}"))
            for t, s in summary.per_tenant.items():
                out.append((
                    f"load_sweep/load{frac:g}x_n{n_items}/tenant{t}", 0.0,
                    f"p50_ms={s['p50_ms']:.1f};p99_ms={s['p99_ms']:.1f};"
                    f"offered_qps={s['offered_qps']:.0f};"
                    f"achieved_qps={s['achieved_qps']:.0f};"
                    f"shed_frac={s['shed_frac']:.3f}"))

        out.append((
            f"load_sweep/qps_at_slo_n{n_items}", 0.0,
            f"qps_at_slo={qps_at_slo:.0f};slo_ms={slo_ms:.1f};"
            f"capacity_qps={cap:.0f};ok={qps_at_slo > 0}"))

        # ---- overload contract at the top load ------------------------
        frac, summary, replay, results = sweep[-1]
        n_matched = _assert_bitmatch(engine, pool, replay, results,
                                     args.batch)
        per_t = summary.per_tenant.values()
        accounted = all(s["n_ok"] + s["n_shed"] + s["n_errors"] ==
                        round(s["offered_qps"] * summary.duration_s)
                        for s in per_t)
        shed_ok = (summary.shed_frac > 0) if frac >= 1.5 else True
        bounded = summary.p99_ms <= bound_ms
        ok = accounted and shed_ok and bounded and summary.error_frac == 0
        out.append((
            f"load_sweep/overload{frac:g}x_n{n_items}", 0.0,
            f"shed_frac={summary.shed_frac:.3f};p99_ms={summary.p99_ms:.1f};"
            f"bound_ms={bound_ms:.1f};errors={summary.error_frac:.3f};"
            f"accounted={accounted};bitmatch_sync=True(n={n_matched});"
            f"ok={ok}"))
        if not ok:
            raise AssertionError(
                f"overload contract violated at {frac}x (n={n_items}): "
                f"shed_frac={summary.shed_frac:.3f}, "
                f"p99={summary.p99_ms:.1f}ms (bound {bound_ms:.1f}ms), "
                f"errors={summary.error_frac:.3f}, accounted={accounted}")
        # the low-load end must bit-match too (shed-free path)
        _assert_bitmatch(engine, pool, sweep[0][2], sweep[0][3], args.batch)
    return out, telemetry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default="16384",
                    help="comma-separated catalog sizes (unified flag)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="per-tenant queue bound (default: sized so a "
                         "full queue drains in ~one SLO)")
    ap.add_argument("--duration", type=float, default=4.0,
                    help="open-loop seconds per load point")
    ap.add_argument("--loads", type=str,
                    default=",".join(str(f) for f in DEFAULT_LOADS),
                    help="offered load as fractions of measured capacity")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="p99 SLO (default: 8 batch-times, min 25ms)")
    ap.add_argument("--zipf-a", type=float, default=1.1)
    ap.add_argument("--burst", type=str, default=None,
                    help="PERIOD_S,DUTY_FRAC,MULT bursty-phase spec")
    ap.add_argument("--pool", type=int, default=512,
                    help="distinct queries in the Zipf pool")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--scan-block", type=int, default=4096,
                    help="engine scan_block (streaming plan); 0 = dense")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="passes per load point (best = lowest p99)")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast-lane preset: 2 tenants, ~2s, 2 loads")
    args = ap.parse_args()

    from repro.launch.runtime import (
        SINGLE_THREAD_EIGEN,
        add_cpu_xla_flags,
        enable_compile_cache,
    )

    add_cpu_xla_flags(SINGLE_THREAD_EIGEN)  # before jax creates backends
    enable_compile_cache()

    if args.smoke:
        args.sizes, args.loads = SMOKE["sizes"], SMOKE["loads"]
        args.duration, args.tenants = SMOKE["duration"], SMOKE["tenants"]
        args.batch, args.pool = SMOKE["batch"], SMOKE["pool"]
        args.repeats, args.scan_block = SMOKE["repeats"], SMOKE["scan_block"]
    else:
        args.sizes = tuple(int(s) for s in args.sizes.split(","))
        args.loads = tuple(float(f) for f in args.loads.split(","))
    if isinstance(args.burst, str):
        p, d, m = args.burst.split(",")
        args.burst = (float(p), float(d), float(m))

    from benchmarks.bench_io import (check_telemetry_schema,
                                     csv_rows_to_json, write_bench_json)

    out, telemetry = rows(args)
    for name, us, derived in out:
        print(f"{name},{us:.3f},{derived}")
    check_telemetry_schema(telemetry, required=("serving.submitted",
                                                "serving.per_tenant"))
    path = write_bench_json(
        "load_sweep", csv_rows_to_json(out), out_dir=args.out,
        config={"sizes": args.sizes, "batch": args.batch,
                "tenants": args.tenants, "queue_depth": args.queue_depth,
                "duration": args.duration, "loads": args.loads,
                "slo_ms": args.slo_ms, "zipf_a": args.zipf_a,
                "burst": args.burst, "pool": args.pool,
                "depth": args.depth, "scan_block": args.scan_block,
                "seed": args.seed, "repeats": args.repeats,
                "smoke": args.smoke},
        telemetry=telemetry)
    print(f"# wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
