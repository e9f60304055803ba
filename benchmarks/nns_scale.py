"""Filtering-stage scaling: dense vs streaming fixed-radius NNS.

The iMARS filtering stage scans the *entire* item signature bank per query.
The dense software path materializes a (q, n) int32 distance matrix — at the
million-item north star that is gigabytes per batch and the capacity wall of
the pipeline. The streaming path (`scan_block`) holds O(q * max_candidates)
instead, and past the 4.19M-row packed-key capacity it scans as offset
superblocks (wide keys) — the 8M/16M cells in the --full sweep exercise that
wide path end to end. This benchmark sweeps catalog size and records, per
path:

  * queries/sec through the jitted `fixed_radius_nns`
  * peak incremental RSS during the scan (compile + steady state)
  * a bit-match check against the dense oracle on a query slice, on every
    cell where the dense matrix for that slice fits in RAM (including the
    8M/16M wide-key cells)

Paths: `streaming` (single device), `dense` (until the OOM guard),
`streaming_qp2` at >= 1M items — the streaming scan shard_mapped over a
2-way query mesh axis (2 virtual CPU devices under ``JAX_PLATFORMS=cpu``,
else the first 2 real devices), the query-block-parallel knob —
and the Zipf-skewed pair `zipf_stream` / `zipf_pruned`: the same streaming
scan over a clustered catalog with Zipf cluster sizes and Zipf query
popularity (the workload shape block-summary pruning targets), without and
with the `BlockSummary` prune mask. The pruned cell records
`blocks_touched` / `scan_frac` (per-query mean fraction of summary blocks
admitted), bit-matches the unpruned scan on the full batch in-cell, and
its row carries `speedup_vs_unpruned` against the zipf_stream cell at the
same size — `--assert-scan-frac` turns the scan_frac < ceiling, bit-match,
and >= 1.2x speedup (at >= 1M rows) checks into hard exit codes for the
nightly lane.

Each (size, path) cell runs in a *fresh subprocess* so `ru_maxrss` deltas
are real per-cell peaks, not shadows of an earlier phase's high-water mark
(the dense top-k at 65k items already pushes ~0.5 GiB of sort workspace).
Dense is skipped (OOM guard) once its distance matrix alone would exceed
DENSE_MAX_BYTES; the streaming path must hold >= 1M items on CPU with peak
incremental memory under 10% of the dense matrix it replaces.

  PYTHONPATH=src python -m benchmarks.nns_scale [--full] [--sizes N,N,...]
      [--repeats 2] [--out DIR] [--assert-stream-mem BYTES]

``--sizes``/``--repeats``/``--out`` are the flags every benchmark shares;
the artifact's ``rows`` are the same csv-shaped dicts every benchmark
emits (so tools/bench_compare.py diffs any pair without special cases)
and the raw per-cell measurements ride in the ``cells`` key.

`--assert-stream-mem` exits non-zero if any streaming cell fails its memory
contract (the nightly CI lane runs the 8M cell under a hard RSS budget).
Emits BENCH_nns_scale.json (see benchmarks/bench_io.py).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

SIZES = (65_536, 262_144, 1_048_576)
FULL_SIZES = SIZES + (4_194_304, 8_388_608, 16_777_216)
Q = 128  # concurrent queries per scan (one serving micro-batch)
Q_ORACLE = 2  # query slice for the dense bit-match check on big cells
WORDS = 8  # 256-bit signatures
RADIUS = 96
MAX_CANDIDATES = 128
SCAN_BLOCK = 4096
DENSE_MAX_BYTES = 1 << 28  # skip dense when (q, n) int32 alone exceeds 256 MiB
REPS = 2  # default --repeats (steady-state scans averaged per cell)

# Zipf-skewed cells: clustered catalog (Zipf cluster sizes, Zipf query
# popularity) with intra-cluster noise confined to a few designated bit
# positions, so block OR/AND summaries stay tight — the layout pruning is
# built for. Radius admits a whole cluster (queries sit <= 5 flips from
# their center) while cross-cluster distances concentrate near 128.
# Query popularity runs ANTI-aligned with cluster size — the recsys hot-set
# shape: a compact set of hot clusters takes most of the traffic while the
# bulky legacy clusters go cold. That anti-alignment is what makes the
# batch-level prune union sublinear; popularity aligned with mass would
# re-touch most of the catalog every batch no matter how sound the bound.
ZIPF_CLUSTERS = 128
ZIPF_EXPONENT = 1.2
ZIPF_FLIP_POSITIONS = 24
ZIPF_RADIUS = 40
PRUNE_MIN_SPEEDUP = 1.2  # zipf_pruned vs zipf_stream qps, >= 1M rows


def scan_block_for(n: int) -> int:
    """Scan chunk: 4096 up to 1M rows (the PR-2 operating point), ramping to
    32k at 16M so per-chunk dispatch overhead stays off the critical path."""
    return min(32_768, max(SCAN_BLOCK, n // 512))


def _zipf_catalog(n: int, rng):
    """Clustered catalog + query batch with Zipf skew (see module docstring).

    Rows are grouped by cluster (contiguous runs of similar signatures)
    and every row/query differs from its cluster center only at the
    cluster's `ZIPF_FLIP_POSITIONS` designated bit positions — random
    flips over all 256 positions would saturate the block OR and the
    summary could never prune. Cluster boundaries align to this size's
    scan chunk (a multiple of the 4096-row summary block) so summary
    blocks stay single-cluster and the ref backend's chunk-granular skip
    maps 1:1 onto clusters. Query popularity is Zipf over clusters in
    REVERSE size order (see the constant block comment)."""
    import numpy as np

    c = ZIPF_CLUSTERS
    unit = scan_block_for(n)  # cluster-run granularity, multiple of 4096
    n_units = max((n + unit - 1) // unit, c)
    w = np.arange(1, c + 1, dtype=np.float64) ** -ZIPF_EXPONENT
    w /= w.sum()
    units = 1 + np.floor(w * (n_units - c)).astype(np.int64)
    units[0] += n_units - units.sum()
    centers = rng.integers(0, 2**32, size=(c, WORDS), dtype=np.uint32)
    pos = rng.integers(0, 32 * WORDS, size=(c, ZIPF_FLIP_POSITIONS))
    cluster = np.repeat(np.arange(c), units * unit)[:n]

    def perturb(owner, n_flips):
        out = centers[owner].copy()
        m = np.empty((owner.shape[0], WORDS), np.uint32)
        for _ in range(n_flips):
            p = pos[owner, rng.integers(0, ZIPF_FLIP_POSITIONS,
                                        size=owner.shape[0])]
            m[:] = 0
            m[np.arange(owner.shape[0]), p // 32] = (
                np.uint32(1) << (p % 32).astype(np.uint32))
            out ^= m
        return out

    db = perturb(cluster, 3)
    # hot queries hit the compact clusters: popularity w reversed over size
    q_cluster = rng.choice(c, size=Q, p=w[::-1])
    queries = perturb(q_cluster, 2)
    return queries, db


def _cell(n: int, path: str) -> dict:
    """One measurement in this process: build arrays, scan, report JSON."""
    from repro.launch.runtime import add_cpu_xla_flags, enable_compile_cache

    if path == "streaming_qp2":  # 2 virtual devices on the CPU platform
        add_cpu_xla_flags("--xla_force_host_platform_device_count=2")
    enable_compile_cache()

    import gc
    import resource
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.nns import (
        build_block_summary,
        fixed_radius_nns,
        query_parallel_nns,
    )
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    zipf = path.startswith("zipf")
    radius = ZIPF_RADIUS if zipf else RADIUS
    if zipf:
        queries_np, db_np = _zipf_catalog(n, rng)
    else:
        queries_np = rng.integers(0, 2**32, size=(Q, WORDS), dtype=np.uint32)
        db_np = rng.integers(0, 2**32, size=(n, WORDS), dtype=np.uint32)
    summary = build_block_summary(db_np) if path == "zipf_pruned" else None
    queries = jnp.asarray(queries_np)
    db = jnp.asarray(db_np)
    del db_np
    jax.block_until_ready(db)
    scan_block = scan_block_for(n) if path != "dense" else 0

    if path == "streaming_qp2":
        mesh = make_mesh((2,), ("qp",), devices=jax.devices()[:2])

        def fn(q):
            return query_parallel_nns(mesh, "qp", q, db, RADIUS,
                                      MAX_CANDIDATES, scan_block=scan_block)
    else:
        # summary=None on the unpruned paths: the prune-mask computation is
        # part of the pruned scan, so it sits inside the timed fn
        def fn(q):
            return fixed_radius_nns(q, db, radius, MAX_CANDIDATES,
                                    scan_block=scan_block, summary=summary)

    gc.collect()
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    t0 = time.perf_counter()
    res = fn(queries)
    jax.block_until_ready(res)  # compile + first scan
    reps = int(os.environ.get("NNS_SCALE_REPS", REPS))
    t1 = time.perf_counter()
    for _ in range(reps):
        res = fn(queries)
    jax.block_until_ready(res)
    steady = (time.perf_counter() - t1) / reps
    rss_delta = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 - rss0

    row = {"n": n, "q": Q, "path": path, "status": "ok",
           "qps": Q / steady, "us_per_query": 1e6 * steady / Q,
           "compile_and_first_s": t1 - t0,
           "rss_peak_delta_bytes": int(rss_delta),
           "dense_matrix_bytes": Q * n * 4,
           "scan_block": scan_block}
    if path in ("streaming", "zipf_stream", "zipf_pruned"):
        # every single-device streaming-family cell carries the memory
        # metric (this used to be the plain `streaming` row only, so the
        # zipf cells' rows pattern-matched as "memory fine" when it was
        # never measured — check_row_schema now pins the per-group schema).
        # The qp2 cells stay excluded: they replicate the catalog once per
        # fake device in-process, so 10%-of-dense would be meaningless
        # noise for them
        row["mem_lt_10pct_dense"] = bool(rss_delta < 0.1 * Q * n * 4)
    if path == "zipf_pruned":
        # scan_frac: per-query mean fraction of summary blocks the bound
        # admitted — the sublinearity headline. Pruned results must carry
        # exactly the unpruned scan's bits on the FULL batch (in-benchmark
        # assertion; `check_prune_contract` turns False into exit 1)
        touched = np.asarray(res.blocks_touched)
        row["blocks_touched_mean"] = float(touched.mean())
        row["n_summary_blocks"] = int(summary.n_blocks)
        row["scan_frac"] = float(touched.mean() / summary.n_blocks)
        plain = fixed_radius_nns(queries, db, radius, MAX_CANDIDATES,
                                 scan_block=scan_block)
        row["bitmatch_unpruned"] = all(
            bool(jnp.array_equal(a, b))
            for a, b in zip(res[:3], plain[:3]))
    # bit-match check while the db is resident: dense cells check streaming
    # against themselves on a query slice; streaming cells check against the
    # dense oracle wherever the slice's distance matrix fits in RAM — this
    # is what certifies the 8M/16M wide-key cells (streaming == oracle).
    # Only the first three NNSResult fields compare: `blocks_touched` is
    # None on exactly one side by design
    if path == "dense":
        # `res` already holds the dense full-batch output; only the
        # streaming side needs computing
        s = fixed_radius_nns(queries[:Q_ORACLE], db, radius, MAX_CANDIDATES,
                             scan_block=scan_block_for(n))
        row["bitmatch_oracle"] = all(
            bool(jnp.array_equal(a[:Q_ORACLE], b))
            for a, b in zip(res[:3], s[:3]))
    elif Q_ORACLE * n * 4 <= DENSE_MAX_BYTES:
        # jit the dense slice so the (Q_ORACLE, n, WORDS) xor/popcount
        # intermediates fuse into the reduction — eager, they would be
        # 2*WORDS x larger than the (Q_ORACLE, n) matrix the guard budgets
        d = jax.jit(lambda qs: fixed_radius_nns(
            qs, db, radius, MAX_CANDIDATES, scan_block=0))(
                queries[:Q_ORACLE])
        # `res` is this path's own full-catalog result from the timing loop
        row["bitmatch_oracle"] = all(
            bool(jnp.array_equal(a, b[:Q_ORACLE]))
            for a, b in zip(d[:3], res[:3]))
    return row


def _spawn_cell(n: int, path: str, repeats: int = REPS) -> dict:
    """Run one cell in a fresh interpreter; returns its JSON row.

    A crashed cell (e.g. the dense path OOM-killed on a small host — the
    failure mode this benchmark probes) is reported as a status=failed row
    with its stderr tail, so the sweep continues and still emits the
    artifact."""
    # the child inherits the platform: this parent never touches jax, so
    # on a chip the child holds it
    env = dict(os.environ)
    env["NNS_SCALE_REPS"] = str(max(repeats, 1))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.nns_scale",
         "--cell", str(n), path],
        env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-3:]
        print(f"# cell n={n} path={path} failed "
              f"(rc={proc.returncode}): {' | '.join(tail)}", file=sys.stderr)
        return {"n": n, "q": Q, "path": path, "status": "failed",
                "returncode": proc.returncode,
                "stderr_tail": tail, "dense_matrix_bytes": Q * n * 4}
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _derived(row: dict) -> str:
    bits = [f"qps={row['qps']:.1f}",
            f"rss_delta={row['rss_peak_delta_bytes']}",
            f"dense_bytes={row['dense_matrix_bytes']}"]
    if "mem_lt_10pct_dense" in row:
        bits.append(f"mem_lt_10pct_dense={row['mem_lt_10pct_dense']}")
    if "blocks_touched_mean" in row:
        bits.append(f"blocks_touched={row['blocks_touched_mean']:.1f}")
        bits.append(f"scan_frac={row['scan_frac']:.4f}")
    if "speedup_vs_unpruned" in row:
        bits.append(f"speedup_vs_unpruned={row['speedup_vs_unpruned']:.2f}")
    if "bitmatch_unpruned" in row:
        bits.append(f"bitmatch_unpruned={row['bitmatch_unpruned']}")
    if "bitmatch_oracle" in row:
        bits.append(f"bitmatch={row['bitmatch_oracle']}")
    return ";".join(bits)


def rows(sizes=SIZES, repeats: int = REPS):
    out, json_rows = [], []
    for n in sizes:
        paths = ["streaming"]
        if n >= 1_048_576:
            paths.append("streaming_qp2")  # query-parallel knob
        # the Zipf-skewed pair: same clustered catalog, scan without / with
        # block-summary pruning (zipf_stream must run first — the pruned
        # row's speedup_vs_unpruned reads it)
        paths += ["zipf_stream", "zipf_pruned"]
        if Q * n * 4 <= DENSE_MAX_BYTES:
            paths.append("dense")
        for path in paths:
            row = _spawn_cell(n, path, repeats)
            if path == "zipf_pruned" and row["status"] == "ok":
                stream = next(
                    (r for r in json_rows
                     if r["n"] == n and r["path"] == "zipf_stream"
                     and r["status"] == "ok"), None)
                # NaN (not absent) when the stream cell failed: the row
                # schema stays uniform across the sweep and bench_compare
                # drops NaN metrics as not-comparable
                row["speedup_vs_unpruned"] = (
                    row["qps"] / stream["qps"] if stream is not None
                    else float("nan"))
            json_rows.append(row)
            if row["status"] != "ok":
                out.append((f"nns_scale/{path}/n{n}", 0.0, "status=failed"))
            else:
                out.append((f"nns_scale/{path}/n{n}", row["us_per_query"],
                            _derived(row)))
        if Q * n * 4 > DENSE_MAX_BYTES:
            json_rows.append({"n": n, "q": Q, "path": "dense",
                              "status": "skipped_oom_guard",
                              "dense_matrix_bytes": Q * n * 4})
            out.append((
                f"nns_scale/dense/n{n}", 0.0,
                f"status=skipped_oom_guard;dense_bytes={Q * n * 4}"))
    return out, json_rows


def check_stream_contract(json_rows, rss_budget: int) -> list[str]:
    """The streaming cells' memory/bit-match contract (nightly lane)."""
    problems = []
    for row in json_rows:
        if not row["path"].startswith("streaming"):
            continue
        if row["status"] != "ok":
            problems.append(f"n={row['n']} {row['path']}: status failed")
            continue
        if row["path"] == "streaming":
            # memory contract applies to the single-device scan only (the
            # qp2 cells replicate the catalog once per fake device inside
            # one process, so their RSS measures devices x db, not the
            # scan) and only once the dense matrix dwarfs constant
            # jit/runtime overheads
            if (row["n"] >= 1_048_576
                    and not row.get("mem_lt_10pct_dense", False)):
                problems.append(
                    f"n={row['n']} {row['path']}: rss_delta "
                    f"{row['rss_peak_delta_bytes']} >= 10% of dense matrix")
            if row["rss_peak_delta_bytes"] >= rss_budget:
                problems.append(
                    f"n={row['n']} {row['path']}: rss_delta "
                    f"{row['rss_peak_delta_bytes']} >= budget {rss_budget}")
        if "bitmatch_oracle" not in row:
            # a cell whose oracle slice never ran is uncertified, not ok
            problems.append(f"n={row['n']} {row['path']}: oracle check "
                            f"skipped (dense slice exceeds DENSE_MAX_BYTES)")
        elif not row["bitmatch_oracle"]:
            problems.append(f"n={row['n']} {row['path']}: oracle mismatch")
    return problems


def check_prune_contract(json_rows, max_scan_frac: float) -> list[str]:
    """The pruned Zipf cells' contract (nightly lane): bit-identical to the
    unpruned scan always; scan_frac under the ceiling and >=
    PRUNE_MIN_SPEEDUP over the unpruned streaming scan at >= 1M rows.
    The perf legs apply at >= 1M only — below that, the 4096-row summary
    blocks each span many clusters, so the OR saturates by construction
    and the pruned scan merely matches the unpruned one."""
    problems = []
    for row in json_rows:
        if row["path"] != "zipf_pruned":
            continue
        if row["status"] != "ok":
            problems.append(f"n={row['n']} zipf_pruned: status failed")
            continue
        if not row.get("bitmatch_unpruned", False):
            problems.append(
                f"n={row['n']} zipf_pruned: pruned != unpruned bits")
        if row["n"] < 1_048_576:
            continue
        if row["scan_frac"] >= max_scan_frac:
            problems.append(
                f"n={row['n']} zipf_pruned: scan_frac {row['scan_frac']:.4f}"
                f" >= ceiling {max_scan_frac}")
        speedup = row.get("speedup_vs_unpruned")
        if speedup is None:
            problems.append(
                f"n={row['n']} zipf_pruned: no zipf_stream cell to compare")
        elif speedup < PRUNE_MIN_SPEEDUP:
            problems.append(
                f"n={row['n']} zipf_pruned: speedup {speedup:.2f}x < "
                f"{PRUNE_MIN_SPEEDUP}x over the unpruned scan")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="extend the sweep to the 4M/8M/16M wide-key cells")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated catalog sizes (unified flag; "
                         "overrides --full)")
    ap.add_argument("--repeats", type=int, default=REPS,
                    help="steady-state scans averaged per cell")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    ap.add_argument("--assert-stream-mem", type=int, default=None,
                    metavar="BYTES",
                    help="exit 1 unless every streaming cell is ok, under "
                         "10%% of the dense matrix AND under this RSS budget")
    ap.add_argument("--assert-scan-frac", type=float, default=None,
                    metavar="FRAC",
                    help="exit 1 unless every zipf_pruned cell bit-matches "
                         "the unpruned scan, keeps scan_frac under FRAC, "
                         f"and (>= 1M rows) beats it {PRUNE_MIN_SPEEDUP}x")
    ap.add_argument("--cell", nargs=2, metavar=("N", "PATH"),
                    help="internal: run one measurement and print JSON")
    args = ap.parse_args()
    if args.cell:
        print(json.dumps(_cell(int(args.cell[0]), args.cell[1])))
        return

    from benchmarks.bench_io import (
        check_row_schema,
        csv_rows_to_json,
        write_bench_json,
    )

    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = FULL_SIZES if args.full else SIZES
    out, json_rows = rows(sizes, args.repeats)
    for name, us, derived in out:
        print(f"{name},{us:.3f},{derived}")
    # schema gate: every cell of a path carries the same metric set (a
    # sweep cell silently dropping a metric fails the run, it doesn't
    # ship a hole in the artifact)
    check_row_schema(
        csv_rows_to_json(out),
        within=tuple(f"nns_scale/{p}/" for p in
                     ("streaming", "streaming_qp2", "zipf_stream",
                      "zipf_pruned", "dense")))
    # `rows` carries the one csv shape bench_compare diffs; the raw
    # per-cell measurements (rss deltas, compile times, ...) ride in
    # `cells` — previously they *were* the rows, which broke any tool
    # expecting the shared name/us_per_call/derived shape
    path = write_bench_json(
        "nns_scale", csv_rows_to_json(out), out_dir=args.out,
        cells=json_rows,
        config={"radius": RADIUS, "max_candidates": MAX_CANDIDATES,
                "words": WORDS, "q": Q, "q_oracle": Q_ORACLE,
                # the chunk each cell ran with is in its row's scan_block
                # field (scan_block_for ramps it with catalog size)
                "dense_max_bytes": DENSE_MAX_BYTES,
                "zipf": {"clusters": ZIPF_CLUSTERS,
                         "exponent": ZIPF_EXPONENT,
                         "flip_positions": ZIPF_FLIP_POSITIONS,
                         "radius": ZIPF_RADIUS},
                "reps": args.repeats})
    print(f"# wrote {path}")
    failed = False
    if args.assert_stream_mem is not None:
        problems = check_stream_contract(json_rows, args.assert_stream_mem)
        for p in problems:
            print(f"# CONTRACT VIOLATION: {p}", file=sys.stderr)
        failed |= bool(problems)
        if not problems:
            print(f"# streaming contract ok (rss budget "
                  f"{args.assert_stream_mem} bytes)")
    if args.assert_scan_frac is not None:
        problems = check_prune_contract(json_rows, args.assert_scan_frac)
        for p in problems:
            print(f"# CONTRACT VIOLATION: {p}", file=sys.stderr)
        failed |= bool(problems)
        if not problems:
            print(f"# prune contract ok (scan_frac < "
                  f"{args.assert_scan_frac})")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
