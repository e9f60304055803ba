"""Frequency-tiered out-of-core catalog vs the all-RAM engine.

The tiered-catalog headline claim: one host serves a catalog far larger
than RAM-resident serving allows, bit-identically, at comparable
throughput, because the working set under skewed (Zipf) traffic is tiny:

  * the **tiered** cell opens the memmapped base shard and serves through
    `TieredCatalog` — int8 pool + f32 hot cache over the measured-hot head,
    block-summary-pruned out-of-core NNS over the cold tail;
  * the **allram** cell loads the SAME shard bytes fully into RAM
    (`TieredCatalog.to_ram_engine()` — the int8 engine with identical hot
    cache, mask, and summary) and serves the same stream.

Both cells serve the identical query stream and report a sha256 digest
over every served item id, CTR score, and the accumulated cache counters
— the cells must agree bit for bit (asserted). The tiered cell must hold
peak RSS under `--rss-frac` (default 0.25) of the all-RAM cell's and
reach `--min-qps-frac` (default 0.7) of its throughput; both checks are
in-benchmark hard exit codes, and the nightly lane adds an absolute
`--rss-budget` on top.

Catalog construction (deterministic, chunked — the writer never holds
the table): the first `BOOT_ITEMS` rows are a bootstrap table; user
histories are Zipf over that hot head. Cold-tail rows are generated in
per-chunk rng streams, clustered (cluster-contiguous ids) around real
user embeddings computed through the bootstrap engine's filtering MLP —
so query signatures land near their home cluster's signatures, the
block summary admits a compact block set per batch, and the out-of-core
scan's residency tracks the admitted working set the way production
skew would make it. The block summary is prebuilt at write time: opening
the shard never touches a signature page.

  PYTHONPATH=src python -m benchmarks.tiered_catalog [--items N] [--full]
      [--repeats 2] [--out DIR] [--rss-budget BYTES]
      [--rss-frac 0.25] [--min-qps-frac 0.7] [--shard-dir DIR]

The digest gate always applies. The RSS/qps *fraction* gates are claims
about scale — below GATE_MIN_ITEMS the fixed jit workspaces dominate
both cells and the ratios are noise, so quick runs skip them (with a
note); ``--full`` (the nightly lane) runs the headline 8M-item catalog
with every gate hard.

Emits BENCH_tiered_catalog.json; the `resident_bytes=` metric is judged
lower-is-better by tools/bench_compare.py.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

N_ITEMS = 1 << 20  # default quick cell; the nightly lane runs --full
FULL_ITEMS = 1 << 23  # the headline scale: 8M items, far beyond hot RAM
# the RSS/qps fractions are claims about SCALE — below this, fixed jit
# workspaces (~100MB) dwarf the catalog itself and the ratios are noise
GATE_MIN_ITEMS = 1 << 22
BOOT_ITEMS = 4096  # bootstrap head: history ids + cluster-center source
EMBED_DIM = 32
WORDS = 8  # 256-bit signatures
CLUSTERS = 96
NOISE = 0.08  # intra-cluster spread around the center embedding
RADIUS = 72
N_CANDIDATES = 64
HISTORY_LEN = 8
BATCH = 64
N_BATCHES = 8  # digest + timing stream length (per repeat)
POOL_ROWS = 1 << 15
HOT_ROWS = 4096
ZIPF_EXPONENT = 1.1
WRITE_CHUNK = 1 << 18
SEED = 11
REPS = 2


def _default_cfg():
    from repro.models import recsys as rs

    return rs.YoutubeDNNConfig(
        n_items=BOOT_ITEMS,
        user_features={"user_id": 512, "gender": 3, "age": 7},
        history_len=HISTORY_LEN, embed_dim=EMBED_DIM)


def _zipf_weights(np, k: int):
    w = np.arange(1, k + 1, dtype=np.float64) ** -ZIPF_EXPONENT
    return w / w.sum()


def _bootstrap_engine():
    """The user-side model + bootstrap item head (deterministic)."""
    import jax

    from repro.models import recsys as rs
    from repro.serving.recsys_engine import RecSysEngine

    cfg = _default_cfg()
    params = rs.init_youtubednn(jax.random.key(SEED), cfg)
    return RecSysEngine.build(params, cfg, radius=RADIUS,
                              n_candidates=N_CANDIDATES, top_k=10,
                              hot_rows=HOT_ROWS)


def _protos(np):
    """The CLUSTERS prototype users (deterministic) that anchor the item
    clusters; the query stream samples them with Zipf popularity, so
    cluster traffic is skewed like production."""
    rng = np.random.default_rng([SEED, 3])
    w = _zipf_weights(np, BOOT_ITEMS)
    return [{"user_id": int(rng.integers(0, 512)),
             "gender": int(rng.integers(0, 3)),
             "age": int(rng.integers(0, 7)),
             "genre": int(rng.integers(0, 18)),
             "history": rng.choice(BOOT_ITEMS, size=HISTORY_LEN, p=w)}
            for _ in range(CLUSTERS)]


def _queries(np, n_queries: int, seed_tag: int):
    """Deterministic Zipf-skewed query stream (regenerated in each cell)."""
    rng = np.random.default_rng([SEED, 7, seed_tag])
    protos = _protos(np)
    pick = rng.choice(CLUSTERS, size=n_queries, p=_zipf_weights(np, CLUSTERS))
    return [protos[int(i)] for i in pick]


def _proto_centers(engine):
    """Cluster centers = the prototype users' real filtering embeddings."""
    import numpy as np

    from repro.serving.batcher import MicroBatcher
    from repro.serving.hot_cache import CacheStats
    from repro.serving.recsys_engine import lookup_step

    mb = MicroBatcher(engine, max_batch=CLUSTERS)
    batch = mb._stack(_protos(np), CLUSTERS)
    u, _, _ = lookup_step(engine, batch, CacheStats.zero())
    return np.asarray(u)


def write_catalog(directory: str, n_items: int) -> None:
    """Stream the n_items catalog to a base shard (O(chunk) resident)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lsh import lsh_signature
    from repro.core.nns import SUMMARY_BLOCK_ROWS, build_block_summary
    from repro.core.quantization import dequantize_rowwise, quantize_rowwise
    from repro.serving.tiered import BaseShardWriter

    engine = _bootstrap_engine()
    centers = _proto_centers(engine)  # (CLUSTERS, d)
    writer = BaseShardWriter(directory, n_items, EMBED_DIM, WORDS)
    writer.write(0, np.asarray(engine.item_table_q.values),
                 np.asarray(engine.item_table_q.scales),
                 np.asarray(engine.item_sigs))
    per_cluster = -(-(n_items - BOOT_ITEMS) // CLUSTERS)
    for ci, lo in enumerate(range(BOOT_ITEMS, n_items, WRITE_CHUNK)):
        hi = min(lo + WRITE_CHUNK, n_items)
        rng = np.random.default_rng([SEED, 5, ci])
        cluster = np.minimum((np.arange(lo, hi) - BOOT_ITEMS) // per_cluster,
                             CLUSTERS - 1)
        rows = (centers[cluster]
                + NOISE * rng.standard_normal((hi - lo, EMBED_DIM))
                ).astype(np.float32)
        q = quantize_rowwise(jnp.asarray(rows))
        sigs = lsh_signature(dequantize_rowwise(q), engine.lsh_proj)
        writer.write(lo, np.asarray(q.values), np.asarray(q.scales),
                     np.asarray(sigs))
    # prebuilt summary: the serving cells never fault in every sig page
    summary = build_block_summary(writer._maps["sigs"], SUMMARY_BLOCK_ROWS)
    writer.finish(summary=summary)


def _serve_stream(serve_fn, batches):
    """Serve every batch; returns (digest over items+scores+stats, results)."""
    import numpy as np

    h = hashlib.sha256()
    hits = lookups = 0
    for batch in batches:
        res = serve_fn(batch)
        h.update(np.ascontiguousarray(np.asarray(res.items)).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(res.topk.scores, np.float32)).tobytes())
        hits += int(res.stats.hits)
        lookups += int(res.stats.lookups)
    h.update(np.asarray([hits, lookups], np.int64).tobytes())
    return h.hexdigest(), hits, lookups


def _reset_peak_rss() -> bool:
    """Reset the kernel's peak-RSS watermark (VmHWM) to current usage so
    the serving phase's peak is measurable above the bootstrap spike.
    Linux-only; returns False (callers fall back to ru_maxrss) elsewhere."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
        return True
    except OSError:
        return False


def _peak_rss_bytes() -> int:
    """Peak RSS in bytes — VmHWM (resettable) if available, else ru_maxrss."""
    import resource

    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _cell(mode: str, n_items: int, shard_dir: str) -> dict:
    import gc
    import time

    import numpy as np

    from repro.serving.batcher import MicroBatcher
    from repro.serving.tiered import TieredCatalog

    reps = int(os.environ.get("TIERED_CATALOG_REPS", REPS))
    engine = _bootstrap_engine()
    mb = MicroBatcher(engine, max_batch=BATCH)
    queries = _queries(np, BATCH * N_BATCHES, seed_tag=1)
    batches = [mb._stack_np(queries[i: i + BATCH], BATCH)
               for i in range(0, len(queries), BATCH)]
    # measured traffic drives the tiers: both cells pin the same hot head
    freqs = np.zeros(n_items, np.int64)
    for b in batches:
        hist = np.asarray(b["history"])
        np.add.at(freqs, hist[hist >= 0], 1)

    gc.collect()
    _reset_peak_rss()  # bootstrap spikes don't count against the tiers
    rss0 = _peak_rss_bytes()
    cat = TieredCatalog.open(shard_dir, engine, pool_rows=POOL_ROWS,
                             item_freqs=freqs, delta_capacity=64)
    if mode == "tiered":
        serve_fn = cat.serve
        resident = cat.resident_bytes()
    else:
        ram = cat.to_ram_engine()  # the whole shard, resident

        def serve_fn(b):
            return ram.serve({k: np.asarray(v) for k, v in b.items()})

        resident = int(sum(np.asarray(x).nbytes for x in
                           (ram.item_table_q.values, ram.item_table_q.scales,
                            ram.item_sigs, ram.item_mask)))
        del cat
    t0 = time.perf_counter()
    digest, hits, lookups = _serve_stream(serve_fn, batches)  # + compile
    t1 = time.perf_counter()
    for _ in range(reps):
        _serve_stream(serve_fn, batches)
    steady = (time.perf_counter() - t1) / max(reps, 1)
    rss_delta = _peak_rss_bytes() - rss0
    n_q = len(queries)
    return {"mode": mode, "n": n_items, "status": "ok", "digest": digest,
            "qps": n_q / steady, "us_per_query": 1e6 * steady / n_q,
            "compile_and_first_s": t1 - t0,
            "rss_peak_delta_bytes": int(rss_delta),
            "resident_bytes": resident,
            "cache_hits": hits, "cache_lookups": lookups,
            "n_queries": n_q, "batch": BATCH}


def _spawn(*args: str, repeats: int = REPS) -> subprocess.CompletedProcess:
    """Run this module in a child that inherits the platform: the parent
    never touches jax, so on a chip the child holds it."""
    env = dict(os.environ)
    env["TIERED_CATALOG_REPS"] = str(max(repeats, 1))
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.tiered_catalog", *args],
        env=env, capture_output=True, text=True)


def _spawn_cell(mode: str, n_items: int, shard_dir: str,
                repeats: int) -> dict:
    proc = _spawn("--cell", mode, str(n_items), shard_dir, repeats=repeats)
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-3:]
        print(f"# cell mode={mode} failed (rc={proc.returncode}): "
              f"{' | '.join(tail)}", file=sys.stderr)
        return {"mode": mode, "n": n_items, "status": "failed",
                "returncode": proc.returncode, "stderr_tail": tail}
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _derived(row: dict) -> str:
    bits = [f"qps={row['qps']:.1f}",
            f"rss_delta={row['rss_peak_delta_bytes']}",
            f"resident_bytes={row['resident_bytes']}",
            f"cache_hit_rate={row['cache_hits'] / max(row['cache_lookups'], 1):.3f}"]
    if "rss_frac_of_allram" in row:
        bits.append(f"rss_frac_of_allram={row['rss_frac_of_allram']:.3f}")
    if "qps_frac_of_allram" in row:
        bits.append(f"qps_frac_of_allram={row['qps_frac_of_allram']:.2f}")
    if "digest_match" in row:
        bits.append(f"digest_match={row['digest_match']}")
    return ";".join(bits)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=None,
                    help=f"catalog rows (default {N_ITEMS})")
    ap.add_argument("--full", action="store_true",
                    help=f"run the headline {FULL_ITEMS}-item catalog "
                         f"(the nightly lane) with all gates hard")
    ap.add_argument("--repeats", type=int, default=REPS)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--shard-dir", type=str, default=None,
                    help="where the shard epoch is written (default: a "
                         "fresh temp dir; reused if it already holds one)")
    ap.add_argument("--rss-budget", type=int, default=None, metavar="BYTES",
                    help="additionally exit 1 if the tiered cell's peak "
                         "RSS delta exceeds this absolute budget")
    ap.add_argument("--rss-frac", type=float, default=0.25,
                    help="tiered peak RSS must stay under this fraction "
                         "of the all-RAM cell's (hard assert)")
    ap.add_argument("--min-qps-frac", type=float, default=0.7,
                    help="tiered qps floor as a fraction of all-RAM qps "
                         "(hard assert)")
    ap.add_argument("--cell", nargs=3, metavar=("MODE", "N", "DIR"),
                    help="internal: run one serving cell and print JSON")
    ap.add_argument("--write", nargs=2, metavar=("N", "DIR"),
                    help="internal: write the N-item base shard to DIR")
    args = ap.parse_args()
    if args.cell or args.write:
        from repro.launch.runtime import enable_compile_cache

        enable_compile_cache()
    if args.cell:
        print(json.dumps(_cell(args.cell[0], int(args.cell[1]),
                               args.cell[2])))
        return
    if args.write:
        write_catalog(args.write[1], int(args.write[0]))
        return

    from benchmarks.bench_io import (
        check_row_schema,
        csv_rows_to_json,
        write_bench_json,
    )

    n = args.items if args.items is not None else (
        FULL_ITEMS if args.full else N_ITEMS)
    gates_on = n >= GATE_MIN_ITEMS
    root = args.shard_dir or tempfile.mkdtemp(prefix="tiered_catalog_")
    shard_dir = os.path.join(root, f"epoch_0_n{n}")
    if not os.path.exists(os.path.join(shard_dir, "meta.json")):
        print(f"# writing {n}-item shard to {shard_dir}", file=sys.stderr)
        proc = _spawn("--write", str(n), shard_dir)
        if proc.returncode != 0:
            sys.exit(f"# shard write failed (rc={proc.returncode}): "
                     f"{proc.stderr.strip()[-2000:]}")
    # TieredCatalog.open expects epoch_* under a root
    cat_root = os.path.join(root, f"catalog_n{n}")
    os.makedirs(cat_root, exist_ok=True)
    link = os.path.join(cat_root, "epoch_0")
    if not os.path.exists(link):
        os.symlink(os.path.abspath(shard_dir), link)

    cells = [_spawn_cell(m, n, cat_root, args.repeats)
             for m in ("allram", "tiered")]
    allram, tiered = cells
    problems = []
    if any(c["status"] != "ok" for c in cells):
        problems.append("cell failed: "
                        + ", ".join(c["mode"] for c in cells
                                    if c["status"] != "ok"))
    else:
        tiered["digest_match"] = tiered["digest"] == allram["digest"]
        tiered["rss_frac_of_allram"] = (
            tiered["rss_peak_delta_bytes"]
            / max(allram["rss_peak_delta_bytes"], 1))
        tiered["qps_frac_of_allram"] = tiered["qps"] / allram["qps"]
        if not tiered["digest_match"]:
            problems.append(
                f"tiered digest {tiered['digest'][:16]} != allram "
                f"{allram['digest'][:16]} — tiering changed served bits")
        if not gates_on:
            print(f"# note: rss/qps fraction gates skipped at n={n} < "
                  f"{GATE_MIN_ITEMS} (fixed jit workspaces dominate; "
                  f"run --full for the hard contract)", file=sys.stderr)
        elif tiered["rss_frac_of_allram"] >= args.rss_frac:
            problems.append(
                f"tiered peak RSS {tiered['rss_peak_delta_bytes']} is "
                f"{tiered['rss_frac_of_allram']:.2f}x all-RAM "
                f"({allram['rss_peak_delta_bytes']}) >= {args.rss_frac}")
        if gates_on and tiered["qps_frac_of_allram"] < args.min_qps_frac:
            problems.append(
                f"tiered qps {tiered['qps']:.1f} is "
                f"{tiered['qps_frac_of_allram']:.2f}x all-RAM "
                f"({allram['qps']:.1f}) < {args.min_qps_frac}")
        if (args.rss_budget is not None
                and tiered["rss_peak_delta_bytes"] >= args.rss_budget):
            problems.append(
                f"tiered peak RSS {tiered['rss_peak_delta_bytes']} >= "
                f"budget {args.rss_budget}")

    out = []
    for row in cells:
        name = f"tiered_catalog/{row['mode']}/n{n}"
        if row["status"] != "ok":
            out.append((name, 0.0, "status=failed"))
        else:
            out.append((name, row["us_per_query"], _derived(row)))
    for name, us, derived in out:
        print(f"{name},{us:.3f},{derived}")
    check_row_schema(csv_rows_to_json(out))
    path = write_bench_json(
        "tiered_catalog", csv_rows_to_json(out), out_dir=args.out,
        cells=cells,
        config={"items": n, "boot_items": BOOT_ITEMS, "clusters": CLUSTERS,
                "radius": RADIUS, "n_candidates": N_CANDIDATES,
                "pool_rows": POOL_ROWS, "hot_rows": HOT_ROWS,
                "batch": BATCH, "n_batches": N_BATCHES,
                "zipf_exponent": ZIPF_EXPONENT, "noise": NOISE,
                "rss_frac": args.rss_frac, "min_qps_frac": args.min_qps_frac,
                "reps": args.repeats})
    print(f"# wrote {path}")
    for p in problems:
        print(f"# CONTRACT VIOLATION: {p}", file=sys.stderr)
    if problems:
        sys.exit(1)
    print(f"# tiered-catalog contract ok (rss "
          f"{tiered['rss_frac_of_allram']:.2f}x, qps "
          f"{tiered['qps_frac_of_allram']:.2f}x all-RAM, digests match)")


if __name__ == "__main__":
    main()
