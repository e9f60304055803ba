"""Serve a trained RecSys through the batched iMARS serving subsystem:
single-user queries go into the micro-batching queue, get bucketed into
fixed batch shapes, and run through one jitted serve step (hot-row-cached
UIET/ItET lookups -> filtering NNS -> ranking -> CTR threshold top-k).
Reports measured software throughput, the hot-cache hit rate, and the
hardware cost model's per-query latency/energy (the 22,025 qps / 16.8x /
713x headline numbers).

Every front-end is constructed through the one factory —
``make_server(engine, mode, **knobs)`` (docs/SERVING.md):

  * ``--mode sync``       the synchronous micro-batcher (default);
  * ``--mode pipelined``  the ring of in-flight buckets dispatched through
    the staged lookup -> scan -> rank steps, overlapping host-side
    batching with the device's NNS scan (bit-identical results; see
    benchmarks/async_serving.py for the measured speedup);
  * ``--mode concurrent`` the threaded multi-tenant front-end: bounded
    per-tenant queues + load shedding over the pipelined ring
    (bit-identical for every admitted query).

  PYTHONPATH=src python examples/serve_recsys.py [--queries 2000]
      [--mode sync|pipelined|concurrent] [--depth 2]
      [--prune on|off|auto] [--scan-block N] [--report]

``--prune`` drives the engine's block-summary pruning knob (`auto` prunes
whenever the scan streams; results are bit-identical either way) and
``--scan-block`` forces the streaming plan — the demo catalog is small
enough to route dense by default, where pruning never engages. The summary
line reports the mean summary blocks touched per query on a sample batch.
``--report`` prints the per-stage latency breakdown of the timed run from
the front-end's ticket span chains (docs/OBSERVABILITY.md).
"""
import argparse
import time

import numpy as np

from repro.core import cost_model as cm
from repro.data import synthetic
from repro.launch.runtime import enable_compile_cache
from repro.serving import RecSysEngine, make_server
from examples.train_recsys import train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=1000)
    ap.add_argument("--items", type=int, default=600)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--hot-rows", type=int, default=128)
    ap.add_argument("--mode", choices=("sync", "pipelined", "concurrent"),
                    default="sync", help="front-end (make_server mode)")
    ap.add_argument("--pipeline", action="store_true",
                    help="deprecated alias for --mode pipelined")
    ap.add_argument("--depth", type=int, default=2,
                    help="in-flight ring depth (pipelined/concurrent)")
    ap.add_argument("--prune", choices=("on", "off", "auto"), default="auto",
                    help="block-summary pruning: on/off/auto "
                         "(auto prunes whenever the scan streams)")
    ap.add_argument("--scan-block", type=int, default=None,
                    help="streaming scan chunk (None routes by catalog "
                         "size; set e.g. 128 to stream the small demo "
                         "catalog so pruning engages)")
    ap.add_argument("--report", action="store_true",
                    help="print the per-stage latency breakdown of the "
                         "timed run (from the ticket span chains)")
    args = ap.parse_args()
    if args.pipeline:
        args.mode = "pipelined"

    data = synthetic.make_movielens(n_users=args.users, n_items=args.items)
    print("== training (quick) ==")
    params, cfg = train(data, args.steps)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=data.n_items)
    prune = {"on": True, "off": False, "auto": None}[args.prune]
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=50,
                                top_k=10, hot_rows=args.hot_rows,
                                item_freqs=freqs, prune=prune,
                                scan_block=args.scan_block)
    knobs = ({} if args.mode == "sync" else {"depth": args.depth})
    batcher = make_server(engine, args.mode, max_batch=args.batch, **knobs)
    if args.mode != "sync":
        print(f"== {args.mode} serving (ring depth {args.depth}) ==")

    rng = np.random.default_rng(0)

    def make_query(i):
        return {
            **{k: v[i] for k, v in data.user_feats.items()},
            "history": data.histories[i],
            "genre": data.genres[i],
        }

    # warmup: compile every bucket shape the timed run will hit
    # (full batches + the leftover-tail bucket)
    warm_sizes = {args.batch}
    if args.queries % args.batch:
        warm_sizes.add(args.queries % args.batch)
    for size in warm_sizes:
        batcher.serve_many([make_query(i) for i in
                            rng.integers(0, data.n_users, size)])
    # reset batch counters so the report covers the timed run only (the
    # concurrent front-end keeps its counters on the inner ring server);
    # draining the trace buffer drops the warmup tickets' span chains too
    counters = getattr(batcher, "_inner", batcher)
    counters.n_batches = counters.n_served = counters.n_padded = 0
    batcher.take_trace()

    idx = rng.integers(0, data.n_users, args.queries)
    t0 = time.time()
    served = batcher.serve_many([make_query(i) for i in idx])
    dt = time.time() - t0

    print(f"\nserved {len(served)} queries in {dt:.2f}s "
          f"({len(served) / dt:.0f} qps measured on THIS CPU — software path)")
    stats = batcher.stats()
    # blocks-touched sample: one filter-stage batch through the engine
    # directly (the front-ends consume the NNSResult before returning)
    sample = [make_query(i) for i in idx[: min(8, len(idx))]]
    batch = {k: np.stack([q[k] for q in sample]) for k in sample[0]}
    nns = engine.filter_stage(batch)
    if nns.blocks_touched is not None:
        nb = engine.block_summary.n_blocks
        bt = np.asarray(nns.blocks_touched)
        prune_note = (f"blocks touched {bt.mean():.1f}/{nb} per query "
                      f"(scan_frac {bt.mean() / nb:.3f})")
    else:
        prune_note = "pruning inactive (dense plan or --prune off)"
    print(f"micro-batches: {stats['n_batches']}, "
          f"padding fraction {stats['padding_fraction']:.3f}, "
          f"hot-cache hit rate {stats['cache_hit_rate']:.3f}, "
          f"{prune_note}")
    if args.report:
        from tools.obs_report import render_breakdown, stage_breakdown
        print("\n== per-stage breakdown (timed run) ==")
        print(render_breakdown(stage_breakdown(batcher.take_trace())))
    batcher.close()
    e2e = cm.end_to_end_movielens(n_candidates=50)
    print(f"iMARS fabric model: {e2e['imars_qps']:.0f} qps/query-engine, "
          f"{e2e['imars_latency_us']:.1f} us, {e2e['imars_energy_uj']:.1f} uJ"
          f" per query -> {e2e['latency_speedup']:.1f}x / "
          f"{e2e['energy_reduction']:.0f}x vs the paper's GPU baseline")
    print("sample recommendations (first 3 users):")
    print(np.stack([s.items for s in served[:3]]))


if __name__ == "__main__":
    main()
