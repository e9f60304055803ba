"""Online freshness: train-while-serve quality, staleness, and throughput.

Every serving benchmark so far froze the model at build time; production
recommenders retrain continuously and fold the updated embeddings into the
live index (the churn iMARS' in-memory fabric exists to absorb). This
benchmark closes the loop end to end with `serving.OnlineTrainer` (gradient
steps -> `LiveCatalog.upsert` folds -> `engine_refresh_model` dense
refreshes, all publishing through `swap_engine` under the concurrent
front-end's serve lock) and locks it down with the `serving.shadow`
freshness oracle. Four phases over one seeded query stream:

  * ``frozen``       — concurrent front-end over the deployed live
                       catalog at rest (trainer idle): the qps baseline,
                       measured through the SAME delta-overlay serving
                       path the training phase uses so the sustain gate
                       isolates the cost of concurrent training (the
                       overlay-vs-plain-engine cost is catalog_churn's
                       gated axis);
  * ``train_serve``  — the same stream while a paced training thread
                       (``--steps-per-s``, modeling the interaction arrival
                       rate) lands gradient steps and folds embedding
                       updates into the live catalog between drain chunks;
  * ``freshness``    — `ShadowHarness.checkpoint()` every ``--eval-every``
                       steps: HR@10 of the continuously-updated live engine
                       vs a **cold rebuild of the current parameters**
                       (`rebuild_from_params` — re-quantized, re-signed,
                       re-summarized from scratch), asserted within
                       ``--tol`` at EVERY checkpoint;
  * ``cadence``      — fold-cadence sweep (`fold_every` in ``--cadences``):
                       measured staleness (update landed -> update visible)
                       against the update rate, the freshness/overhead axis.

Acceptance gates (asserted in-benchmark, reported as ``ok=`` fields):
  * live HR@10 within ``--tol`` (0.01 absolute) of the cold-retrained
    reference at every checkpoint;
  * serving qps under concurrent training >= 0.8x frozen;
  * zero ``status="error"`` tickets across every served stream.

  PYTHONPATH=src python -m benchmarks.online_freshness
      [--sizes 2000] [--queries 1024] [--batch 256] [--train-batch 256]
      [--pretrain 300] [--train-steps 300] [--eval-every 100]
      [--steps-per-s 8] [--fold-every 8] [--compact-every 1]
      [--cadences 1,8,32] [--tol 0.01] [--repeats 2] [--out DIR] [--smoke]

``--sizes``/``--repeats``/``--out`` are the flags every serving benchmark
shares (see tools/bench_compare.py). ``--smoke`` is the CI fast-lane cell:
a tiny model (~200 online steps) that still runs every phase and gate.

Variance control mirrors benchmarks/catalog_churn.py: the Eigen
single-thread XLA flag is defaulted in before jax loads and every qps cell
reports the best of ``--repeats`` measured passes.

Emits BENCH_online_freshness.json (see benchmarks/bench_io.py).
"""
from __future__ import annotations

import argparse
import itertools
import threading
import time


def _setup(n_items: int, n_users: int, pretrain_steps: int,
           train_batch: int, history_len: int = 12, hot_rows: int = 128,
           seed: int = 0):
    """Pretrain a YoutubeDNN (the exact `make_recsys_train_step`
    computation the online trainer continues) and build its engine."""
    import jax

    from repro.data import synthetic
    from repro.distributed import training
    from repro.models import recsys as rs
    from repro.serving import RecSysEngine
    import numpy as np

    data = synthetic.make_movielens(n_users=n_users, n_items=n_items,
                                    history_len=history_len)
    cfg = rs.YoutubeDNNConfig(
        n_items=n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=history_len)
    params = rs.init_youtubednn(jax.random.key(seed), cfg)
    state = training.init_recsys_train_state(params)
    step = training.make_recsys_train_step(cfg)
    for batch in synthetic.movielens_batches(data, train_batch,
                                             pretrain_steps):
        state, _ = step(state, batch)
    params = state.params
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=64,
                                top_k=10, hot_rows=hot_rows,
                                item_freqs=freqs)
    return engine, data, cfg, params


def _paced_steps(trainer, batches, steps_per_s: float,
                 stop: threading.Event | None = None):
    """Run `trainer.step` over `batches` paced at `steps_per_s` (the
    modeled interaction arrival rate; 0 = free-run). Returns steps taken."""
    period = 1.0 / steps_per_s if steps_per_s > 0 else 0.0
    next_t = time.perf_counter()
    n = 0
    for batch in batches:
        if stop is not None and stop.is_set():
            break
        trainer.step(batch)
        n += 1
        if period:
            next_t += period
            lag = next_t - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            else:
                next_t = time.perf_counter()  # don't burst after a stall
    return n


def _serve_stream(server, queries, repeats: int, min_s: float = 0.0):
    """Best-of-passes qps over the stream; counts error tickets (run 1
    doubles as warmup, same policy as benchmarks/catalog_churn.py).

    `min_s` keeps replaying the stream until that much wall time has
    elapsed (as well as at least `repeats` passes) — the train-while-serve
    phase needs the serving window to span several paced gradient steps
    and folds, not to outrun them."""
    best_qps, n_err, n_pass = 0.0, 0, 0
    t_start = time.perf_counter()
    while n_pass < max(repeats, 1) or time.perf_counter() - t_start < min_s:
        t0 = time.perf_counter()
        served = server.serve_many(queries)
        dt = time.perf_counter() - t0
        n_err += sum(1 for s in served if s.status == "error")
        best_qps = max(best_qps, len(queries) / dt)
        n_pass += 1
    return best_qps, n_err


def rows(n_items: int, n_users: int, n_queries: int, batch: int,
         train_batch: int, pretrain: int, train_steps: int, eval_every: int,
         steps_per_s: float, fold_every: int, compact_every: int,
         cadences, tol: float, max_users: int | None, repeats: int = 2):
    import numpy as np

    from repro.data.synthetic import movielens_batches, serving_queries
    from repro.serving import (
        LiveCatalog,
        OnlineTrainer,
        ShadowHarness,
        make_server,
    )

    def concurrent_server(eng):
        # queue_depth=None: this harness measures throughput, not
        # admission control — nothing sheds, errors still surface
        return make_server(eng, "concurrent", max_batch=batch,
                           buckets=(batch,), queue_depth=None)

    engine, data, cfg, params = _setup(n_items, n_users, pretrain,
                                       train_batch)
    rng = np.random.default_rng(0)
    queries = serving_queries(data, rng.integers(0, data.n_users, n_queries))
    warm = serving_queries(data, rng.integers(0, data.n_users, batch))
    out = []

    # -- deploy the online-learning stack -------------------------------
    # delta_capacity=n_items: the full-softmax gradient touches every item
    # row, so a fold may upsert the whole catalog — size for it and let
    # compaction be a cadence choice, not a forced stall
    cat = LiveCatalog(engine, delta_capacity=n_items)
    live = concurrent_server(cat.engine)
    cat.attach(live)
    trainer = OnlineTrainer(cat, cfg, params, fold_every=fold_every,
                            compact_every=compact_every)
    batches = list(movielens_batches(data, train_batch, train_steps,
                                     seed=1))
    trainer.step(batches[0])  # train-step compile off the clock
    trainer.fold()  # first fold + compact pay one-time compiles; eat them
    live.serve_many(warm)  # ...and re-warm serving on the swapped engine

    # -- frozen baseline: the same serving path, trainer idle -----------
    # Both sides of the sustain gate serve through the live catalog — the
    # delta-overlay-vs-plain-engine cost is benchmarks/catalog_churn.py's
    # gated axis; this gate isolates the marginal cost of CONCURRENT
    # TRAINING, which an engine-vs-catalog comparison would drown out.
    # Both sides also get the same min_s window so best-of-pass counts
    # are comparable; the window spans several folds (see below).
    min_s = max(3.0, 4 * fold_every / steps_per_s) if steps_per_s > 0 \
        else 3.0
    qps_frozen, err_frozen = _serve_stream(live, queries, repeats,
                                           min_s=min_s)
    out.append((f"serving/online/frozen_{n_items}", 1e6 / qps_frozen,
                f"qps={qps_frozen:.0f};items={n_items};path=live_catalog;"
                f"errors={err_frozen}"))

    # -- train-while-serve: paced trainer vs the same stream ------------
    stop = threading.Event()
    feed = itertools.cycle(batches)  # trainer runs as long as serving does
    tally = {}
    th = threading.Thread(
        target=lambda: tally.setdefault(
            "steps", _paced_steps(trainer, feed, steps_per_s, stop)),
        name="online-trainer", daemon=True)
    # the shared min_s window holds serving open long enough for the paced
    # trainer to land several folds inside it — otherwise a fast stream
    # outruns the pacing and "qps under training" measures an idle trainer
    t_train0 = time.perf_counter()
    th.start()
    qps_train, err_train = _serve_stream(live, queries, repeats,
                                         min_s=min_s)
    stop.set()
    th.join()
    train_dt = time.perf_counter() - t_train0
    sustain = qps_train / qps_frozen
    n_err = live.stats()["n_errors"]
    ok_sustain = sustain >= 0.8
    ok_err = n_err == 0 and err_train == 0 and err_frozen == 0
    out.append((
        f"serving/online/train_serve_{n_items}", 1e6 / qps_train,
        f"qps={qps_train:.0f};sustain_vs_frozen={sustain:.2f}x"
        f"(target >=0.8x);ok={ok_sustain};errors={n_err};"
        f"steps_during={tally.get('steps', 0)};"
        f"steps_per_s={tally.get('steps', 0) / train_dt:.1f};"
        f"folds={trainer.n_folds};rows_folded={trainer.rows_folded}"))
    assert ok_sustain, (
        f"serving under concurrent training sustained only {sustain:.2f}x "
        f"of frozen qps (target >= 0.8x)")
    assert ok_err, (
        f"error tickets under train-while-serve: {n_err} in stats, "
        f"{err_train} in stream (target: zero)")

    # -- freshness: shadow checkpoints against the cold rebuild ---------
    # (trainer thread has exited — the main thread is now the single
    # writer, so checkpoints may fold/refresh directly)
    shadow = ShadowHarness(trainer, data, k=10, mode="lsh", tol=tol,
                           max_users=max_users)
    feed = movielens_batches(data, train_batch, train_steps, seed=2)
    done = 0
    while done < train_steps:
        burst = min(eval_every, train_steps - done)
        done += _paced_steps(trainer, itertools.islice(feed, burst),
                             steps_per_s)
        shadow.checkpoint()  # raises the moment live leaves the tol band
    recs = shadow.records
    max_gap = max(r.gap for r in recs)
    ok_gap = max_gap <= tol  # every checkpoint already asserted
    out.append((
        f"serving/online/freshness_{n_items}", 0.0,
        f"hr_at_10={recs[-1].hr_live:.4f};hr_ref={recs[-1].hr_ref:.4f};"
        f"max_gap={max_gap:.4f}(tol {tol});checkpoints={len(recs)};"
        f"agree_frac={recs[-1].agree_frac:.3f};ok={ok_gap}"))

    # -- staleness under the measured update rate -----------------------
    st = trainer.stats()
    out.append((
        f"serving/online/staleness_{n_items}",
        st["staleness_ms_mean"] * 1e3,
        f"staleness_ms={st['staleness_ms_mean']:.1f};"
        f"staleness_p95_ms={st['staleness_ms_p95']:.1f};"
        f"update_rate={steps_per_s:.1f};"
        f"updates_landed={st['updates_landed']};"
        f"updates_visible={st['updates_visible']};"
        f"updates_pending={st['updates_pending']}"))
    live.close()

    # -- fold-cadence sweep: staleness vs update rate -------------------
    feed = movielens_batches(data, train_batch, 10_000, seed=3)
    for cadence in cadences:
        trainer.fold_every = cadence
        trainer.fold()  # drain pending from the previous cadence
        lo = len(trainer.staleness_ms)
        burst = max(16, 2 * cadence)
        _paced_steps(trainer, itertools.islice(feed, burst), steps_per_s)
        trainer.fold()
        lat = trainer.staleness_ms[lo:]
        out.append((
            f"serving/online/cadence{cadence}_{n_items}",
            float(np.mean(lat)) * 1e3,
            f"staleness_ms={np.mean(lat):.1f};fold_every={cadence};"
            f"update_rate={steps_per_s:.1f};steps={burst}"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated catalog sizes (unified flag; "
                         "default: --items)")
    ap.add_argument("--items", type=int, default=2000)
    ap.add_argument("--users", type=int, default=800)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--train-batch", type=int, default=256)
    ap.add_argument("--pretrain", type=int, default=300,
                    help="offline steps before the engine deploys")
    ap.add_argument("--train-steps", type=int, default=300,
                    help="online steps in the freshness phase")
    ap.add_argument("--eval-every", type=int, default=100,
                    help="shadow checkpoint cadence (steps)")
    ap.add_argument("--steps-per-s", type=float, default=8.0,
                    help="paced trainer rate (modeled interaction arrival "
                         "rate; 0 = free-run)")
    # the full-softmax gradient densifies every item row, so each fold
    # upserts ~the whole catalog into the delta shard; without compaction
    # serving pays a permanent full-size delta scan + overlay and the
    # 0.8x sustain gate fails.  Pairing folds with compaction (and folding
    # every few steps rather than every step) keeps the delta drained.
    # Fold+compact cost scales with catalog size (~14 ms at 400 items,
    # ~400 ms at 2000), so the default cadence is sized for the full run;
    # --smoke folds tighter (every 4) where folds are cheap.
    ap.add_argument("--fold-every", type=int, default=8)
    ap.add_argument("--compact-every", type=int, default=1,
                    help="compact the catalog every N folds (0 = never)")
    ap.add_argument("--cadences", type=str, default="1,8,32",
                    help="fold_every values for the staleness sweep")
    ap.add_argument("--tol", type=float, default=0.01,
                    help="max |HR@10 live - cold rebuild| per checkpoint")
    ap.add_argument("--max-users", type=int, default=None,
                    help="cap the HR eval stream (None = every user)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="measured passes per qps cell (first doubles as "
                         "warmup; best pass reported)")
    ap.add_argument("--out", type=str, default=None,
                    help="artifact directory (default $BENCH_OUT_DIR or .)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast-lane cell: tiny model, ~200 online "
                         "steps, every phase and gate")
    args = ap.parse_args()
    if args.smoke:
        args.items, args.users = 400, 300
        args.queries, args.batch, args.train_batch = 256, 64, 64
        args.pretrain, args.train_steps, args.eval_every = 120, 200, 100
        args.cadences, args.max_users = "1,8", 200
        args.fold_every = 4  # folds are cheap at this scale; keep fresh
    sizes = (tuple(int(s) for s in args.sizes.split(","))
             if args.sizes else (args.items,))
    cadences = tuple(int(c) for c in args.cadences.split(","))

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
        out.extend(rows(n_items, args.users, args.queries, args.batch,
                        args.train_batch, args.pretrain, args.train_steps,
                        args.eval_every, args.steps_per_s, args.fold_every,
                        args.compact_every, cadences, args.tol,
                        args.max_users, args.repeats))
    for name, us, derived in out:
        print(f"{name},{us:.6f},{derived}")
    path = write_bench_json(
        "online_freshness", csv_rows_to_json(out), out_dir=args.out,
        config={"sizes": sizes, "users": args.users,
                "queries": args.queries, "batch": args.batch,
                "train_batch": args.train_batch, "pretrain": args.pretrain,
                "train_steps": args.train_steps,
                "eval_every": args.eval_every,
                "steps_per_s": args.steps_per_s,
                "fold_every": args.fold_every,
                "compact_every": args.compact_every, "cadences": cadences,
                "tol": args.tol, "repeats": args.repeats,
                "smoke": args.smoke})
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
