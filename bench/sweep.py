#!/usr/bin/env python3
"""Find the highest Poisson rate a cell's open loop sustains.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates <r>,<r>,... --repeats <k>

Builds and warms the cell once, then offers each rate `--repeats` times,
each time for `--seconds` on a seed of its own, through a fresh server of
the cell's own knobs. An offer is sustained when it leaves no growing
backlog and was really offered:

* every request resolved within a second of the window's close;
* the median latency of the window's last quarter is under 1.5 times
  that of its second (the first is left out: the queue starts empty);
  a backlog that grows through the window makes each quarter's latency
  grow with it;
* the generator kept to its schedule (95th percentile lateness under
  10 ms, about one drain cycle of the front end): it shares the host
  process with the server, and a generator later than that bunches
  arrivals into cycles they were not due in, so the stream offered is no
  longer the mix's Poisson stream.

A rate is sustained when most of its offers are: a host stall (~1.5 s,
about one run in seven) backs up any rate. No latency limit is applied:
the knee is the server's capacity, and the cell at four fifths of it
reports what the tails are there. Prints one JSON line an offer, then the
knee: the highest rate sustained with every lower rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import loadgen, run, stats  # noqa: E402

LATE_LIMIT_S = 0.010
DRIFT_LIMIT = 1.5
CLOSE_S = 1.0


def offer(st, rate: float, seconds: float, seed: int) -> dict:
    import numpy as np

    from repro.serving import make_server

    knobs = dict(st.traffic["server"])
    server = make_server(st.built["engine"], knobs.pop("mode"), **knobs)
    try:
        rng = np.random.default_rng(loadgen.subseed(seed, 29))
        offsets, users = loadgen.open_schedule(
            rate, seconds, st.built["n_users"], st.traffic["users_zipf"], rng)
        with run.frozen_setup():
            t0 = time.perf_counter()
            records, harvest = loadgen.open_loop(
                server, st.built["pool"], users, offsets, t0)
            t_close = time.perf_counter()
            loadgen.redeem(server, records, harvest)
    finally:
        server.close()
    lat = stats.latencies_s(records)
    late_s = stats.submit_lateness_s(records)
    q = len(lat) // 4
    second = float(np.median(lat[q:2 * q]))
    last = float(np.median(lat[-q:]))
    unresolved = int(np.sum(~(records.done <= t_close + CLOSE_S)))
    late_p95 = stats.percentile(late_s, 95)
    return {"rate": rate, "seed": seed, "offered": len(records),
            "completed_per_s": float(np.sum(records.status == loadgen.OK))
            / seconds,
            "p50_ms": 1e3 * stats.percentile(lat, 50),
            "p90_ms": 1e3 * stats.percentile(lat, 90),
            "p95_ms": 1e3 * stats.percentile(lat, 95),
            "p99_ms": 1e3 * stats.percentile(lat, 99),
            "gen_late_p95_ms": 1e3 * late_p95,
            "second_quarter_p50_ms": 1e3 * second,
            "last_quarter_p50_ms": 1e3 * last,
            "drift": last / second,
            "unresolved_1s_after_close": unresolved,
            "sustained": bool(unresolved == 0 and last < DRIFT_LIMIT * second
                              and late_p95 < LATE_LIMIT_S)}


def knee(offers: list[dict]) -> float | None:
    """The highest rate that most of its offers sustain, as most offers of
    every lower rate do (None when the lowest rate is not)."""
    best = None
    for rate in sorted({o["rate"] for o in offers}):
        marks = [o["sustained"] for o in offers if o["rate"] == rate]
        if 2 * sum(marks) <= len(marks):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    st = run.setup(args.workload, args.seed)
    st.server.close()
    offers = []
    for rate in (float(r) for r in args.rates.split(",")):
        for k in range(args.repeats):
            offers.append(offer(st, rate, args.seconds,
                                loadgen.subseed(args.seed, 1000 + k)))
            print(json.dumps(offers[-1]), flush=True)
    print(json.dumps({"knee": knee(offers)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
