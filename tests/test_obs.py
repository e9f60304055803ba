"""The telemetry layer: registry semantics, trace completeness, exporters.

Three contracts (docs/OBSERVABILITY.md):

  * **registry** — counters/histograms merge correctly across thread
    shards, collectors publish gauges at snapshot time (registration
    order wins), the event log is bounded, and both exporters
    (`snapshot()` dict, Prometheus text) agree with the writes;
  * **trace completeness** — every ticket in every `make_server` mode
    carries a well-ordered stage-span chain for every status (ok, shed,
    error), including close-with-inflight and epoch-swap-mid-ring, and
    the chain's stage durations sum to the ticket's measured latency
    exactly (the contiguity property `benchmarks/obs_overhead.py` gates);
  * **unification** — `stats()` is one schema over `snapshot()` in all
    three modes, and BENCH artifacts' embedded telemetry passes
    `bench_io.check_telemetry_schema`.
"""
import gc
import sys
import threading
import time
from collections import deque

import jax
import numpy as np
import pytest

from repro.data import synthetic
from repro.data.synthetic import serving_queries as _queries
from repro.models import recsys as rs
from repro.obs import (
    PHASES,
    STAGES,
    EventLog,
    MetricsRegistry,
    PhaseSpans,
    TicketTrace,
    bucket_upper_bounds,
    dump_trace,
    stage_durations,
    well_ordered,
)
from repro.serving import (
    STATUS_ERROR,
    STATUS_OK,
    STATUS_SHED,
    RecSysEngine,
    ServingError,
    make_server,
)
from tools.obs_report import load_trace, render_breakdown, stage_breakdown

MODES = ("sync", "pipelined", "concurrent")


# ---------------------------------------------------------------------------
# registry units (no engine needed)
# ---------------------------------------------------------------------------
def test_counters_merge_across_thread_shards():
    reg = MetricsRegistry()

    def bump():
        for _ in range(500):
            reg.count("t.hits")
            reg.observe("t.lat_s", 1e-4)

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reg.count("t.hits", 3)  # main thread gets its own shard too
    snap = reg.snapshot()
    assert snap["t.hits"] == 4 * 500 + 3
    assert snap["t.lat_s.count"] == 4 * 500
    assert snap["t.lat_s.sum"] == pytest.approx(4 * 500 * 1e-4)


def test_histogram_summary_and_bucket_bounds():
    bounds = bucket_upper_bounds()
    # bounds double each bucket — strictly increasing
    assert all(a < b for a, b in zip(bounds, bounds[1:]))
    reg = MetricsRegistry()
    vals = [1e-5, 1e-4, 1e-3, 1e-2, 0.1]
    for v in vals:
        reg.observe("h.lat_s", v)
    snap = reg.snapshot()
    assert snap["h.lat_s.count"] == len(vals)
    assert snap["h.lat_s.sum"] == pytest.approx(sum(vals))
    assert snap["h.lat_s.mean"] == pytest.approx(sum(vals) / len(vals))
    assert snap["h.lat_s.max"] == pytest.approx(0.1)
    # quantiles are conservative upper bucket bounds: within 2x of exact
    assert 1e-3 <= snap["h.lat_s.p50"] <= 2e-3
    assert 0.1 <= snap["h.lat_s.p99"] <= 0.2


def test_collector_registration_order_wins():
    reg = MetricsRegistry()
    reg.register_collector(lambda r: r.gauge("g.x", 1))
    reg.register_collector(lambda r: r.gauge("g.x", 2))  # outer wins
    assert reg.snapshot()["g.x"] == 2


def test_event_log_is_bounded_and_counts_drops():
    log = EventLog(cap=10)
    for i in range(25):
        log.append("tick", i=i)
    recs = log.records()
    assert len(recs) == 10 and log.n_dropped == 15
    assert [r["i"] for r in recs] == list(range(15, 25))  # newest retained
    assert all(a["seq"] < b["seq"] for a, b in zip(recs, recs[1:]))
    assert log.to_jsonl().count("\n") == 10
    reg = MetricsRegistry()
    reg.event("compact", epoch=3)
    snap = reg.snapshot()
    assert snap["events.count"] == 1 and snap["events.dropped"] == 0


def test_prometheus_export_shapes():
    reg = MetricsRegistry()
    reg.count("c.total", 7)
    reg.gauge("g.depth", 3)
    reg.info("i.mode", "sync")  # info never exports to Prometheus
    reg.observe("h.lat_s", 2e-6)
    text = reg.to_prometheus()
    assert "# TYPE repro_c_total counter" in text and "repro_c_total 7" in text
    assert "# TYPE repro_g_depth gauge" in text
    assert "# TYPE repro_h_lat_s histogram" in text
    assert 'repro_h_lat_s_bucket{le="+Inf"} 1' in text
    assert "repro_h_lat_s_count 1" in text
    assert "i_mode" not in text and "sync" not in text


# ---------------------------------------------------------------------------
# tracing units
# ---------------------------------------------------------------------------
def test_well_ordered_accepts_subsequences_rejects_junk():
    full = tuple((s, float(i)) for i, s in enumerate(STAGES))
    assert well_ordered(full)
    shed = (("submit", 1.0), ("admit", 1.0), ("resolve", 1.0))
    assert well_ordered(shed)
    assert not well_ordered(())  # empty
    assert not well_ordered((("admit", 0.0), ("resolve", 1.0)))  # no submit
    assert not well_ordered((("submit", 0.0), ("rank", 1.0)))  # no resolve
    assert not well_ordered(  # out of canonical order
        (("submit", 0.0), ("scan", 1.0), ("bucket", 2.0), ("resolve", 3.0)))
    assert not well_ordered(  # time goes backwards
        (("submit", 2.0), ("admit", 1.0), ("resolve", 3.0)))
    assert not well_ordered(  # unknown stage name
        (("submit", 0.0), ("warp", 1.0), ("resolve", 2.0)))


def test_stage_durations_sum_to_span():
    chain = tuple((s, 0.5 * i) for i, s in enumerate(STAGES))
    dur = stage_durations(chain)
    assert set(dur) == set(STAGES[1:])  # submit anchors, never charged
    assert sum(dur.values()) == pytest.approx(chain[-1][1] - chain[0][1])


def test_dump_trace_roundtrip_and_breakdown(tmp_path):
    recs = []
    for i in range(8):
        t0 = 10.0 * i
        chain = (("submit", t0), ("admit", t0), ("bucket", t0 + 1),
                 ("dispatch", t0 + 2), ("scan", t0 + 5), ("rank", t0 + 6),
                 ("resolve", t0 + 7))
        recs.append(TicketTrace(i, i % 2, t0, t0 + 7, STATUS_OK, chain))
    recs.append(TicketTrace(99, 0, 0.0, 0.0, STATUS_SHED,
                            (("submit", 0.0), ("admit", 0.0),
                             ("resolve", 0.0))))
    path = tmp_path / "trace.jsonl"
    assert dump_trace(recs, path) == 9
    loaded = load_trace(path)
    assert len(loaded) == 9
    bd = stage_breakdown(loaded, status=STATUS_OK)
    assert bd["n"] == 8 and bd["by_status"] == {STATUS_OK: 8}
    assert bd["latency_s"]["mean"] == pytest.approx(7.0)
    # contiguity: stage-sum mean equals measured latency mean exactly
    assert bd["stage_sum_mean_s"] == pytest.approx(bd["latency_s"]["mean"])
    assert bd["stages"]["scan"]["mean_s"] == pytest.approx(3.0)
    assert bd["stages"]["scan"]["frac"] == pytest.approx(3.0 / 7.0)
    # tenant filter partitions the records
    assert stage_breakdown(loaded, tenant=1)["n"] == 4
    table = render_breakdown(bd)
    assert "stage-sum mean" in table and "scan" in table
    # TicketTrace records render without a dump/load round-trip too
    assert stage_breakdown(recs, status=STATUS_OK)["n"] == 8


def test_check_telemetry_schema():
    from benchmarks.bench_io import check_telemetry_schema
    good = {"serving.served": 10, "serving.mode": "sync",
            "serving.per_tenant": {0: {"served": 10}},
            "serving.ticket_latency_s.mean": 1.5e-3,
            "serving.last_error": None, "serving.closed": False}
    check_telemetry_schema(good, required=("serving.served",))
    with pytest.raises(ValueError, match="must be a dict"):
        check_telemetry_schema(["not", "a", "dict"])
    with pytest.raises(ValueError, match="dotted"):
        check_telemetry_schema({"nodots": 1})
    with pytest.raises(ValueError, match="lowercase"):
        check_telemetry_schema({"serving.Served": 1})
    with pytest.raises(ValueError, match="JSON"):
        check_telemetry_schema({"serving.bad": object()})
    with pytest.raises(ValueError, match="missing"):
        check_telemetry_schema(good, required=("nns.blocks_touched",))


# ---------------------------------------------------------------------------
# trace completeness across the serving stack
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    data = synthetic.make_movielens(n_users=120, n_items=90, history_len=6)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=6)
    params = rs.init_youtubednn(jax.random.key(0), cfg)
    freqs = np.bincount(data.histories[data.histories >= 0],
                        minlength=data.n_items)
    engine = RecSysEngine.build(params, cfg, radius=112, n_candidates=16,
                                top_k=5, hot_rows=32, item_freqs=freqs)
    return engine, data, params, cfg, freqs


def _make(engine, mode, **knobs):
    knobs.setdefault("max_batch", 8)
    if mode == "concurrent":
        knobs.setdefault("tenants", 2)
    return make_server(engine, mode, **knobs)


def _stream(data, n=19):
    return _queries(data, np.arange(n) % 7)


@pytest.mark.parametrize("mode", MODES)
def test_ok_tickets_carry_full_contiguous_chains(served, mode):
    """Every served ticket's chain hits all seven stages in order, rides
    both the ServedQuery and the TicketTrace, and its stage durations sum
    to the measured latency exactly (contiguity)."""
    engine, data = served[:2]
    server = _make(engine, mode)
    stream = _stream(data)
    out = server.serve_many(stream)
    for s in out:
        assert tuple(n for n, _ in s.stages) == STAGES
        assert well_ordered(s.stages)
    trace = server.take_trace()
    assert len(trace) == len(stream)
    for rec in trace:
        assert rec.status == STATUS_OK and well_ordered(rec.stages)
        assert sum(stage_durations(rec.stages).values()) == pytest.approx(
            rec.latency_s, abs=1e-9)
        assert rec.stages[0][1] == rec.submit_s
        assert rec.stages[-1][1] == rec.done_s
    assert server.take_trace() == []  # take clears
    server.close()


@pytest.mark.parametrize("mode", MODES)
def test_trace_false_disables_spans(served, mode):
    engine, data = served[:2]
    server = _make(engine, mode, trace=False)
    out = server.serve_many(_stream(data, 5))
    assert all(s.stages == () for s in out)
    trace = server.take_trace()
    if mode == "concurrent":
        # the load harness still needs submit/done timestamps per ticket
        assert len(trace) == 5 and all(r.stages == () for r in trace)
    else:
        assert trace == []
    snap = server.snapshot()
    assert snap.get("serving.ticket_latency_s.count", 0) == 0
    # the phase spans cost nothing and record nothing either
    assert not [k for k in snap if k.startswith(("serving.phase.",
                                                  "host.gc"))]
    assert "serving.drain_cycles" not in snap
    server.close()


def test_shed_tickets_carry_degenerate_chains(served):
    """A shed ticket resolves at admission: its chain is the well-ordered
    submit -> admit -> resolve subsequence, on the sentinel and the trace."""
    engine, data = served[:2]
    server = _make(engine, "concurrent", queue_depth=3, autostart=False)
    stream = _stream(data, 9)
    tickets = [server.submit(q) for q in stream]
    server.start()
    server.flush()
    got = [server.result(t, timeout=30.0) for t in tickets]
    shed = [g for g in got if g.status == STATUS_SHED]
    assert len(shed) == len(stream) - 3
    for g in shed:
        assert tuple(n for n, _ in g.stages) == ("submit", "admit", "resolve")
        assert well_ordered(g.stages)
    trace = server.take_trace()
    assert len(trace) == len(stream)
    assert all(well_ordered(r.stages) for r in trace)
    by_status = {}
    for r in trace:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    assert by_status == {STATUS_OK: 3, STATUS_SHED: len(stream) - 3}
    server.close()


def test_error_tickets_carry_degenerate_chains(served):
    """Drain failures resolve tickets as status=error with the degenerate
    submit -> admit -> resolve chain — traced, never lost."""
    engine, data = served[:2]
    server = _make(engine, "concurrent", autostart=False)
    real_inner = server._inner

    class _Exploding:
        engine = real_inner.engine
        _pending: list = []
        _ring = deque()
        _results: dict = {}

        def submit(self, q):
            raise ServingError("injected serve failure")

    server._inner = _Exploding()
    stream = _stream(data, 4)
    tickets = [server.submit(q) for q in stream]
    server.start()
    server.flush()
    got = [server.result(t, timeout=30.0) for t in tickets]
    assert all(g.status == STATUS_ERROR for g in got)
    trace = server.take_trace()
    assert len(trace) == len(stream)
    for rec in trace:
        assert rec.status == STATUS_ERROR
        assert tuple(n for n, _ in rec.stages) == \
            ("submit", "admit", "resolve")
        assert well_ordered(rec.stages)
    server._inner = real_inner
    server.close()


def test_close_with_inflight_tickets_traces_everything(served):
    """close() drains queued work — and every drained ticket still gets a
    complete, well-ordered chain (the drain-at-shutdown path is traced
    like any other)."""
    engine, data = served[:2]
    server = _make(engine, "concurrent", autostart=False)
    stream = _stream(data, 9)
    tickets = [server.submit(q, tenant=i % 2) for i, q in enumerate(stream)]
    server.close()
    got = [server.result(t, timeout=30.0) for t in tickets]
    assert all(g.status == STATUS_OK for g in got)
    trace = server.take_trace()
    assert len(trace) == len(stream)
    for rec in trace:
        assert tuple(n for n, _ in rec.stages) == STAGES
        assert well_ordered(rec.stages)


def test_epoch_swap_mid_ring_keeps_chains_well_ordered(served):
    """An engine swap while the pipelined ring holds in-flight buckets:
    every ticket (old epoch and new) resolves with a complete chain."""
    engine, data, params, cfg, freqs = served
    engine2 = RecSysEngine.build(params, cfg, radius=112, n_candidates=16,
                                 top_k=5, hot_rows=32, item_freqs=freqs)
    server = _make(engine, "pipelined", depth=2)
    stream = _stream(data, 16)
    tickets = [server.submit(q) for q in stream[:8]]
    server.swap_engine(engine2)  # ring may still hold old-epoch buckets
    tickets += [server.submit(q) for q in stream[8:]]
    server.flush()
    got = [server.result(t) for t in tickets]
    assert all(g.status == STATUS_OK for g in got)
    trace = server.take_trace()
    assert len(trace) == len(stream)
    for rec in trace:
        assert tuple(n for n, _ in rec.stages) == STAGES
        assert well_ordered(rec.stages)
        assert sum(stage_durations(rec.stages).values()) == pytest.approx(
            rec.latency_s, abs=1e-9)
    server.close()


# ---------------------------------------------------------------------------
# unification: one stats schema, one snapshot, shared registries
# ---------------------------------------------------------------------------
def test_stats_schema_is_identical_across_modes(served):
    engine, data = served[:2]
    keysets, servers = [], []
    for mode in MODES:
        server = _make(engine, mode)
        server.serve_many(_stream(data, 5))
        st = server.stats()
        assert st["mode"] == mode and st["n_served"] == 5
        keysets.append(set(st))
        servers.append(server)
    assert keysets[0] == keysets[1] == keysets[2]
    for server in servers:
        server.close()


@pytest.mark.parametrize("mode", MODES)
def test_snapshot_covers_serving_and_stage_histograms(served, mode):
    engine, data = served[:2]
    server = _make(engine, mode)
    n = len(_stream(data))
    server.serve_many(_stream(data))
    snap = server.snapshot()
    assert snap["serving.mode"] == mode
    assert snap["serving.served"] == n
    assert snap["serving.ticket_latency_s.count"] == n
    for phase in ("stack", "dispatch", "sync", "fanout"):
        assert snap[f"serving.phase.{phase}_s.count"] >= 1
    assert snap["serving.ticket_latency_s.mean"] > 0
    assert snap["cache.lookups"] > 0
    if mode == "concurrent":
        # one latency a ticket, from the outer chain: queue wait included
        trace = server.take_trace()
        assert snap["serving.ticket_latency_s.sum"] == pytest.approx(
            sum(r.latency_s for r in trace))
        assert snap["serving.drain_cycles"] >= 1
        assert snap["serving.per_tenant"][0]["served"] == n
    from benchmarks.bench_io import check_telemetry_schema
    check_telemetry_schema(snap, required=("serving.served",
                                           "serving.ticket_latency_s.count",
                                           "cache.lookups"))
    server.close()


def test_shared_registry_spans_servers(served):
    """A caller-supplied registry is adopted (not replaced) so several
    servers can report into one snapshot."""
    engine, data = served[:2]
    reg = MetricsRegistry()
    server = _make(engine, "sync", registry=reg)
    assert server.registry is reg
    server.serve_many(_stream(data, 3))
    assert reg.snapshot()["serving.served"] == 3
    server.close()


# ---------------------------------------------------------------------------
# phase spans of the serving thread, the collector's pauses, and a snapshot
# taken while the drain thread serves
# ---------------------------------------------------------------------------
def _phase_sums(snap) -> dict:
    return {p: snap.get(f"serving.phase.{p}_s.sum", 0.0) for p in PHASES}


def test_phase_spans_tile_a_chain_and_nest():
    """enter() ends one phase where the next begins; re-entering the open
    phase continues it; only the outermost chain ends the open phase."""
    reg = MetricsRegistry()
    phases = PhaseSpans(reg)
    with phases.chain():
        t0 = phases.enter("wait")
        t1 = phases.enter("stack")
        assert phases.enter("stack") >= t1  # continues, no new span
        with phases.chain():  # a nested flush joins the outer chain
            t2 = phases.enter("dispatch")
        assert reg.snapshot().get("serving.phase.dispatch_s.count", 0) == 0
        t3 = phases.enter("publish")
    snap = reg.snapshot()
    assert snap["serving.phase.wait_s.sum"] == pytest.approx(t1 - t0)
    assert snap["serving.phase.stack_s.count"] == 1
    assert snap["serving.phase.stack_s.sum"] == pytest.approx(t2 - t1)
    assert snap["serving.phase.dispatch_s.sum"] == pytest.approx(t3 - t2)
    assert snap["serving.phase.publish_s.count"] == 1
    assert snap["serving.phase.publish_s.sum"] > 0


def test_drain_phases_cover_the_drain_threads_wall_time(served):
    """A served stream: the drain thread's phases (wait, collect, stack,
    dispatch, sync, fanout, publish) sum to its wall time within 2 %, and
    every cycle is counted."""
    engine, data = served[:2]
    server = _make(engine, "concurrent", autostart=False)
    wall = {}
    loop = server._drain_loop

    def timed_loop():
        wall["start"] = time.perf_counter()
        loop()
        wall["end"] = time.perf_counter()

    server._thread = threading.Thread(target=timed_loop, daemon=True)
    server.start()
    tickets = []
    for wave in range(8):
        tickets += [server.submit(q, tenant=i % 2)
                    for i, q in enumerate(_stream(data, 11))]
        time.sleep(0.03)
    server.close()
    assert all(server.result(t).ok for t in tickets)
    snap = server.snapshot()
    sums = _phase_sums(snap)
    assert all(sums[p] > 0 for p in PHASES), sums
    span = wall["end"] - wall["start"]
    assert sum(sums.values()) == pytest.approx(span, rel=0.02)
    cycles = snap["serving.drain_cycles"]
    assert cycles == snap["serving.phase.publish_s.count"] and cycles >= 8


def test_profiler_trace_holds_the_drain_phases_and_gc(served, tmp_path):
    """On the profiler's clock: a CPU trace of a concurrent server holds
    the serving.<phase> annotations, and a collection under a traced
    front end is a host.gc span and a host.gc_pause_s observation."""
    engine, data = served[:2]
    server = _make(engine, "concurrent")
    server.serve_many(_stream(data, 5))  # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.serve_many(_stream(data))
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    server.close()
    assert server.snapshot()["host.gc_pause_s.count"] >= 1
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {f"serving.{p}" for p in PHASES} <= names, sorted(names)[:40]
    assert "host.gc" in names


def test_gc_hook_lives_with_traced_frontends(served):
    """One gc.callbacks hook a process, installed while a traced front
    end lives; two front ends on one registry count a pause once."""
    from repro.obs import tracing

    engine = served[0]
    reg = MetricsRegistry()
    gc.disable()  # only the collections below
    try:
        a = _make(engine, "concurrent", registry=reg)
        b = _make(engine, "concurrent", registry=reg)
        assert gc.callbacks.count(tracing._on_gc) == 1
        gc.collect()
        assert reg.snapshot()["host.gc_pause_s.count"] == 1
        a.close()
        assert gc.callbacks.count(tracing._on_gc) == 1
        b.close()
        b.close()  # idempotent: the hook goes once
        assert tracing._on_gc not in gc.callbacks
        gc.collect()
        assert reg.snapshot()["host.gc_pause_s.count"] == 1
    finally:
        gc.enable()


def test_snapshot_while_serving_raises_nothing(served):
    """A thread snapshots in a loop while the drain thread serves (and
    donates the cache-stats accumulator): no error, and the final counts
    match a server nobody watched."""
    engine, data = served[:2]
    stream = _stream(data, 40)

    def serve(server):
        for i in range(4):
            server.serve_many(stream[i::4], tenant=i % 2)

    quiet = _make(engine, "concurrent")
    serve(quiet)
    want = quiet.snapshot()
    quiet.close()

    server = _make(engine, "concurrent")
    stop, errors, n_snaps = threading.Event(), [], [0]

    def scrape():
        while not stop.is_set():
            try:
                server.snapshot()
                server.registry.to_prometheus()
                n_snaps[0] += 1
            except Exception as e:  # noqa: BLE001 - the test's finding
                errors.append(e)
                return
            time.sleep(0.001)

    scraper = threading.Thread(target=scrape)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # more interleavings of the three threads
    scraper.start()
    try:
        serve(server)
    finally:
        stop.set()
        scraper.join(timeout=60)
        sys.setswitchinterval(switch)
    assert not scraper.is_alive()
    assert not errors, errors
    assert n_snaps[0] > 0
    got = server.snapshot()
    for key in ("serving.served", "serving.padded", "cache.hits",
                "cache.lookups", "serving.errors"):
        assert got[key] == want[key], key
    server.close()


@pytest.mark.parametrize("step,scopes", [
    ("lookup_step", ("lookup",)), ("scan_step", ("lsh_sign", "scan")),
    ("rank_stage_step", ("rank",))])
def test_stage_programs_carry_their_named_scopes(served, step, scopes):
    """The device work is named: each staged program's ops sit under its
    `jax.named_scope`, inside the jitted function the trace reduction
    matches by name (`_lookup_stage`, `_scan_stage`, `_rank_stage`)."""
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats

    engine, data = served[:2]
    server = _make(engine, "pipelined")
    batch = {k: jax.numpy.asarray(v) for k, v in
             server._stack_np(_stream(data, 3), 4).items()}
    u, pooled, _ = rse.lookup_step(engine, batch, CacheStats.zero())
    cand = rse.scan_step(engine, u).indices
    args = {"lookup_step": (engine, batch, CacheStats.zero()),
            "scan_step": (engine, u),
            "rank_stage_step": (engine, batch, cand, u, pooled,
                                CacheStats.zero())}[step]
    text = getattr(rse, step).lower(*args).as_text(debug_info=True)
    fn = {"lookup_step": "_lookup_stage", "scan_step": "_scan_stage",
          "rank_stage_step": "_rank_stage"}[step]
    for scope in scopes:
        assert f"jit({fn})/{scope}/" in text, scope
