"""The device's idle time split by the drain thread's phases
(`bench/host_phases.py`) and the two front-end readers built on it
(`drain_host_ms`, `idle_host_frac`): on synthetic intervals, on the chip
trace of a program without phase spans, and on the host side of a CPU
profiler trace of a concurrent server."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import host_phases as HP, spec, trace_reduce as TR  # noqa: E402

FIXTURE = (pathlib.Path(__file__).parent / "data"
           / "catalog8m_trace.xplane.pb.gz")


def _host(line="drain"):
    """A drain thread tiling [0, 100) with phases, a gc pause on another
    thread, and a benchmark span."""
    tiles = [("wait", 0, 20), ("collect", 20, 25), ("stack", 25, 35),
             ("dispatch", 35, 40), ("sync", 40, 70), ("fanout", 70, 85),
             ("publish", 85, 95), ("wait", 95, 100)]
    return ([(f"serving.{p}", s, e, line) for p, s, e in tiles]
            + [("host.gc", 30, 38, "gen"), ("bench.gen.sleep", 0, 100,
                                            "gen")])


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(5, 15)], 5), ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [(10, 20)], 0), ([], [(0, 5)], 0),
    ([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 20)], 1 + 1 + 1 + 1)])
def test_overlap_is_the_intersection_length(a, b, want):
    assert HP.overlap(a, b) == want
    assert HP.overlap(b, a) == want


def test_split_charges_idle_time_to_the_phase_under_it():
    # the device runs 40..70 (the sync phase) and 0..10 (under wait)
    out = HP.split_intervals({"d0": [(40, 70), (0, 10)]}, _host(), 0, 100)
    assert out["line"] == "drain"
    assert out["idle_s"] == pytest.approx(60e-9)
    by = {k: v * 1e9 for k, v in out["by_phase_s"].items()}
    assert by == pytest.approx({"wait": 15, "collect": 5, "stack": 10,
                                "dispatch": 5, "sync": 0, "fanout": 15,
                                "publish": 10, "none": 0, "host.gc": 8})
    assert out["host_frac"] == pytest.approx(45 / 60)
    assert out["cover"] == pytest.approx(1.0)
    assert out["phase_s"]["wait"] == pytest.approx(25e-9)
    assert out["phase_s"]["sync"] == pytest.approx(30e-9)


def test_split_averages_devices_and_counts_uncovered_idle():
    host = [h for h in _host() if h[0] != "serving.publish"]
    out = HP.split_intervals({"d0": [(40, 70)], "d1": [(0, 100)]}, host,
                             0, 100)
    # d0 idles 70 ns, d1 never: per chip 35 ns
    assert out["idle_s"] == pytest.approx(35e-9)
    assert out["by_phase_s"]["none"] == pytest.approx(5e-9)  # 85..95 / 2
    assert out["host_frac"] == pytest.approx((5 + 10 + 5 + 15) / 70)
    assert out["cover"] == pytest.approx(0.9)


def test_split_clips_to_the_window():
    out = HP.split_intervals({"d0": []}, _host(), 30, 50)
    assert out["idle_s"] == pytest.approx(20e-9)
    assert out["by_phase_s"]["stack"] == pytest.approx(5e-9)
    assert out["by_phase_s"]["sync"] == pytest.approx(10e-9)
    assert out["by_phase_s"]["host.gc"] == pytest.approx(8e-9)


def test_no_phase_spans_no_split():
    host = [h for h in _host() if not h[0].startswith("serving.")]
    assert HP.split_intervals({"d0": [(0, 5)]}, host, 0, 100) is None
    # a chip trace of a program that emits no phase spans
    assert HP.split(TR.load(FIXTURE)) is None


def _ctx(snap0, snap1, trace=None):
    return types.SimpleNamespace(snap0=snap0, snap1=snap1, trace=trace)


def test_drain_host_ms_reads_the_host_phases_a_cycle():
    reader = spec.reader("drain_host_ms", ROOT)
    snap0 = {"serving.drain_cycles": 10, "serving.phase.stack_s.sum": 1.0,
             "serving.phase.sync_s.sum": 5.0}
    snap1 = {"serving.drain_cycles": 30, "serving.phase.stack_s.sum": 1.1,
             "serving.phase.sync_s.sum": 9.0,
             "serving.phase.collect_s.sum": 0.02,
             "serving.phase.dispatch_s.sum": 0.04,
             "serving.phase.fanout_s.sum": 0.06,
             "serving.phase.publish_s.sum": 0.08,
             "serving.phase.wait_s.sum": 3.0}
    # (0.1 + 0.02 + 0.04 + 0.06 + 0.08) s over 20 cycles; sync, wait out
    assert reader.read(_ctx(snap0, snap1)) == pytest.approx(15.0)
    # a program without the counter gives nothing to read
    assert reader.read(_ctx({}, {"serving.served": 5})) is None


def test_idle_host_frac_reads_the_trace_only_when_traced(monkeypatch):
    reader = spec.reader("idle_host_frac", ROOT)
    assert reader.read(_ctx({}, {})) is None
    monkeypatch.setattr(HP, "read_split", lambda: {"host_frac": 0.8})
    assert reader.read(_ctx({}, {}, trace={})) == 0.8
    monkeypatch.setattr(HP, "read_split", lambda: None)
    assert reader.read(_ctx({}, {}, trace={})) is None


def test_split_of_a_cpu_trace_of_a_concurrent_server(tmp_path):
    """The host side of a real profiler trace: the drain thread's phase
    spans are found on one line and tile its time; with the device idle
    throughout, the split charges every phase."""
    import jax
    import numpy as np

    from repro.data import synthetic
    from repro.data.synthetic import serving_queries
    from repro.models import recsys as rs
    from repro.serving import RecSysEngine, make_server

    data = synthetic.make_movielens(n_users=60, n_items=50, history_len=4)
    cfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={"user_id": data.n_users, "gender": 3, "age": 7,
                       "occupation": 21, "zip_bucket": 250},
        history_len=4)
    engine = RecSysEngine.build(rs.init_youtubednn(jax.random.key(1), cfg),
                                cfg, radius=112, n_candidates=8, top_k=4)
    server = make_server(engine, "concurrent", max_batch=8)
    stream = serving_queries(data, np.arange(24) % 9)
    server.serve_many(stream)  # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            server.serve_many(stream)
    finally:
        jax.profiler.stop_trace()
    server.close()
    host = TR.host_events(TR.load(tmp_path))
    line = HP.phase_line(host)
    assert line is not None
    mine = [(s, e) for n, s, e, ln in host
            if ln == line and n.startswith("serving.")]
    lo, hi = min(s for s, _ in mine), max(e for _, e in mine)
    out = HP.split_intervals({"cpu": []}, host, lo, hi)
    assert out["cover"] > 0.98
    assert out["idle_s"] == pytest.approx((hi - lo) / 1e9)
    assert all(out["by_phase_s"][p] > 0 for p in HP.HOST_PHASES)
    assert 0 < out["host_frac"] < 1
