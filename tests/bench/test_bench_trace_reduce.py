"""The trace reduction: busy union, idle share, per-program and per-kernel
device time and gap naming, on synthetic intervals and on a small trace
recorded on one TPU v5e chip (`data/catalog8m_trace.xplane.pb.gz`: a
`catalog8m-closed` window, `bench/run.py --trace 1`)."""
import itertools
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as TR  # noqa: E402

FIXTURE = (pathlib.Path(__file__).parent / "data"
           / "catalog8m_trace.xplane.pb.gz")
PROGRAMS = {"lookup": "_lookup_stage", "scan": "_scan_stage",
            "rank": "_rank_stage"}
KERNELS = {"streaming_nns": "streaming_nns", "hamming": "hamming"}


def test_union_merges_overlaps_and_clips():
    iv = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 25)]
    assert TR.union(iv) == [(0, 3), (5, 12), (20, 25)]
    assert TR.union(iv, 2, 22) == [(2, 3), (5, 12), (20, 22)]
    assert TR.union([(0, 1)], 5, 9) == []


def test_gaps_are_the_complement_in_the_window():
    busy = [(2, 3), (5, 12), (20, 22)]
    assert TR.gaps(busy, 0, 25) == [(0, 2), (3, 5), (12, 20), (22, 25)]
    assert TR.gaps([], 0, 4) == [(0, 4)]
    assert TR.gaps([(0, 4)], 0, 4) == []


@pytest.mark.parametrize("s,e,want", [(0, 30, 10), (4, 6, 1), (12, 20, 0),
                                      (2.5, 21, 8.5), (-5, 0, 0)])
def test_covered_counts_busy_time_in_a_span(s, e, want):
    busy = [(2, 3), (5, 12), (20, 22)]
    prefix = [0, *itertools.accumulate(b - a for a, b in busy)]
    assert TR.covered(busy, prefix, s, e) == pytest.approx(want)


def test_gap_named_by_innermost_bench_span_else_host_event():
    host = [("bench.traced", 0, 100, "t1"), ("bench.client.wait", 10, 40,
                                             "t1"),
            ("PjitFunction(step)", 15, 20, "t2"), ("Execute", 50, 60, "t3")]
    assert TR.name_gap(host, 17) == "bench.client.wait"
    assert TR.name_gap(host, 55) == "bench.traced"
    assert TR.name_gap([h for h in host if not h[0].startswith("bench")],
                       55) == "Execute"
    assert TR.name_gap(host, 200) == "no host span"


def test_op_label_is_the_instruction_name():
    assert TR.op_label("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == \
        "fusion.3"
    assert TR.op_label("copy.1") == "copy.1"


@pytest.fixture(scope="module")
def chip_trace():
    return TR.load(FIXTURE)


@pytest.fixture(scope="module")
def reduced(chip_trace):
    return TR.reduce(chip_trace, TR.span_window(chip_trace, "bench.traced"),
                     programs=PROGRAMS, kernels=KERNELS)


def test_chip_trace_planes(chip_trace):
    assert list(TR.device_lines(chip_trace)) == ["/device:TPU:0"]
    names = {n for n, *_ in TR.host_events(chip_trace)}
    assert {"bench.traced", "bench.client.wait"} <= names


def test_chip_trace_busy_is_the_union_of_ops(chip_trace, reduced):
    lo, hi = TR.span_window(chip_trace, "bench.traced")
    ops = TR.device_lines(chip_trace)["/device:TPU:0"][TR.OPS_LINE]
    busy = TR.union([(s, e) for _, s, e, _ in ops], lo, hi)
    assert reduced["busy_s"] == pytest.approx(sum(e - s for s, e in busy)
                                              / 1e9)
    assert reduced["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(b - a for a, b in TR.gaps(busy, lo, hi)) / 1e9
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"])


def test_chip_trace_programs_and_kernel(reduced):
    progs, kern = reduced["programs"], reduced["kernels"]
    n = progs["scan"]["n"]
    assert n >= 2 and progs["lookup"]["n"] >= n and progs["rank"]["n"] >= n
    # the streaming kernel is one call a scan and nearly all of its time
    assert kern["streaming_nns"]["n"] == n
    assert 0.95 < kern["streaming_nns"]["s"] / progs["scan"]["s"] <= 1.0
    assert progs["scan"]["s"] / n > 1.0  # seconds a batch at 2**23 items
    assert progs["lookup"]["s"] / progs["lookup"]["n"] < 0.05
    assert kern["hamming"]["n"] >= n  # the pending rows scan densely
    assert reduced["device_ops"][0][0].startswith("streaming_nns")


def test_chip_trace_gaps_are_named_and_sum_to_idle(reduced):
    gaps = reduced["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    assert all(isinstance(name, str) and name for name, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert sum(s for _, s in gaps) <= reduced["window_s"] - \
        reduced["busy_s"] + 1e-9
