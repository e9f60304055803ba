"""Metric arithmetic of the benchmark on synthetic schedules: latency from
the due time, generator lateness, misses in a percentile, and the rate
over the first-to-last completion span."""
import math
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import loadgen, stats, sweep  # noqa: E402


def req(due, submit, done, status="ok", stages=()):
    return (due, submit, done, status, stages)


def recs(rows):
    """Records of `req` rows, from a capacity that has to grow."""
    out = loadgen.Records(capacity=1)
    for ticket, (due, submit, done, status, stages) in enumerate(rows):
        row = out.add(ticket, 0, due, submit)
        if done is not None:
            out.resolve([row], [done], [status], [stages])
    return out


def test_latency_runs_from_due_time_not_submit():
    # the generator ran 40 ms late; the server took 10 ms after submit
    r = req(due=1.00, submit=1.04, done=1.05)
    assert stats.latencies_s(recs([r]))[0] == pytest.approx(0.05)
    assert stats.submit_lateness_s(recs([r]))[0] == pytest.approx(0.04)


@pytest.mark.parametrize("status,done", [("error", 1.2), ("shed", 1.0),
                                         ("pending", None)])
def test_failed_request_is_a_miss_in_every_percentile(status, done):
    ok = [req(1.0, 1.0, 1.0 + 0.001 * i) for i in range(99)]
    bad = req(1.0, 1.0, done, status)
    lat = stats.latencies_s(recs(ok + [bad]))
    assert math.isinf(lat[-1])
    assert math.isinf(stats.percentile(lat, 100))
    # 99 of 100 served: p99 is the slowest served request, p99.5 the miss
    assert stats.percentile(lat, 99) == pytest.approx(0.098)
    assert math.isinf(stats.percentile(lat, 99.5))


def test_percentile_is_nearest_rank():
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 50) == 2.0
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 51) == 3.0
    assert math.isnan(stats.percentile([], 50))


def test_rate_spans_first_to_last_completion_of_chunks():
    # chunks of 512 complete every 7.2 s; the window's edges fall mid-gap
    reqs = [req(0.0, 0.0, 3.0 + 7.2 * k) for k in range(5) for _ in range(512)]
    rate = stats.completion_rate(recs(reqs), (1.0, 31.0))
    # four completions in the window: three chunks after the first
    assert rate == pytest.approx(3 * 512 / (3 * 7.2))
    # a stall between completions counts
    reqs.append(req(0.0, 0.0, 30.9))
    assert stats.completion_rate(recs(reqs), (1.0, 31.0)) < rate


def test_rate_needs_two_completion_times():
    reqs = [req(0.0, 0.0, 5.0) for _ in range(10)]
    assert stats.completion_rate(recs(reqs), (0.0, 10.0)) is None
    assert stats.completion_rate(recs(reqs + [req(0, 0, 6.0, "error")]),
                                 (0.0, 10.0)) is None


def test_stage_gap_reads_span_chain():
    r = req(0, 0, 1, stages=(("submit", 0.0), ("admit", 0.1),
                             ("bucket", 0.35), ("resolve", 1.0)))
    short = req(0, 0, 1, stages=(("submit", 0.0), ("resolve", 1.0)))
    assert stats.stage_gaps_s(recs([r, short]), "admit", "bucket") == \
        pytest.approx([0.25])
    assert len(stats.stage_gaps_s(recs([short]), "admit", "bucket")) == 0


def test_counter_delta_over_window():
    assert stats.counter_delta({"a": 3}, {"a": 10}, "a") == 7
    assert stats.counter_delta({}, {"a": 10}, "a") == 10
    assert stats.counter_delta({}, {}, "a") == 0


def test_poisson_schedule_is_seeded_and_at_rate():
    a = loadgen.poisson_offsets(2000.0, 20.0, np.random.default_rng(5))
    b = loadgen.poisson_offsets(2000.0, 20.0, np.random.default_rng(5))
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[-1] < 20.0
    assert len(a) == pytest.approx(40_000, rel=0.03)


def test_every_seed_offers_the_same_work_in_its_own_order():
    a_off, a_users = loadgen.open_schedule(
        3000.0, 5.0, 6040, 1.1, np.random.default_rng(1))
    b_off, b_users = loadgen.open_schedule(
        3000.0, 5.0, 6040, 1.1, np.random.default_rng(2 ** 40 + 3))
    assert len(a_off) == len(b_off) == pytest.approx(15_000, rel=0.05)
    assert np.all(np.diff(a_off) > 0) and a_off[-1] < 5.0
    np.testing.assert_allclose(np.sort(np.diff(a_off, prepend=0)),
                               np.sort(np.diff(b_off, prepend=0)))
    np.testing.assert_array_equal(np.sort(a_users), np.sort(b_users))
    assert not np.array_equal(a_users, b_users)
    one = loadgen.user_cycle(6040, 1.1, np.random.default_rng(1))
    two = loadgen.user_cycle(6040, 1.1, np.random.default_rng(2))
    a = [next(one) for _ in range(2 * loadgen.USER_CYCLE)]
    b = [next(two) for _ in range(2 * loadgen.USER_CYCLE)]
    n = loadgen.USER_CYCLE
    assert sorted(a[:n]) == sorted(b[:n]) == sorted(a[n:]) and a != b


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 40])
def test_subseed_takes_large_seeds(seed):
    s = loadgen.subseed(seed, 3)
    assert 0 <= s < 2 ** 31
    assert s == loadgen.subseed(seed, 3) != loadgen.subseed(seed, 4)


def test_zipf_popularity_is_the_same_for_every_seed():
    a = loadgen.zipf_users(np.random.default_rng(1), 6040, 200_000, 1.1)
    b = loadgen.zipf_users(np.random.default_rng(2), 6040, 200_000, 1.1)
    assert not np.array_equal(a, b)
    top_a, top_b = np.mean(a == 0), np.mean(b == 0)
    assert top_a == pytest.approx(top_b, rel=0.05)
    assert np.mean(a < 10) == pytest.approx(np.mean(b < 10), rel=0.03)


def test_records_grow_and_keep_rows():
    rows = [req(0.1 * i, 0.1 * i, 1.0 + i) for i in range(37)]
    r = recs(rows)
    assert len(r) == 37 and r.cap >= 37
    np.testing.assert_allclose(r.due, [0.1 * i for i in range(37)])
    r.store([3], [np.arange(4)], [np.full(4, 0.5, np.float32)])
    assert r.redeemed.tolist() == [i == 3 for i in range(37)]
    part = r.take(r.due < 1.0)
    assert len(part) == 10 and part.items[3].tolist() == [0, 1, 2, 3]
    assert np.isnan(part.scores[0]).all() and part.items[0, 0] == -1


def test_records_hold_no_objects_the_collector_walks():
    """The window runs with the garbage collector on: a window's records
    must not add tracked objects per request."""
    import gc

    r = loadgen.Records(capacity=8)
    for i in range(20_000):
        row = r.add(i, i % 7, float(i), float(i))
        r.resolve([row], [i + 0.5], ["ok"], [(("admit", 0.1),
                                              ("bucket", 0.2))])
        r.index.pop(i)
    assert not any(gc.is_tracked(v) for v in r._cols.values())
    assert not gc.is_tracked(r.index)


@pytest.mark.parametrize("sustained,expect", [
    ({4: [1, 1, 1], 8: [1, 1, 1], 12: [1, 0, 1], 16: [1, 1, 1]}, 16),
    ({4: [1, 1, 1], 8: [0, 0, 1], 12: [1, 1, 1]}, 4),
    ({4: [0, 1, 1], 8: [1, 1, 1]}, 8),
    ({4: [0, 0, 1], 8: [1, 1, 1]}, None)])
def test_knee_is_highest_rate_most_offers_sustain(sustained, expect):
    offers = [{"rate": float(rate), "sustained": bool(s)}
              for rate, marks in sustained.items() for s in marks]
    assert sweep.knee(offers) == (None if expect is None else float(expect))
