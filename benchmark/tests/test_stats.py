import math

from harness import stats


def rec(due, done, ok=True):
    return {"due": due, "done": done, "ok": ok}


def test_latency_is_taken_from_the_due_time():
    # sent late (a stalled generator) still counts from when it was due
    lat = stats.latencies_from_due([rec(1.0, 1.25)], close_s=10)
    assert lat == [250.0]


def test_failures_and_never_completed_count_as_misses():
    recs = [rec(0.0, 0.01), rec(0.1, None, ok=False), rec(0.2, 0.3, ok=False)]
    lat = stats.latencies_from_due(recs, close_s=10)
    assert sorted(lat)[0] == 10.0
    assert lat.count(math.inf) == 2


def test_requests_due_after_the_close_are_left_out():
    recs = [rec(9.9, 10.5), rec(10.0, 10.1)]
    lat = stats.latencies_from_due(recs, close_s=10.0)
    assert len(lat) == 1 and abs(lat[0] - 600.0) < 1e-9


def test_nearest_rank_percentile():
    vals = list(range(1, 201))  # 200 samples
    assert stats.pctl(vals, 0.5) == 100
    # p95 of 200: index int(0.95 * 199) = 189 -> value 190, ten samples beyond it
    assert stats.pctl(vals, 0.95) == 190
    assert stats.pctl([], 0.5) != stats.pctl([], 0.5)  # nan


def test_a_miss_lands_in_the_tail():
    vals = [1.0] * 94 + [math.inf] * 6  # index int(0.95 * 99) = 94: the first miss
    assert stats.pctl(vals, 0.95) == math.inf
    assert stats.pctl(vals, 0.5) == 1.0
