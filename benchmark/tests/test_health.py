from harness import health


def snap(items, decode_count, decode_mean, lanes=None):
    ex = {"items": items}
    if lanes is not None:
        ex["lanes"] = [{"served_items": n} for n in lanes]
    return {"executor": ex,
            "stageTimesMs": {"decode": {"count": decode_count, "mean_ms": decode_mean,
                                        "p50_ms": 99.0, "p99_ms": 99.0}}}


def test_counter_delta():
    assert health.executor_delta(snap(10, 0, 0), snap(35, 0, 0), "items") == 25
    assert health.executor_delta({}, snap(3, 0, 0), "items") == 3


def test_stage_delta_uses_count_and_mean_not_the_ring_percentiles():
    # 10 samples at mean 2 ms, then 30 at mean 3 ms: 20 new samples, 70 ms
    count, ms = health.stage_delta(snap(0, 10, 2.0), snap(0, 30, 3.0), "decode")
    assert count == 20
    assert abs(ms - 70.0) < 1e-9
    assert health.stage_delta({}, {}, "decode") == (0, 0.0)


def test_lane_delta():
    a, b = snap(0, 0, 0, lanes=[1, 2, 3, 4]), snap(0, 0, 0, lanes=[11, 12, 13, 24])
    assert health.lane_items_delta(a, b) == [10, 10, 10, 20]
    assert health.lane_items_delta(snap(0, 0, 0), snap(0, 0, 0)) == []
