"""The reduction from a profiler capture to device numbers."""

import glob
import json
import os

import pytest

from harness import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    # ns; device busy [100, 300) and [500, 600) on a window [100, 1000): it
    # starts at the device's first event, not at the host's at 0
    return {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 100.0, 150.0), ("copy.2", 200.0, 100.0),
                        ("fusion.1", 500.0, 100.0)],
            "XLA Modules": [("jit__run_chain(17)", 100.0, 200.0), ("jit__run_chain(17)", 500.0, 100.0)],
        },
        "/host:CPU": {
            "python": [("serve", 0.0, 1000.0), ("decode", 320.0, 150.0),
                       ("ThreadpoolListener::StartRegion", 310.0, 0.0)],
            "worker": [("encode", 650.0, 300.0)],
        },
    }


def test_busy_is_the_union_of_op_intervals():
    r = xtrace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(900e-9)
    assert r["busy_s"] == pytest.approx(300e-9)  # [100,300) + [500,600)
    assert r["devices"] == 1
    assert r["anchor_s"] == pytest.approx(100e-9)


def test_modules_drop_the_program_id():
    r = xtrace.reduce(synthetic())
    assert r["modules"] == {"jit__run_chain": {"count": 2, "seconds": pytest.approx(300e-9)}}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]


def test_idle_gaps_are_labelled_by_the_innermost_host_event():
    gaps = xtrace.reduce(synthetic())["idle_gaps"]
    # holes: [600,1000) 400, [300,500) 200; [0,100) is before the window
    assert [round(s * 1e9) for _, s in gaps] == [400, 200]
    # [600,1000): encode covers 300 of it, serve all 400 -> most overlap wins
    assert gaps[0][0] == "serve"
    # [300,500): serve 200, decode 150 -> serve
    assert [g[0] for g in gaps] == ["serve", "serve"]


def test_equal_overlap_goes_to_the_shorter_event():
    flat = synthetic()
    flat["/host:CPU"]["python"].append(("drain", 290.0, 220.0))  # covers [300,500) fully
    gaps = xtrace.reduce(flat)["idle_gaps"]
    assert gaps[1][0] == "drain"


def test_window_is_clipped_to_the_seconds_asked_for():
    r = xtrace.reduce(synthetic(), window_s=450e-9)
    assert r["window_s"] == pytest.approx(450e-9)
    assert r["busy_s"] == pytest.approx(250e-9)  # [100,300) + [500,550)
    assert r["modules"]["jit__run_chain"]["count"] == 2


def test_no_device_plane_gives_nothing():
    with pytest.raises(xtrace.NoDeviceTime, match="no device plane in the capture"):
        xtrace.reduce({"/host:CPU": {"python": [("x", 0.0, 5.0)]}}, 2.0)


def test_a_device_plane_without_ops_gives_nothing():
    flat = {"/host:CPU": {"python": [("x", 0.0, 5.0)]}, "/device:TPU:0": {"XLA Ops": []}}
    with pytest.raises(xtrace.NoDeviceTime, match="no device plane with ops"):
        xtrace.reduce(flat, 2.0)


def test_no_op_in_the_window_gives_nothing():
    # the device plane's first event is a program 3 s before its first op
    flat = {"/device:TPU:0": {"XLA Modules": [("jit_f(1)", 0.0, 1e3)],
                              "XLA Ops": [("fusion.1", 3e9, 1e6)]}}
    with pytest.raises(xtrace.NoDeviceTime, match="no device op in the 2.000 s window"):
        xtrace.reduce(flat, 2.0)


def slow_start(host_lead_s):
    """A capture whose host tracer records `host_lead_s` before the device
    tracer's first event, as when starting the capture blocks under load:
    the host thread asking for it from 0, the device busy 10 ms of every
    100 ms from then on, for 2.5 s."""
    t_dev = host_lead_s * 1e9
    ops = [("fusion.1", t_dev + k * 1e8, 1e7) for k in range(25)]
    return {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [("jit__run_chain(3)", s, d) for _, s, d in ops]},
        "/host:CPU": {"python3": [("start_trace", 0.0, t_dev)],
                      "pjrt-tpu-tasks/1": [("launch", s - 1e6, 1e6) for _, s, _ in ops]},
    }


def test_window_starts_where_the_device_was_traced():
    flat = slow_start(2.5)
    # a window anchored at the host's first event, [0, 2 s), holds no op
    assert min(s for _, s, _ in flat["/device:TPU:0"]["XLA Ops"]) >= 2e9
    r = xtrace.reduce(flat, 2.0)
    assert r["anchor_s"] == pytest.approx(2.5)
    assert r["window_s"] == pytest.approx(2.0)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(20 * 1e-2)  # 20 of the 25 ops start in [2.5, 4.5) s
    assert r["modules"]["jit__run_chain"]["count"] == 20


def test_a_fast_start_reads_the_same_from_either_anchor():
    # the device traced 1 ms after the host: the window moves by 1 ms
    r = xtrace.reduce(slow_start(0.001), 2.0)
    assert r["anchor_s"] == pytest.approx(0.001)
    assert r["busy_s"] == pytest.approx(0.2)


def test_digest_lists_every_line_from_the_first_event():
    d = xtrace.digest(synthetic())
    assert ["/device:TPU:0", "XLA Ops", 3, pytest.approx(100e-9), pytest.approx(600e-9)] in d
    assert ["/host:CPU", "python", 3, 0.0, pytest.approx(1000e-9)] in d
    assert len(d) == 4


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


RECORDED = sorted(glob.glob(os.path.join(DATA, "**", "*.xplane.pb"), recursive=True))


@pytest.mark.skipif(not RECORDED, reason="no recorded capture under tests/data")
def test_recorded_chip_capture():
    """A capture the program wrote on a v5e chip: the reduction agrees with
    a plain recount from the flattened events and with what was recorded
    beside it."""
    with open(os.path.join(DATA, "recorded_capture.json")) as f:
        want = json.load(f)
    flat = xtrace.flatten(RECORDED[0])
    r = xtrace.reduce(flat, want["capture_seconds"])
    assert r is not None and r["devices"] == 1
    ops = flat[xtrace.device_planes(flat)[0]][xtrace.OPS_LINE]
    # plain recount of busy: mark every covered ns-bucket of 1 us
    covered = set()
    for _, s, d in ops:
        covered.update(range(int(s // 1000), int((s + d) // 1000) + 1))
    assert r["busy_s"] <= len(covered) * 1e-6 + 1e-9
    assert r["busy_s"] >= sum(d for _, _, d in ops) / 1e9 / 64  # nested ops count once
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(want["capture_seconds"])
    assert r["modules"][xtrace.module_name(want["chain_module"])]["count"] == want["chain_launches"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)


def test_recorded_pipeline_capture_digest():
    """The planes and lines of a capture of 4K /pipeline traffic on a v5e
    chip, rebuilt from their first and last events: the device's events
    begin 2.02 s after the host's first, so a window anchored at the host's
    first event held no device op; anchored at the device's, it holds some."""
    with open(os.path.join(DATA, "pipeline4k_capture_digest.json")) as f:
        rec = json.load(f)
    flat: dict = {}
    for plane, line, n, first, last in rec["lines"]:
        flat.setdefault(plane, {})[line] = [("first", first * 1e9, 1e3), ("last", last * 1e9 - 1e3, 1e3)]
    got = xtrace.digest(flat)
    assert [d[:2] for d in got] == [d[:2] for d in rec["lines"]]
    assert [t for d in got for t in d[3:]] == pytest.approx([t for d in rec["lines"] for t in d[3:]], abs=1e-9)
    window = rec["capture_seconds"]
    dev_first = min(first for plane, _, _, first, _ in rec["lines"] if plane.startswith(xtrace.DEVICE_PREFIX))
    host_last = max(last for plane, _, _, _, last in rec["lines"] if plane == xtrace.HOST_PLANE)
    assert rec["host_anchored_busy_s"] == 0.0 and dev_first > window and host_last < dev_first
    r = xtrace.reduce(flat, window)
    assert r["anchor_s"] == pytest.approx(dev_first)
    assert 0 < r["busy_s"] <= r["window_s"] == pytest.approx(window)
