"""The reduction from a profiler capture to device numbers."""

import glob
import json
import os

import pytest

from harness import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    # ns; device busy [100, 300) and [500, 600) on a window [0, 1000)
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
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)  # [100,300) + [500,600)
    assert r["devices"] == 1


def test_modules_drop_the_program_id():
    r = xtrace.reduce(synthetic())
    assert r["modules"] == {"jit__run_chain": {"count": 2, "seconds": pytest.approx(300e-9)}}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]


def test_idle_gaps_are_labelled_by_the_innermost_host_event():
    gaps = xtrace.reduce(synthetic())["idle_gaps"]
    # holes: [600,1000) 400, [300,500) 200, [0,100) 100
    assert [round(s * 1e9) for _, s in gaps] == [400, 200, 100]
    # [600,1000): encode covers 300 of it, serve all 400 -> most overlap wins
    assert gaps[0][0] == "serve"
    # [300,500): serve 200, decode 150 -> serve; [0,100): serve
    assert [g[0] for g in gaps] == ["serve", "serve", "serve"]


def test_equal_overlap_goes_to_the_shorter_event():
    flat = synthetic()
    flat["/host:CPU"]["python"].append(("drain", 290.0, 220.0))  # covers [300,500) fully
    gaps = xtrace.reduce(flat)["idle_gaps"]
    assert gaps[1][0] == "drain"


def test_window_is_clipped_to_the_seconds_asked_for():
    r = xtrace.reduce(synthetic(), window_s=550e-9)
    assert r["window_s"] == pytest.approx(550e-9)
    assert r["busy_s"] == pytest.approx(250e-9)  # [100,300) + [500,550)
    assert r["modules"]["jit__run_chain"]["count"] == 2


def test_no_device_plane_gives_nothing():
    assert xtrace.reduce({"/host:CPU": {"python": [("x", 0.0, 5.0)]}}) is None


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
