"""puts_per_launch.*: the executor's launch_puts delta per launch, and
None (not an error) from a program without the counter."""

from types import SimpleNamespace

import pytest

from harness import registry

NAMES = ("puts_per_launch.paced", "puts_per_launch.backfill")


def ctx(e0, e1, profile=True):
    return SimpleNamespace(health_start={"executor": e0},
                           profile={"health_a": {"executor": e1}} if profile else None)


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_counter_delta_per_launch(name):
    c = ctx({"launches": 4, "launch_puts": 9}, {"launches": 10, "launch_puts": 21})
    assert registry.metric(name)(c) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("c", [
    ctx({"launches": 4, "launch_ms": 1.0}, {"launches": 10, "launch_ms": 9.0}),
    ctx({"launches": 4, "launch_puts": 8}, {"launches": 4, "launch_puts": 8}),
    ctx({"launches": 4, "launch_puts": 8}, {"launches": 6, "launch_puts": 12}, profile=False),
], ids=["parent", "no_launch", "untraced"])
def test_reads_nothing_without_the_counter_or_a_launch(name, c):
    assert registry.metric(name)(c) is None


def test_each_is_a_per_layer_metric_of_its_one_cell():
    entries = {m["name"]: m for m in registry.load_spec()["per_layer"]}
    for name in NAMES:
        cell, moves = (("ref-trio.paced", "p50_ms") if name.endswith(".paced")
                       else ("ref-trio.backfill", "served_rps"))
        assert entries[name]["workloads"] == [cell]
        assert entries[name]["moves"] == moves
        assert entries[name]["source"] == "program_counter"
