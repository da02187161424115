"""early_close_share.*: the executor's early_closes delta per chunk, and
None (not an error) from a program without the counter."""

from types import SimpleNamespace

import pytest

from harness import registry

NAMES = ("early_close_share.paced", "early_close_share.backfill")


def ctx(e0, e1, profile=True):
    return SimpleNamespace(health_start={"executor": e0},
                           profile={"health_a": {"executor": e1}} if profile else None)


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_counter_delta_per_chunk(name):
    c = ctx({"batches": 10, "early_closes": 3}, {"batches": 30, "early_closes": 18})
    assert registry.metric(name)(c) == pytest.approx(0.75)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("c", [
    ctx({"batches": 4, "launches": 4}, {"batches": 10, "launches": 10}),
    ctx({"batches": 4, "early_closes": 2}, {"batches": 4, "early_closes": 2}),
    ctx({"batches": 4, "early_closes": 2}, {"batches": 6, "early_closes": 4}, profile=False),
], ids=["parent", "no_chunk", "untraced"])
def test_reads_nothing_without_the_counter_or_a_chunk(name, c):
    assert registry.metric(name)(c) is None


def test_each_is_a_per_layer_metric_of_its_one_cell():
    entries = {m["name"]: m for m in registry.load_spec()["per_layer"]}
    for name in NAMES:
        cell, moves = (("ref-trio.paced", "p50_ms") if name.endswith(".paced")
                       else ("ref-trio.backfill", "served_rps"))
        assert entries[name]["workloads"] == [cell]
        assert entries[name]["moves"] == moves
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["unit"] == "share"
        assert entries[name]["layer"] == entries["launch_ms.paced"]["layer"]
