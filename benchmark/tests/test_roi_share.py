"""roi_share.*: the host codecs' roi_decodes delta per decode, and None
(not an error) from a program without the counter."""

from types import SimpleNamespace

import pytest

from harness import registry

NAMES = ("roi_share.paced", "roi_share.backfill")


def ctx(c0, c1, profile=True):
    return SimpleNamespace(health_start={"codecs": c0} if c0 is not None else {},
                           profile={"health_a": {"codecs": c1} if c1 is not None else {}}
                           if profile else None)


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_counter_delta_per_decode(name):
    c = ctx({"decodes": 30, "roi_decodes": 10}, {"decodes": 90, "roi_decodes": 30})
    assert registry.metric(name)(c) == pytest.approx(1 / 3)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("c", [
    ctx(None, None),
    ctx({"decodes": 4}, {"decodes": 10}),
    ctx({"decodes": 4, "roi_decodes": 2}, {"decodes": 4, "roi_decodes": 2}),
    ctx({"decodes": 4, "roi_decodes": 2}, {"decodes": 6, "roi_decodes": 4}, profile=False),
], ids=["parent", "no_roi_counter", "no_decode", "untraced"])
def test_reads_nothing_without_the_counter_or_a_decode(name, c):
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
        assert entries[name]["layer"] == entries["decode_ms.paced"]["layer"]
