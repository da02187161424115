"""The readers of the web wait's split from inside, on a synthetic context:
what each returns with the program's spans and counters present, and
None (not an error) where the program lacks them."""

from types import SimpleNamespace

import pytest

from harness import inside, registry

NEW = ("pool_wait_ms.paced", "pool_wait_ms.backfill", "resume_ms.paced",
       "resume_ms.backfill", "unseen_ms.paced", "unseen_ms.backfill",
       "launch_ms.paced", "launch_ms.backfill", "loop_stall_share.paced")


def reply(sent, done, **spans):
    return {"ok": True, "sent": sent, "done": done, "spans": spans}


def health(uptime, launch_ms=None, launches=None, stall_ms=None):
    h = {"uptime": uptime, "executor": {"items": 0}}
    if launches is not None:
        h["executor"].update(launch_ms=launch_ms, launches=launches)
    if stall_ms is not None:
        h["eventLoop"] = {"lagMsLast": 0.0, "lagMsMax": 0.0, "samples": 9,
                          "stalls": 1, "stallMsSum": stall_ms}
    return h


def ctx(untraced, h0, ha):
    return SimpleNamespace(untraced=untraced, records=untraced, health_start=h0,
                           profile={"health_a": ha, "health_b": ha,
                                    "interval": (14.0, 16.5)})


def program_ctx():
    replies = [
        reply(1.0, 1.040, pool_wait=2.0, resume=0.5, request=30.0, total=25.0),
        reply(2.0, 2.050, pool_wait=4.0, resume=1.5, request=36.0, total=30.0),
        # a failed reply is left out
        dict(reply(3.0, 3.9, pool_wait=99.0, resume=99.0, request=1.0), ok=False),
    ]
    return ctx(replies, health(100.0, 10.0, 4, 50.0), health(114.0, 40.0, 10, 750.0))


def parent_ctx():
    """What a program without these spans and counters reports."""
    replies = [reply(1.0, 1.04, total=25.0, decode=10.0)]
    h0, ha = health(100.0), health(114.0)
    h0["eventLoop"] = ha["eventLoop"] = {"lagMsLast": 0.0, "lagMsMax": 0.0, "samples": 9}
    return ctx(replies, h0, ha)


def test_unseen_is_client_latency_less_the_request_span():
    # (40 - 30 + 50 - 36) / 2
    assert inside.unseen_ms(program_ctx()) == pytest.approx(12.0)


def test_launch_ms_is_the_counter_delta_per_launch():
    assert inside.launch_ms(program_ctx()) == pytest.approx(30.0 / 6)


def test_loop_stall_share_is_stall_ms_over_the_server_clock():
    assert inside.loop_stall_share(program_ctx()) == pytest.approx(700.0 / 14000.0)


def test_counters_without_a_capture_read_nothing():
    c = program_ctx()
    c.profile = None
    assert inside.launch_ms(c) is None
    assert inside.loop_stall_share(c) is None


@pytest.mark.parametrize("name,expected", [
    ("pool_wait_ms.paced", 3.0), ("pool_wait_ms.backfill", 3.0),
    ("resume_ms.paced", 1.0), ("resume_ms.backfill", 1.0),
    ("unseen_ms.paced", 12.0), ("unseen_ms.backfill", 12.0),
    ("launch_ms.paced", 5.0), ("launch_ms.backfill", 5.0),
    ("loop_stall_share.paced", 0.05),
])
def test_each_metric_file_reads_the_program(name, expected):
    assert registry.metric(name)(program_ctx()) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_each_metric_reads_nothing_from_a_program_without_it(name):
    assert registry.metric(name)(parent_ctx()) is None


def test_each_is_a_per_layer_metric_of_its_one_cell():
    spec = registry.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        cell = "ref-trio.paced" if name.endswith(".paced") else "ref-trio.backfill"
        assert entries[name]["workloads"] == [cell]
        assert entries[name]["moves"] in ("p50_ms", "served_rps")
