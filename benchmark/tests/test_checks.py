"""The comparison that decides `correct`, at the cell's sizes on a couple of
inputs: replies made by the reference itself pass; the control (the
reference at 4 bits per sample) and planted faults fail."""

import json
import os

import numpy as np
import pytest

from harness import checks, imaging, registry

SEED = 2147483647 + 12


def cell_parts(config, traffic):
    with open(os.path.join(registry.BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    tr = registry.traffic(traffic)
    gen = dict(cfg["inputs"], count=2)
    return tr["routes"], registry.generator(gen["generator"])(gen, SEED)


def served_like(routes, inputs, frame_of=None):
    """Records as the harness keeps them, each reply the reference coded
    by the route's codec (what a sound program serves, to the codec)."""
    ops, recs, i = checks.Ops(), [], 0
    for route in routes:
        for k in range(len(inputs)):
            frame = checks.expected_frame(route, checks.Source(inputs[k]), ops)
            if frame_of:
                frame = frame_of(route, k, frame)
            recs.append({"i": i, "route": route["name"], "input": k, "ok": True,
                         "body": checks.code(frame, route)})
            i += 1
    return recs


@pytest.fixture(scope="module", params=[("ref-1080p-jpeg", "trio.paced"),
                                        ("pipeline-4k-png", "pipeline4k.paced")])
def parts(request):
    routes, inputs = cell_parts(*request.param)
    return routes, inputs, served_like(routes, inputs)


def test_reference_replies_pass(parts):
    routes, inputs, recs = parts
    cks, _ = checks.judge(recs, routes, inputs, SEED)
    assert checks.passed(cks), cks
    for name, c in cks.items():
        if name.startswith("excess."):
            assert abs(c["value"]) < 1e-9, (name, c)


def test_control_fails_every_route(parts):
    routes, inputs, recs = parts
    ctl = checks.control_bodies(recs, routes, inputs)
    cks, _ = checks.judge(ctl, routes, inputs, SEED)
    assert not checks.passed(cks)
    for route in routes:
        c = cks[f"excess.{route['name']}"]
        assert c["value"] > c["limit"], (route["name"], c)


def test_another_requests_frame_fails(parts):
    routes, inputs, recs = parts
    swapped = [dict(r, body=recs[(j + 1) % len(inputs) + (j // len(inputs)) * len(inputs)]["body"])
               for j, r in enumerate(recs)]
    cks, _ = checks.judge(swapped, routes, inputs, SEED)
    assert not checks.passed(cks)


def test_wrong_geometry_fails(parts):
    routes, inputs, recs = parts
    shifted = served_like(routes, inputs, lambda route, k, f: np.roll(f, 2, axis=1))
    cks, _ = checks.judge(shifted, routes, inputs, SEED)
    assert not checks.passed(cks)
    resized = [dict(r, body=checks.code(imaging.decode(r["body"])[:-2], {"format": "JPEG", "quality": 80}))
               for r in recs]
    cks, _ = checks.judge(resized, routes, inputs, SEED)
    assert cks["wrong_shape"]["value"] == len(recs)


def test_a_failed_reply_is_not_sampled_and_an_empty_sample_fails(parts):
    routes, inputs, recs = parts
    cks, _ = checks.judge([dict(r, ok=False) for r in recs], routes, inputs, SEED)
    assert not checks.passed(cks)
    assert json.dumps(cks)  # inf serialises (as Infinity)
