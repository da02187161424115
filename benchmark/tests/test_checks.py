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


def test_nearest_names_the_input_a_frame_came_from(parts):
    routes, inputs, recs = parts
    n = len(inputs)
    # each reply carries the next input's frame of its route
    swapped = [dict(r, body=recs[(j + 1) % n + (j // n) * n]["body"]) for j, r in enumerate(recs)]
    _, detail = checks.judge(swapped, routes, inputs, SEED, diagnose=True)
    assert len(detail["nearest"]) == len(recs)
    for d in detail["nearest"]:
        other = f"input {(d['input'] + 1) % n}"
        assert d["nearest"] == other and d["excess"] > d["nearest_excess"], d
        assert d["bands"] == [other] * checks.NEAREST_BANDS, d


def test_nearest_of_garbage_and_of_a_torn_frame(parts):
    routes, inputs, recs = parts
    ops = checks.Ops()

    def frame_of(route, k, f):
        if k == 0:  # garbage
            return np.zeros_like(f)
        other = checks.expected_frame(route, checks.Source(inputs[0]), ops)
        return np.concatenate([f[:f.shape[0] // 2], other[f.shape[0] // 2:]])  # torn

    _, detail = checks.judge(served_like(routes, inputs, frame_of), routes, inputs, SEED, diagnose=True)
    assert len(detail["nearest"]) == len(recs)
    half = checks.NEAREST_BANDS // 2
    for d in detail["nearest"]:
        if d["input"] == 0:
            assert d["nearest"] == "none" and d["bands"] == ["none"] * checks.NEAREST_BANDS, d
        else:
            assert d["bands"] == ["own"] * half + ["input 0"] * half, d


def test_sound_replies_need_no_diagnosis(parts):
    routes, inputs, recs = parts
    _, detail = checks.judge(recs, routes, inputs, SEED, diagnose=True)
    assert detail["nearest"] == []


def test_nearest_of_a_white_frame_gives_its_levels(parts):
    routes, inputs, recs = parts
    white = served_like(routes, inputs, lambda route, k, f: np.full_like(f, 255.0))
    _, detail = checks.judge(white, routes, inputs, SEED, diagnose=True)
    assert [d["levels"] for d in detail["nearest"]] == [[255.0, 255.0, 255.0]] * len(recs)
    assert {d["nearest"] for d in detail["nearest"]} == {"none"}
