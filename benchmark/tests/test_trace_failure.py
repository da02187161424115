"""A traced run that gives no device numbers fails with its reason: it exits
1 with `trace:` in its message, prints no result line, and keeps the reason
and the profile call's reply in its run.json. Whole runs on the CPU, where
a capture holds no device plane at all."""

import asyncio  # noqa: F401  (run.py's loop)
import json
import os

import pytest

import run
from harness import xtrace


def traced_run(args, capsys) -> tuple:
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "1"]
    rc = run.main(argv)
    out, err = capsys.readouterr()
    with open(os.path.join(run.OUT, args.workload, f"{args.seed}-t1", "run.json")) as f:
        return rc, out, err, json.load(f)


def assert_failed_with(rc, out, err, saved, reason):
    assert rc == 1
    assert not any(line.lstrip().startswith("{") for line in out.splitlines()), out
    last = err.strip().splitlines()[-1]
    assert last.startswith("[bench] FAILED: trace: ") and reason in last, last
    assert saved["capture"]["error"] == last[len("[bench] FAILED: "):]
    assert saved["trace"] is None and saved["profile_interval"]


def test_refused_capture(small_cell, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    real = run.get_json

    async def get_json(base, path, timeout=60.0):
        if path.startswith("/debugz/profile"):
            return {"error": "a profiler capture is already active"}
        return await real(base, path, timeout)

    monkeypatch.setattr(run, "get_json", get_json)
    rc, out, err, saved = traced_run(small_cell, capsys)
    assert_failed_with(rc, out, err, saved, "the capture was refused")
    assert saved["capture"]["reply"] == {"error": "a profiler capture is already active"}


def test_no_capture_file(small_cell, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(xtrace, "find_capture", lambda trace_dir: None)
    rc, out, err, saved = traced_run(small_cell, capsys)
    assert_failed_with(rc, out, err, saved, "wrote no .xplane.pb")
    assert saved["capture"]["reply"]["stop_s"] >= 0


def test_capture_without_a_device_plane(small_cell, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, out, err, saved = traced_run(small_cell, capsys)
    assert_failed_with(rc, out, err, saved, "no device plane in the capture")
    lines = saved["capture"]["lines"]
    assert lines and all(p.startswith("/host:") for p, *_ in lines)


@pytest.mark.parametrize("reply", [{"error": "x"}, ["not", "a", "reply"]])
def test_read_capture_gives_the_reason(tmp_path, reply):
    got = run.read_capture(reply, str(tmp_path), run.PROFILE_S)
    assert got["error"].startswith("trace: the capture was refused") and "trace" not in got
