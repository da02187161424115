"""The program's own spans on the device trace's clock.

One stage API (engine/timing.stage, obs/trace.span and .annotation)
records the stage timers and request spans as before, and while a
jax.profiler capture is active also opens a TraceAnnotation; the capture
runs without JAX's Python tracer and off the event loop. Also the web
wait's split from inside (`pool_wait`, `resume`, `request` spans), the
executor's launch counters and the event loop's stall counters.

Every test runs under a time limit: a thread joined with a timeout for
the synchronous ones, asyncio.wait_for for the served ones.
"""

import asyncio
import glob
import io
import os
import threading
import time

import numpy as np
import pytest

from imaginary_tpu.engine import Executor, ExecutorConfig, timing
from imaginary_tpu.obs import trace as obs_trace
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation
from imaginary_tpu.params import build_params_from_query
from imaginary_tpu.pipeline import process_operation
from imaginary_tpu.web.config import ServerOptions
from tests.conftest import fixture_bytes

LIMIT_S = 120.0


@pytest.fixture(scope="module", autouse=True)
def _fixtures(testdata):
    return testdata


def bounded(fn, seconds=LIMIT_S):
    """fn() on a thread, failing the test if it has not returned in time."""
    out = {}

    def body():
        try:
            out["value"] = fn()
        except BaseException as e:  # handed to the test's thread below
            out["error"] = e

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"did not finish within {seconds} s"
    if "error" in out:
        raise out["error"]
    return out.get("value")


def serve(options, fn, seconds=LIMIT_S):
    """fn(client) against a fresh app, within `seconds`."""
    from aiohttp.test_utils import TestClient, TestServer

    from imaginary_tpu.web.app import create_app

    async def runner():
        client = TestClient(TestServer(create_app(options, log_stream=io.StringIO())))
        await client.start_server()
        try:
            await fn(client)
        finally:
            await client.close()

    asyncio.run(asyncio.wait_for(runner(), seconds))


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation and records its names."""

    made: list = []

    def __init__(self, name):
        FakeAnnotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax

    FakeAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.made


def _img(h, w, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (h, w, 3), dtype=np.uint8)


def _resize_plan(h, w, width):
    return plan_operation("resize", ImageOptions(width=width), h, w, 0, 3)


def _host_event_names(trace_dir) -> set:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    assert paths, "the capture wrote no .xplane.pb"
    names = set()
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


class TestCapture:
    def test_capture_holds_program_annotations_and_no_python_frames(self, tmp_path):
        def body():
            ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
            buf = fixture_bytes("imaginary.jpg")
            opts = build_params_from_query({"width": "120"})
            try:
                # compile outside the capture
                process_operation("resize", buf, opts, runner=ex.process)
                assert timing.start_profiler(str(tmp_path))
                try:
                    for _ in range(3):
                        process_operation("resize", buf, opts, runner=ex.process)
                finally:
                    timing.stop_profiler()
            finally:
                ex.shutdown()
            return _host_event_names(str(tmp_path))

        names = bounded(body)
        for name in ("executor.await_items", "executor.launch", "executor.drain",
                     "executor.await_chunks", "probe", "decode", "encode"):
            assert name in names, name
        # JAX's Python tracer names its events "$<module> <function>"
        assert not [n for n in names if n.startswith("$")]
        # enclosing stages stay off the capture
        assert "total" not in names and "execute" not in names
        assert not obs_trace.capture_active

    def test_no_annotation_constructed_without_capture(self, fake_annotation):
        def body():
            assert not obs_trace.capture_active
            ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
            try:
                process_operation("resize", fixture_bytes("imaginary.jpg"),
                                  build_params_from_query({"width": "120"}),
                                  runner=ex.process)
                ex.process(_img(64, 48), _resize_plan(64, 48, 32))
            finally:
                ex.shutdown()
            with obs_trace.span("fetch"):
                pass

        bounded(body)
        assert fake_annotation == []

    def test_annotations_follow_the_capture_flag(self, fake_annotation, monkeypatch):
        monkeypatch.setattr(obs_trace, "capture_active", True)
        with timing.stage("decode"):
            pass
        with timing.stage("total", annotate=False):
            pass
        with obs_trace.annotation("executor.launch"):
            pass
        with obs_trace.span("fetch"):
            pass
        with obs_trace.span("execute", annotate=False):
            pass
        assert fake_annotation == ["decode", "executor.launch", "fetch"]

    def test_span_on_the_event_loop_is_not_annotated(self, fake_annotation,
                                                     monkeypatch):
        monkeypatch.setattr(obs_trace, "capture_active", True)

        async def on_loop():
            with obs_trace.span("fetch"):
                await asyncio.sleep(0)

        asyncio.run(asyncio.wait_for(on_loop(), LIMIT_S))
        assert fake_annotation == []

    def test_profile_capture_leaves_the_server_answering(self, tmp_path,
                                                         monkeypatch):
        # writing a capture out takes seconds on a busy chip host: stand
        # that in with a slow stop, and ask /health while it runs
        real_stop = timing.stop_profiler

        def slow_stop():
            time.sleep(0.6)
            real_stop()

        monkeypatch.setattr(timing, "stop_profiler", slow_stop)

        async def health_ms(client, after_s):
            """Ask /health `after_s` from now; ms from then until it
            answered. The client shares the server's event loop, so a
            blocked loop delays the asking as much as the answer."""
            due = time.monotonic() + after_s
            await asyncio.sleep(after_s)
            res = await client.get("/health")
            assert res.status == 200
            return (time.monotonic() - due) * 1000.0

        async def fn(client):
            prof = asyncio.ensure_future(client.get(
                "/debugz/profile", params={"seconds": "1", "dir": str(tmp_path)}))
            during = await health_ms(client, 0.5)
            # the capture's 1 s is over: it is stopping
            stopping = await health_ms(client, 0.8)
            res = await prof
            assert res.status == 200
            body = await res.json()
            assert body["start_s"] >= 0.0 and body["stop_s"] >= 0.6
            assert not timing.profiler_active()
            assert during < 200.0, f"/health took {during:.1f} ms during a capture"
            assert stopping < 200.0, f"/health took {stopping:.1f} ms while it stopped"

        serve(ServerOptions(enable_debug=True), fn)


class TestStage:
    def test_records_on_clean_exit_only(self):
        timing.TIMES.reset()
        with timing.stage("probe"):
            pass
        with pytest.raises(ValueError):
            with timing.stage("probe"):
                raise ValueError("the stage failed")
        assert timing.TIMES.snapshot()["probe"]["count"] == 1

    @pytest.mark.parametrize("mesh_policy", ["off", "lanes"])
    def test_executor_counts_launches(self, mesh_policy):
        def body():
            ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                         mesh_policy=mesh_policy, n_devices=2))
            try:
                for seed in range(3):
                    ex.process(_img(64, 48, seed), _resize_plan(64, 48, 32))
                return ex.stats.to_dict()
            finally:
                ex.shutdown()

        stats = bounded(body)
        assert stats["launches"] == stats["batches"] >= 1
        assert stats["launch_ms"] > 0.0


class TestWebSplit:
    def test_server_timing_carries_the_web_split(self):
        async def fn(client):
            res = await client.post("/resize?width=100",
                                    data=fixture_bytes("imaginary.jpg"))
            assert res.status == 200
            spans = {}
            for entry in res.headers["Server-Timing"].split(","):
                name, _, dur = entry.strip().partition(";dur=")
                spans[name] = float(dur)
            for name in ("pool_wait", "resume", "request", "total"):
                assert name in spans, name
                assert spans[name] >= 0.0
            assert spans["request"] >= spans["total"]
            assert len(spans) <= 16

        serve(ServerOptions(), fn)

    def test_event_loop_counts_an_injected_block_as_a_stall(self):
        from imaginary_tpu.obs import looplag

        def block_the_loop(seconds):
            time.sleep(seconds)

        async def probe():
            task = looplag.start(0.05)
            try:
                await asyncio.sleep(0.12)
                before = looplag.snapshot()
                block_the_loop(0.12)
                await asyncio.sleep(0.12)
                return before, looplag.snapshot()
            finally:
                looplag.stop(task)

        before, after = asyncio.run(asyncio.wait_for(probe(), LIMIT_S))
        assert after["stalls"] - before["stalls"] >= 1
        assert after["stallMsSum"] - before["stallMsSum"] >= 50.0
