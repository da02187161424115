"""Observability layer (imaginary_tpu/obs/ + its web/engine threading).

Covers the ISSUE 3 acceptance list: X-Request-ID / traceparent
propagation (inbound passthrough, generation, outbound forwarding to
origins), histogram bucket monotonicity + _sum/_count consistency,
Server-Timing response header contents, /debugz gating (404 when
disabled, auth posture when enabled), the wide-event JSON schema, and a
STRICT Prometheus exposition-format parse of /metrics (HELP/TYPE per
family, grouped samples, escaped labels, no duplicate series).
"""

import asyncio
import io
import json
import re
import secrets

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from imaginary_tpu.obs import debugz as obs_debugz
from imaginary_tpu.obs import events as obs_events
from imaginary_tpu.obs import histogram as obs_hist
from imaginary_tpu.obs import trace as obs_trace
from imaginary_tpu.web.config import ServerOptions
from tests.conftest import fixture_bytes


@pytest.fixture(scope="module", autouse=True)
def _fixtures(testdata):
    return testdata


def run(options, fn, origin_handler=None, log_stream=None):
    """test_cache.py's harness: fn(client, origin_url, app) against a
    fresh app; optional captured log stream (access log + wide events)."""

    async def runner():
        from imaginary_tpu.web.app import create_app

        origin_url = None
        origin = None
        if origin_handler is not None:
            oapp = web.Application()
            oapp.router.add_route("*", "/{tail:.*}", origin_handler)
            origin = TestServer(oapp)
            await origin.start_server()
            origin_url = f"http://127.0.0.1:{origin.port}"

        app = create_app(options, log_stream=log_stream or io.StringIO())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await fn(client, origin_url, app)
        finally:
            await client.close()
            if origin is not None:
                await origin.close()

    asyncio.run(runner())


def jpg() -> bytes:
    return fixture_bytes("imaginary.jpg")


# --- trace unit behavior ------------------------------------------------------

class TestTraceUnit:
    def test_traceparent_inbound_parsed(self):
        tid, sid = secrets.token_hex(16), secrets.token_hex(8)
        tr = obs_trace.RequestTrace("rid", f"00-{tid}-{sid}-01")
        assert tr.trace_id == tid
        assert tr.parent_span_id == sid
        assert tr.traceparent().startswith(f"00-{tid}-")
        assert tr.traceparent().endswith("-01")

    def test_malformed_traceparent_starts_fresh_trace(self):
        for bad in ("", "garbage", "00-xyz-abc-01", "00-" + "0" * 31 + "-" +
                    "0" * 16 + "-01"):
            tr = obs_trace.RequestTrace("rid", bad)
            assert re.fullmatch(r"[0-9a-f]{32}", tr.trace_id)
            assert tr.parent_span_id == ""

    def test_outbound_traceparent_same_trace_new_span(self):
        tr = obs_trace.RequestTrace("rid")
        a, b = tr.outbound_traceparent(), tr.outbound_traceparent()
        assert a != b
        assert a.split("-")[1] == b.split("-")[1] == tr.trace_id

    def test_sanitize_request_id(self):
        assert obs_trace.sanitize_request_id("abc-123_X.y") == "abc-123_X.y"
        assert obs_trace.sanitize_request_id("") == ""
        assert obs_trace.sanitize_request_id("evil\nheader: x") == ""
        assert obs_trace.sanitize_request_id("x" * 200) == ""

    def test_server_timing_aggregates_repeated_spans(self):
        tr = obs_trace.RequestTrace("rid")
        tr.add_span("decode", 2.0)
        tr.add_span("decode", 3.0)
        tr.add_span("encode", 1.5)
        st = tr.server_timing()
        assert "decode;dur=5.00" in st
        assert "encode;dur=1.50" in st

    def test_span_context_manager_needs_active_trace(self):
        # no active trace: pure no-op, no error
        with obs_trace.span("x"):
            pass
        tr = obs_trace.RequestTrace("rid")
        token = obs_trace.activate(tr)
        try:
            with obs_trace.span("work"):
                pass
        finally:
            obs_trace.deactivate(token)
        assert [s.name for s in tr.spans] == ["work"]

    def test_disabled_trace_records_nothing(self):
        tr = obs_trace.RequestTrace("rid", enabled=False)
        tr.add_span("decode", 2.0)
        tr.annotate(op="resize")
        assert tr.spans == [] and tr.fields == {}


# --- histogram unit behavior --------------------------------------------------

class TestHistogramUnit:
    def test_bucket_monotonicity_and_sum_count(self):
        h = obs_hist.Histogram(buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0, 0.05):
            h.observe(v)
        cumulative, total_sum, total_count = h.snapshot()
        assert cumulative == [1, 3, 4, 5]  # nondecreasing, +Inf == count
        assert total_count == 5
        assert abs(total_sum - 5.605) < 1e-9
        assert all(a <= b for a, b in zip(cumulative, cumulative[1:]))

    def test_boundary_value_lands_in_its_le_bucket(self):
        h = obs_hist.Histogram(buckets=(0.1, 1.0))
        h.observe(0.1)  # le="0.1" is INCLUSIVE (Prometheus semantics)
        cumulative, _, _ = h.snapshot()
        assert cumulative[0] == 1

    def test_label_escaping(self):
        assert obs_hist.escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'

    def test_vec_series_bound(self):
        vec = obs_hist.CounterVec(("k",))
        for i in range(obs_hist._MAX_SERIES + 10):
            vec.inc((f"v{i}",))
        assert len(vec.items()) <= obs_hist._MAX_SERIES + 1  # + overflow


# --- request identity over HTTP ----------------------------------------------

class TestRequestIdentity:
    def test_request_id_generated_on_every_response(self):
        async def fn(client, _origin, _app):
            for path in ("/health", "/metrics", "/bogus-route"):
                res = await client.get(path)
                rid = res.headers.get("X-Request-ID")
                assert rid and re.fullmatch(r"[0-9a-f]{32}", rid)

        run(ServerOptions(), fn)

    def test_inbound_request_id_passthrough(self):
        async def fn(client, _origin, _app):
            res = await client.get("/health",
                                   headers={"X-Request-ID": "my-id-123"})
            assert res.headers["X-Request-ID"] == "my-id-123"
            # hostile ids are regenerated, not echoed
            res = await client.get("/health",
                                   headers={"X-Request-ID": "x y\tz"})
            assert re.fullmatch(r"[0-9a-f]{32}",
                                res.headers["X-Request-ID"])

        run(ServerOptions(), fn)

    def test_outbound_fetch_forwards_trace_headers(self):
        seen = []

        async def origin(request):
            seen.append(dict(request.headers))
            return web.Response(body=jpg(), content_type="image/jpeg")

        tid = secrets.token_hex(16)

        async def fn(client, origin_url, _app):
            res = await client.get(
                f"/resize?width=100&url={origin_url}/img.jpg",
                headers={"traceparent": f"00-{tid}-{'ab' * 8}-01",
                         "X-Request-ID": "req-42"},
            )
            assert res.status == 200
            assert res.headers["X-Request-ID"] == "req-42"
            assert len(seen) == 1
            h = seen[0]
            assert h["X-Request-ID"] == "req-42"
            # same trace continues; the hop gets its own child span id
            parts = h["traceparent"].split("-")
            assert parts[1] == tid and parts[2] != "ab" * 8

        run(ServerOptions(enable_url_source=True), fn, origin_handler=origin)

    def test_trace_headers_do_not_partition_source_cache(self):
        hits = [0]

        async def origin(request):
            hits[0] += 1
            return web.Response(body=jpg(), content_type="image/jpeg")

        async def fn(client, origin_url, app):
            for _ in range(3):  # unique traceparent per request
                res = await client.get(
                    f"/resize?width=100&url={origin_url}/img.jpg")
                assert res.status == 200
            assert hits[0] == 1  # origin fetched once despite 3 traces
            assert app["service"].caches.stats.source_hits == 2

        run(ServerOptions(enable_url_source=True, cache_source_ttl=60.0),
            fn, origin_handler=origin)


# --- Server-Timing ------------------------------------------------------------

class TestServerTiming:
    def test_image_response_carries_stage_timings(self):
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            st = res.headers.get("Server-Timing", "")
            for name in ("fetch", "decode", "execute", "encode", "total"):
                assert re.search(rf"{name};dur=\d+(\.\d+)?", st), (name, st)

        run(ServerOptions(), fn)

    def test_device_path_stage_splits_reach_the_header(self):
        # PR 9/15 promised batch_form / dispatch_wait / drain stage
        # splits; the collector threads carry no trace contextvar, so
        # only the executor's direct per-item add_span stamps can get
        # them here (ISSUE 18 satellite)
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            st = res.headers.get("Server-Timing", "")
            for name in ("batch_form", "dispatch_wait", "drain"):
                assert re.search(rf"{name};dur=\d+(\.\d+)?", st), (name, st)

        run(ServerOptions(), fn)

    def test_tracing_disabled_still_sets_request_id(self):
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            assert "Server-Timing" not in res.headers
            assert re.fullmatch(r"[0-9a-f]{32}",
                                res.headers["X-Request-ID"])

        run(ServerOptions(trace_enabled=False), fn)


# --- wide events --------------------------------------------------------------

def _wide_events(stream: io.StringIO) -> list:
    return [json.loads(ln) for ln in stream.getvalue().splitlines()
            if ln.startswith("{")]


class TestWideEvents:
    def test_schema_and_5xx_correlation(self):
        stream = io.StringIO()

        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            rid_ok = res.headers["X-Request-ID"]
            res = await client.post("/resize?width=100", data=b"notanimage")
            rid_bad = res.headers["X-Request-ID"]
            assert res.status >= 400

            events = _wide_events(stream)
            assert len(events) == 2
            ok = next(e for e in events if e["status"] == 200)
            for field in ("ts", "request_id", "trace_id", "span_id",
                          "method", "route", "path", "status", "remote",
                          "duration_ms", "bytes_in", "bytes_out", "op",
                          "plan", "cache", "placement", "spans"):
                assert field in ok, field
            assert ok["request_id"] == rid_ok
            assert ok["op"] == "resize"
            assert ok["cache"] == "off"
            assert ok["placement"] in ("device", "host")
            assert ok["bytes_in"] > 0 and ok["bytes_out"] > 0
            names = [s["name"] for s in ok["spans"]]
            assert "decode" in names and "encode" in names
            assert all(s["dur_ms"] >= 0 and "start_ms" in s
                       for s in ok["spans"])
            # the error event still carries the response's id (the 5xx
            # correlation contract; 4xx pins the same code path)
            bad = next(e for e in events if e["status"] >= 400)
            assert bad["request_id"] == rid_bad

        run(ServerOptions(wide_events=True), fn, log_stream=stream)

    def test_access_log_line_and_wide_event_share_id(self):
        stream = io.StringIO()

        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            rid = res.headers["X-Request-ID"]
            text = stream.getvalue()
            log_line = next(ln for ln in text.splitlines()
                            if not ln.startswith("{"))
            assert log_line.rstrip().endswith(rid)
            assert _wide_events(stream)[0]["request_id"] == rid

        run(ServerOptions(wide_events=True), fn, log_stream=stream)

    def test_cache_and_coalesce_outcomes_recorded(self):
        stream = io.StringIO()

        async def fn(client, _origin, _app):
            for _ in range(2):
                res = await client.post("/resize?width=100", data=jpg())
                assert res.status == 200
            events = _wide_events(stream)
            assert events[0]["cache"] == "result_miss"
            assert events[1]["cache"] == "result_hit"

        run(ServerOptions(wide_events=True, cache_result_mb=16.0), fn,
            log_stream=stream)


# --- strict exposition-format parser -----------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? "
    r"(-?(?:\d+\.?\d*(?:[eE][+-]?\d+)?|\+?Inf|NaN))$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\["\\n])*)"')


def parse_exposition_strict(text: str):
    """Parse Prometheus text format 0.0.4 the way a scraper does; raise
    AssertionError on any violation: samples before their family's TYPE,
    duplicate TYPE, malformed labels, duplicate series."""
    types: dict = {}
    samples: list = []
    seen_series: set = set()
    assert text.endswith("\n")
    for ln in text.splitlines():
        assert ln.strip(), "blank line in exposition"
        if ln.startswith("# TYPE "):
            _, _, rest = ln.partition("# TYPE ")
            name, mtype = rest.split(" ", 1)
            assert mtype in ("counter", "gauge", "histogram", "summary",
                             "untyped"), ln
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = mtype
        elif ln.startswith("# HELP "):
            continue
        elif ln.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(ln)
            assert m, f"malformed sample line: {ln!r}"
            name, raw_labels, value = m.group(1), m.group(2), m.group(3)
            labels = {}
            if raw_labels:
                consumed = 0
                for lm in _LABEL_RE.finditer(raw_labels):
                    labels[lm.group(1)] = lm.group(2)
                    consumed += len(lm.group(0))
                stripped = raw_labels.replace(",", "")
                assert consumed == len(stripped), \
                    f"unparseable labels: {raw_labels!r}"
            family = name
            for suffix in ("_bucket", "_sum", "_count"):
                base = name[: -len(suffix)] if name.endswith(suffix) else None
                if base and types.get(base) == "histogram":
                    family = base
            assert family in types, f"sample before TYPE: {ln!r}"
            series = (name, tuple(sorted(labels.items())))
            assert series not in seen_series, f"duplicate series: {series}"
            seen_series.add(series)
            samples.append((name, labels, float(value.replace("Inf", "inf"))))
    return types, samples


def check_histograms(types, samples):
    """Every histogram family: buckets cumulative-monotone in le order,
    +Inf bucket == _count, _sum present."""
    for family, mtype in types.items():
        if mtype != "histogram":
            continue
        groups: dict = {}
        for name, labels, value in samples:
            if name == f"{family}_bucket":
                rest = tuple(sorted((k, v) for k, v in labels.items()
                                    if k != "le"))
                groups.setdefault(rest, []).append(
                    (float(labels["le"].replace("+Inf", "inf")), value))
        assert groups, f"histogram {family} emitted no buckets"
        counts = {tuple(sorted(labels.items())): value
                  for name, labels, value in samples
                  if name == f"{family}_count"}
        sums = {tuple(sorted(labels.items())): value
                for name, labels, value in samples
                if name == f"{family}_sum"}
        for rest, buckets in groups.items():
            buckets.sort()
            values = [v for _, v in buckets]
            assert all(a <= b for a, b in zip(values, values[1:])), \
                f"{family}{dict(rest)}: non-monotone buckets {values}"
            assert buckets[-1][0] == float("inf")
            assert rest in counts and counts[rest] == buckets[-1][1], \
                f"{family}{dict(rest)}: +Inf bucket != _count"
            assert rest in sums


class TestMetricsExposition:
    def test_strict_parse_and_histogram_consistency(self):
        async def fn(client, _origin, _app):
            for _ in range(3):
                res = await client.post("/resize?width=100", data=jpg())
                assert res.status == 200
            await client.get("/bogus")  # a 404 for the RED counters
            res = await client.get("/metrics")
            assert res.status == 200
            text = await res.text()
            types, samples = parse_exposition_strict(text)
            check_histograms(types, samples)
            names = {n for n, _, _ in samples}
            assert "imaginary_tpu_request_duration_seconds_bucket" in names
            assert "imaginary_tpu_stage_duration_seconds_bucket" in names
            assert "imaginary_tpu_requests_total" in names
            # RED counters: route x status class, bounded labels
            red = [(labels, v) for n, labels, v in samples
                   if n == "imaginary_tpu_requests_total"]
            assert any(labels.get("code") == "2xx" for labels, _ in red)
            assert any(labels.get("code") == "4xx"
                       and labels.get("route") == "unmatched"
                       for labels, _ in red)
            # stage histogram covers the pipeline stages
            stages = {labels["stage"] for n, labels, _ in samples
                      if n == "imaginary_tpu_stage_duration_seconds_bucket"}
            assert {"decode", "encode", "total"} <= stages
            # cache/executor counters are TYPEd as counters, gauges as gauges
            assert types["imaginary_tpu_executor_items"] == "counter"
            assert types["imaginary_tpu_executor_queue_depth"] == "gauge"

        run(ServerOptions(), fn)

    def test_label_values_escaped(self):
        from imaginary_tpu.web.metrics import render_metrics

        text = render_metrics({
            "backend": 'we"ird\\backend',
            "stageTimesMs": {
                'de"code': {"count": 3, "mean_ms": 1.0, "p50_ms": 1.0,
                            "p99_ms": 2.0},
            },
        })
        types, samples = parse_exposition_strict(text)
        backend = next(labels for n, labels, _ in samples
                       if n == "imaginary_tpu_backend_info")
        assert backend["backend"] == 'we\\"ird\\\\backend'

    def test_lane_families_render_strict(self):
        from imaginary_tpu.web.metrics import render_metrics

        text = render_metrics({
            "executor": {
                "items": 24,
                "batches": 6,
                "mesh_generation": 2,
                "lanes": [
                    {"lane": 0, "queued": 3, "inflight": 1, "owed": 4,
                     "ewma_ms": 2.5, "dispatches": 6, "active": True},
                    {"lane": 1, "queued": 0, "inflight": 0, "owed": 0,
                     "ewma_ms": 1.0, "dispatches": 9, "active": False},
                ],
                "wire_bytes_by_device": {
                    "h2d": {"0": 4096, "1": 2048},
                    "d2h": {"0": 1024},
                },
            },
        })
        types, samples = parse_exposition_strict(text)
        assert types["imaginary_tpu_lane_queued"] == "gauge"
        assert types["imaginary_tpu_lane_inflight"] == "gauge"
        assert types["imaginary_tpu_lane_dispatches_total"] == "counter"
        assert types["imaginary_tpu_executor_mesh_generation"] == "gauge"
        assert types["imaginary_tpu_wire_device_bytes_total"] == "counter"
        queued = {labels["lane"]: v for n, labels, v in samples
                  if n == "imaginary_tpu_lane_queued"}
        assert queued == {"0": 3.0, "1": 0.0}
        disp = {labels["lane"]: v for n, labels, v in samples
                if n == "imaginary_tpu_lane_dispatches_total"}
        assert disp == {"0": 6.0, "1": 9.0}
        wire = {(labels["direction"], labels["device"]): v
                for n, labels, v in samples
                if n == "imaginary_tpu_wire_device_bytes_total"}
        assert wire[("h2d", "0")] == 4096.0
        assert wire[("h2d", "1")] == 2048.0
        assert wire[("d2h", "0")] == 1024.0

    def test_lane_families_absent_when_policy_off(self):
        from imaginary_tpu.web.metrics import render_metrics

        # mesh_policy off: the executor block carries no lanes /
        # wire_bytes_by_device keys, and no lane family may leak out
        text = render_metrics({"executor": {"items": 24, "batches": 6}})
        parse_exposition_strict(text)
        assert "imaginary_tpu_lane_" not in text
        assert "imaginary_tpu_wire_device_bytes_total" not in text


# --- /debugz ------------------------------------------------------------------

class TestDebugz:
    def test_gated_off_by_default(self):
        async def fn(client, _origin, _app):
            res = await client.get("/debugz")
            assert res.status == 404
            res = await client.get("/debugz/profile?seconds=1")
            assert res.status == 404

        run(ServerOptions(), fn)

    def test_enabled_payload_shape(self):
        async def fn(client, _origin, _app):
            await client.post("/resize?width=100", data=jpg())
            res = await client.get("/debugz")
            assert res.status == 200
            body = await res.json()
            for key in ("pid", "threads", "tasks", "slowest_requests",
                        "executor", "executor_counters", "host_pool",
                        "cache"):
                assert key in body, key
            assert isinstance(body["tasks"], list)
            ex = body["executor"]
            for key in ("queue_depth", "inflight_groups", "breaker_open",
                        "owed_ms", "host_gate_free_permits"):
                assert key in ex, key
            assert body["host_pool"]["workers"] >= 1
            # slow-request exemplars carry the full span timeline
            slow = body["slowest_requests"]
            assert slow and "spans" in slow[0] and "request_id" in slow[0]

        obs_debugz.SLOW.clear()
        run(ServerOptions(enable_debug=True), fn)

    def test_api_key_guards_debugz_when_set(self):
        async def fn(client, _origin, _app):
            res = await client.get("/debugz")
            assert res.status == 401
            res = await client.get("/debugz", headers={"API-Key": "sekrit"})
            assert res.status == 200

        run(ServerOptions(enable_debug=True, api_key="sekrit"), fn)

    def test_profile_requires_destination(self, monkeypatch):
        monkeypatch.delenv("IMAGINARY_TPU_PROFILE_DIR", raising=False)

        async def fn(client, _origin, _app):
            res = await client.get("/debugz/profile?seconds=0.1")
            assert res.status == 400
            body = await res.json()
            assert "IMAGINARY_TPU_PROFILE_DIR" in body["error"]

        run(ServerOptions(enable_debug=True), fn)

    def test_profile_dir_query_param_overrides_env(self, monkeypatch,
                                                   tmp_path):
        # the no-restart path: a process booted WITHOUT the env var can
        # still name a destination per capture
        monkeypatch.delenv("IMAGINARY_TPU_PROFILE_DIR", raising=False)

        async def fn(client, _origin, _app):
            res = await client.get(
                "/debugz/profile", params={"seconds": "0.05",
                                           "dir": str(tmp_path)})
            assert res.status == 200
            body = await res.json()
            assert body["profile_dir"] == str(tmp_path)
            import os

            assert any(os.scandir(str(tmp_path)))

        run(ServerOptions(enable_debug=True), fn)

    def test_profile_one_shot_capture(self, monkeypatch, tmp_path):
        monkeypatch.setenv("IMAGINARY_TPU_PROFILE_DIR", str(tmp_path))

        async def fn(client, _origin, _app):
            res = await client.get("/debugz/profile?seconds=0.05")
            assert res.status == 200
            body = await res.json()
            assert body["profile_dir"] == str(tmp_path)
            # jax wrote a trace under the dir and the session is closed
            # (a second capture can start)
            import os

            assert any(os.scandir(str(tmp_path)))
            from imaginary_tpu.engine import timing

            assert not timing.profiler_active()

        run(ServerOptions(enable_debug=True), fn)

    def test_profile_bad_seconds_rejected(self, monkeypatch, tmp_path):
        monkeypatch.setenv("IMAGINARY_TPU_PROFILE_DIR", str(tmp_path))

        async def fn(client, _origin, _app):
            res = await client.get("/debugz/profile?seconds=nope")
            assert res.status == 400

        run(ServerOptions(enable_debug=True), fn)


# --- slow-request ring --------------------------------------------------------

class TestSlowRing:
    def test_slowest_ordering_and_bound(self):
        ring = obs_debugz.SlowRing(keep=4)
        for i, dur in enumerate([5.0, 50.0, 1.0, 20.0, 9.0]):
            ring.note({"request_id": str(i), "duration_ms": dur})
        top = ring.slowest(2)
        # the oldest entry (5.0) aged out of the keep=4 window
        assert [e["duration_ms"] for e in top] == [50.0, 20.0]
        assert len(ring.slowest(100)) == 4


# --- tail-sampled wide events (ISSUE 13) --------------------------------------

class TestClassify:
    """classify() precedence: the most actionable signal wins."""

    def test_interesting_tail_always_kept(self):
        cases = [
            ({"status": 503}, "shed"),
            ({"status": 504}, "deadline"),
            ({"status": 418}, "error"),
            ({"status": 200, "hedge": "won"}, "hedged"),
            ({"status": 200,
              "placement_attempts": ["device:0:error", "host_spill"]},
             "placement"),
            ({"status": 200,
              "placement_attempts": ["device:quarantined", "host_spill"]},
             "placement"),
            ({"status": 200, "fenced_publish": True}, "fenced"),
            ({"status": 200, "duration_ms": 1500.0}, "slow"),
        ]
        for event, want in cases:
            # sample=0: only the always-keep rules can save these events
            assert obs_events.classify(event, sample=0.0) == want, event

    def test_precedence_shed_beats_error_and_slow(self):
        ev = {"status": 503, "duration_ms": 9000.0}
        assert obs_events.classify(ev, sample=0.0) == "shed"
        ev = {"status": 200, "hedge": "lost", "duration_ms": 9000.0}
        assert obs_events.classify(ev, sample=0.0) == "hedged"

    def test_boring_event_sampling(self):
        boring = {"status": 200, "duration_ms": 3.0,
                  "placement_attempts": ["device:0"]}
        # default sample=1.0: everything kept (legacy parity)
        assert obs_events.classify(boring) == "random"
        assert obs_events.classify(boring, sample=0.0) == "unsampled"
        # injectable roll pins the probabilistic branch deterministically
        assert obs_events.classify(boring, sample=0.5,
                                   roll=lambda: 0.4) == "random"
        assert obs_events.classify(boring, sample=0.5,
                                   roll=lambda: 0.6) == "unsampled"

    def test_every_verdict_is_registered(self):
        # the ITPU010 contract from the python side
        for v in ("shed", "deadline", "error", "hedged", "placement",
                  "fenced", "slow", "random", "unsampled"):
            assert v in obs_events.SAMPLED_REASONS


class TestTailSampling:
    def test_sample_zero_keeps_only_the_interesting_tail(self):
        stream = io.StringIO()

        async def fn(client, _origin, _app):
            for _ in range(5):
                res = await client.post("/resize?width=100", data=jpg())
                assert res.status == 200
            res = await client.post("/resize?width=100", data=b"nope")
            assert res.status >= 400

            events = _wide_events(stream)
            # the five boring 200s were dropped; the error survived
            assert len(events) == 1
            assert events[0]["status"] >= 400
            assert events[0]["sampled_reason"] == "error"

        obs_debugz.SLOW.clear()
        run(ServerOptions(wide_events=True, wide_events_sample=0.0), fn,
            log_stream=stream)

    def test_default_sample_emits_everything_with_stamps(self):
        stream = io.StringIO()

        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            events = _wide_events(stream)
            assert len(events) == 1
            ev = events[0]
            assert ev["sampled_reason"] == "random"
            # fleet attribution stamps (satellite a): a standalone
            # process is worker 0 at epoch 0
            assert ev["worker"] == 0
            assert ev["epoch"] == 0

        run(ServerOptions(wide_events=True), fn, log_stream=stream)

    def test_slow_ring_carries_verdict_even_for_unsampled(self):
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200

        obs_debugz.SLOW.clear()
        run(ServerOptions(wide_events=True, wide_events_sample=0.0), fn)
        entries = obs_debugz.SLOW.slowest(10)
        assert entries, "slow ring must record unsampled requests too"
        ev = entries[0]
        assert ev["sampled_reason"] == "unsampled"
        assert ev["worker"] == 0 and ev["epoch"] == 0


# --- exemplars (ISSUE 13) -----------------------------------------------------

class TestExemplars:
    def test_histogram_stores_and_renders_exemplar(self):
        reg = obs_hist.Registry()
        h = reg.histogram("ex_seconds", "help text", (0.1, 1.0))
        h.observe(0.05, exemplar=("req-1", "trace-1"))
        h.observe(0.5)
        plain = "\n".join(reg.render_lines()) + "\n"
        assert " # {" not in plain  # default render stays strict 0.0.4
        parse_exposition_strict(plain)
        rich = "\n".join(reg.render_lines(exemplars=True)) + "\n"
        assert 'trace_id="trace-1"' in rich
        assert 'request_id="req-1"' in rich
        # only the bucket that saw the exemplar carries one
        ex_lines = [ln for ln in rich.splitlines() if " # {" in ln]
        assert len(ex_lines) == 1 and 'le="0.1"' in ex_lines[0]

    def test_metrics_endpoint_exemplar_query(self):
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            rid = res.headers["X-Request-ID"]
            # plain scrape: byte-strict, no exemplar clause
            plain = await (await client.get("/metrics")).text()
            assert " # {" not in plain
            parse_exposition_strict(plain)
            # opted-in scrape: the request-duration bucket names the
            # exact request that landed in it
            rich = await (await client.get("/metrics?exemplars=1")).text()
            assert f'request_id="{rid}"' in rich
            # stripping the exemplar clause restores a strict body
            stripped = "\n".join(
                ln.split(" # {")[0] for ln in rich.splitlines()) + "\n"
            parse_exposition_strict(stripped)

        run(ServerOptions(), fn)


# --- SLO burn rates (ISSUE 13) ------------------------------------------------

class TestSloEngine:
    def test_load_config_inline_file_and_errors(self, tmp_path):
        from imaginary_tpu.obs import slo as slo_mod

        objectives = slo_mod.load_config(
            '{"/resize": {"latency_ms": 250, "latency_target": 0.99,'
            ' "availability": 0.999}}')
        assert objectives["/resize"].latency_ms == 250.0
        p = tmp_path / "slo.json"
        p.write_text('{"*": {"availability": 0.99}}')
        objectives = slo_mod.load_config(str(p))
        assert objectives["*"].availability == 0.99
        # defaults fill unspecified fields
        assert objectives["*"].latency_ms == 1000.0
        for bad in ("{nope", '{"*": 5}', '{"*": {"availability": 1.5}}',
                    '{"*": {"latency_ms": -1}}', str(tmp_path / "missing")):
            with pytest.raises(ValueError):
                slo_mod.load_config(bad)

    def test_burn_rate_math(self):
        from imaginary_tpu.obs import slo as slo_mod

        t = [1000.0]
        eng = slo_mod.SloEngine(
            slo_mod.load_config(
                '{"*": {"availability": 0.999, "latency_ms": 100,'
                ' "latency_target": 0.99}}'),
            clock=lambda: t[0])
        for _ in range(99):
            eng.observe("/resize", 200, 0.01)
        eng.observe("/resize", 500, 0.01)
        snap = eng.snapshot()
        r = snap["routes"]["/resize"]
        # 1 bad / 100 total against a 0.1% budget => burn 10x
        assert r["availability"]["burn_5m"] == pytest.approx(10.0)
        assert r["availability"]["bad_5m"] == 1
        assert r["availability"]["budget_remaining"] == 0.0
        # no over-latency requests: latency burn 0, budget intact
        assert r["latency"]["burn_5m"] == 0.0
        assert r["latency"]["budget_remaining"] == 1.0

    def test_sliding_window_forgets_old_badness(self):
        from imaginary_tpu.obs import slo as slo_mod

        t = [1000.0]
        eng = slo_mod.SloEngine(
            slo_mod.load_config('{"*": {"availability": 0.999}}'),
            clock=lambda: t[0])
        eng.observe("/x", 500, 0.01)  # ring snapshot at t=1000
        for _ in range(9):
            eng.observe("/x", 200, 0.01)
        t[0] += 6.0
        eng.observe("/x", 200, 0.01)  # second ring snapshot
        t[0] += 400.0  # the bad minute is now outside the 5m window...
        eng.observe("/x", 200, 0.01)
        snap = eng.snapshot()["routes"]["/x"]["availability"]
        assert snap["bad_5m"] == 0
        assert snap["burn_5m"] == 0.0
        # ...but still inside the 1h window
        assert snap["bad_1h"] == 1

    def test_unmatched_route_without_catchall_ignored(self):
        from imaginary_tpu.obs import slo as slo_mod

        eng = slo_mod.SloEngine(slo_mod.load_config(
            '{"/resize": {"availability": 0.999}}'))
        eng.observe("/other", 500, 0.01)
        assert eng.snapshot()["routes"] == {}

    def test_infra_routes_excluded_from_catchall(self):
        # the supervisor's liveness probes land ~0.5 rps of fast 200s
        # per worker on /health; a '*' objective must not let that
        # traffic dilute burn rates for real routes
        from imaginary_tpu.obs import slo as slo_mod

        eng = slo_mod.SloEngine(
            slo_mod.load_config('{"*": {"availability": 0.999}}'))
        for route in ("/health", "/metrics", "/debugz",
                      "/api/health", "/api/metrics"):
            eng.observe(route, 200, 0.001)
        eng.observe("/resize", 500, 0.01)
        routes = eng.snapshot()["routes"]
        assert set(routes) == {"/resize"}
        assert routes["/resize"]["availability"]["bad_5m"] == 1
        assert routes["/resize"]["availability"]["total_5m"] == 1

    def test_explicit_infra_objective_still_applies(self):
        from imaginary_tpu.obs import slo as slo_mod

        eng = slo_mod.SloEngine(slo_mod.load_config(
            '{"/health": {"availability": 0.999}}'))
        eng.observe("/health", 200, 0.001)
        assert eng.snapshot()["routes"]["/health"]["total"] == 1

    def test_from_options_parity_off(self):
        from imaginary_tpu.obs import slo as slo_mod

        assert slo_mod.from_options(ServerOptions()) is None
        assert slo_mod.from_options(
            ServerOptions(slo_config="  ")) is None


class TestSloSurfaces:
    SLO = '{"*": {"latency_ms": 500, "latency_target": 0.99, "availability": 0.999}}'

    def test_health_metrics_and_debugz_blocks(self):
        async def fn(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            health = await (await client.get("/health")).json()
            assert "slo" in health
            route = health["slo"]["routes"]["/resize"]
            assert route["total"] >= 1
            assert "burn_5m" in route["availability"]
            text = await (await client.get("/metrics")).text()
            types, samples = parse_exposition_strict(text)
            assert types["imaginary_tpu_slo_burn_rate"] == "gauge"
            burn = [(labels, v) for n, labels, v in samples
                    if n == "imaginary_tpu_slo_burn_rate"]
            assert {labels["slo"] for labels, _ in burn} \
                == {"availability", "latency"}
            assert {labels["window"] for labels, _ in burn} == {"5m", "1h"}
            assert any(n == "imaginary_tpu_slo_error_budget_remaining"
                       for n, _l, _v in samples)
            debug = await (await client.get("/debugz")).json()
            assert "slo" in debug

        run(ServerOptions(enable_debug=True, slo_config=self.SLO), fn)

    def test_parity_no_slo_block_without_config(self):
        async def fn(client, _origin, _app):
            await client.post("/resize?width=100", data=jpg())
            health = await (await client.get("/health")).json()
            assert "slo" not in health
            text = await (await client.get("/metrics")).text()
            assert "imaginary_tpu_slo_" not in text


# --- cost attribution & capacity plane (ISSUE 18) -----------------------------

class TestCostPlaneUnit:
    def test_parse_windows(self):
        from imaginary_tpu.obs import cost as cost_mod

        assert cost_mod.parse_windows("10s,1m,5m") == (
            ("10s", 10), ("1m", 60), ("5m", 300))
        for bad in ("", " , ", "10x", "10s,5s", "0s", "120m",
                    "1s,2s,3s,4s,5s,6s,7s"):
            with pytest.raises(ValueError):
                cost_mod.parse_windows(bad)

    def test_space_saving_fold_is_deterministic(self):
        from imaginary_tpu.obs.cost import SpaceSaving

        sk = SpaceSaving(2)
        assert sk.offer("a") is None
        assert sk.offer("a") is None
        assert sk.offer("b") is None
        # full table: the newcomer evicts the minimum entry — ties break
        # by (count, name), so replay order alone decides nothing
        assert sk.offer("c") == "b"
        assert sk.tracked("a") and sk.tracked("c") and not sk.tracked("b")
        # the newcomer inherited the victim's count floor
        assert dict(sk.top())["c"] == 2.0

    def test_booking_windows_and_topz_ranking(self):
        from imaginary_tpu.obs.cost import CostPlane

        t = [1000.0]
        plane = CostPlane(topk=4, windows="10s,1m", clock=lambda: t[0])
        for _ in range(3):
            plane.book("hog", "batch", "/process", "process",
                       device_ms=100.0, wire_bytes=5e6)
        for _ in range(2):
            plane.book("inter", "interactive", "/resize", "resize",
                       device_ms=1.0, host_ms=2.0, wire_bytes=1e4)
        snap = plane.snapshot()
        assert snap["booked"] == 5
        assert set(snap["windows"]) == {"10s", "1m"}
        assert snap["windows"]["10s"]["requests"] == 5
        assert snap["windows"]["10s"]["device_ms"] == pytest.approx(302.0)
        assert snap["tenants"]["hog"]["wire_bytes"] == 15_000_000
        topz = plane.topz()
        ranked = topz["windows"]["10s"]["by_chip_ms"]
        assert [r["tenant"] for r in ranked] == ["hog", "inter"]
        assert ranked[0]["chip_ms"] == pytest.approx(300.0)
        # host-ms ranking only lists tenants that actually burned host time
        assert [r["tenant"] for r in topz["windows"]["10s"]["by_host_ms"]] \
            == ["inter"]
        # 11 seconds later the 10s window has forgotten, the 1m one not
        t[0] += 11.0
        plane.book("late", "-", "/resize", "resize", device_ms=7.0)
        snap = plane.snapshot()
        assert snap["windows"]["10s"]["requests"] == 1
        assert snap["windows"]["10s"]["device_ms"] == pytest.approx(7.0)
        assert snap["windows"]["1m"]["requests"] == 6

    def test_topk_folds_into_other(self):
        from imaginary_tpu.obs.cost import OTHER, CostPlane

        t = [1000.0]
        plane = CostPlane(topk=2, windows="10s", clock=lambda: t[0])
        plane.book("a", "-", "/x", "x", device_ms=5.0)
        plane.book("a", "-", "/x", "x", device_ms=5.0)
        plane.book("b", "-", "/x", "x", device_ms=5.0)
        plane.book("c", "-", "/x", "x", device_ms=5.0)  # evicts b
        snap = plane.snapshot()
        assert snap["folds"] == 1
        assert set(snap["tenants"]) == {"a", "c", OTHER}
        # b's cumulative vector folded into `other`
        assert snap["tenants"][OTHER]["device_ms"] == pytest.approx(5.0)
        assert plane.normalize("tenant", "b") == OTHER
        assert plane.normalize("tenant", "a") == "a"
        # route/qos_class kinds pass through; unknown kinds raise
        assert plane.normalize("route", "/whatever") == "/whatever"
        with pytest.raises(ValueError):
            plane.normalize("flavor", "x")

    def test_seeded_tenants_never_report_other(self):
        from imaginary_tpu.obs.cost import CostPlane

        plane = CostPlane(topk=4, windows="10s")
        plane.seed_tenants(("gold", "bronze"))
        assert plane.normalize("tenant", "gold") == "gold"
        assert plane.normalize("tenant", "stranger") == "other"

    def test_should_book_skips_infra_routes(self):
        from imaginary_tpu.obs.cost import CostPlane

        plane = CostPlane()
        for route in ("/", "/health", "/metrics", "/topz", "/fleetz",
                      "/api/health", "/debugz"):
            assert not plane.should_book(route), route
        for route in ("/resize", "/process", "/api/crop"):
            assert plane.should_book(route), route

    def test_advisor_unknown_without_traffic(self):
        from imaginary_tpu.obs.cost import CostPlane

        plane = CostPlane(windows="10s")
        verdict = plane.advise()
        assert verdict["verdict"] == "unknown"

    def test_advisor_verdict_argmin(self):
        from imaginary_tpu.obs.cost import SERVING_BATCH, CostPlane

        class _Ex:
            _drain_floor_ms = 80.0
            _device_ms_per_mb = 2.0

        t = [1000.0]
        plane = CostPlane(topk=4, windows="10s", clock=lambda: t[0])
        plane.bind(executor=_Ex(), host_view=lambda: (4, 0))
        plane.book("t", "-", "/process", "process",
                   device_ms=20.0, host_ms=1.0, wire_bytes=10e6)
        out = plane.advise()
        # link: 80/16 + 10*2 = 25 ms/req; chip: 20 ms/req; host: 1/4
        assert out["serving_batch"] == SERVING_BATCH
        assert out["link_rate"] == pytest.approx(1000.0 / 25.0)
        assert out["chip_rate"] == pytest.approx(50.0)
        assert out["verdict"] == "link"
        assert out["e2e_rate"] == pytest.approx(40.0)

    def test_from_options_parity_and_install(self):
        from imaginary_tpu.obs import cost as cost_mod

        assert cost_mod.from_options(ServerOptions()) is None
        assert cost_mod.active() is None
        plane = cost_mod.from_options(
            ServerOptions(cost_attribution=True, cost_topk=7))
        try:
            assert plane is not None and plane.topk == 7
            assert cost_mod.active() is plane
            # armed: normalize_label delegates to the plane
            assert cost_mod.normalize_label("tenant", "ghost") == "other"
        finally:
            cost_mod.install(None)
        # disarmed: identity passthrough, but kinds still validated
        assert cost_mod.normalize_label("tenant", "ghost") == "ghost"
        with pytest.raises(ValueError):
            cost_mod.normalize_label("flavor", "x")


class TestCostSurfaces:
    def test_armed_health_metrics_topz_debugz(self):
        async def fn(client, _origin, _app):
            for _ in range(2):
                res = await client.post("/resize?width=100", data=jpg())
                assert res.status == 200
            health = await (await client.get("/health")).json()
            cap = health["capacity"]
            assert cap["booked"] >= 2
            assert set(cap["windows"]) == {"10s", "1m", "5m"}
            assert cap["tenants"]["default"]["requests"] >= 2
            assert "verdict" in cap["bound_by"]
            assert "wait_cum_ms" in cap["utilization"]
            # scrape twice: utilization busy fractions are deltas
            # between snapshots, so the second scrape carries them
            await client.get("/metrics")
            text = await (await client.get("/metrics")).text()
            types, samples = parse_exposition_strict(text)
            names = {n for n, _, _ in samples}
            for field in ("device_ms", "host_ms", "wire_bytes",
                          "copied_bytes", "cache_bytes", "requests"):
                fam = f"imaginary_tpu_cost_{field}_total"
                assert fam in names, fam
                assert types[fam] == "counter"
            assert "imaginary_tpu_cost_folds_total" in names
            assert "imaginary_tpu_cost_booked_total" in names
            assert types["imaginary_tpu_utilization_wait_ms_total"] \
                == "counter"
            assert {labels["kind"] for n, labels, _ in samples
                    if n == "imaginary_tpu_utilization_wait_ms_total"} \
                == {"batch_form", "dispatch_wait", "drain"}
            assert types["imaginary_tpu_utilization_chip_busy"] == "gauge"
            assert "imaginary_tpu_utilization_host_pool" in names
            # every cost family is tenant-labeled with the booked tenant
            reqs = [(labels, v) for n, labels, v in samples
                    if n == "imaginary_tpu_cost_requests_total"]
            assert any(labels.get("tenant") == "default" and v >= 2
                       for labels, v in reqs)
            topz = await client.get("/topz")
            assert topz.status == 200
            body = await topz.json()
            assert body["k"] == 20
            assert body["windows"]["5m"]["totals"]["requests"] >= 2
            ranked = body["windows"]["5m"]["by_chip_ms"]
            assert ranked and ranked[0]["tenant"] == "default"
            debug = await (await client.get("/debugz")).json()
            assert "capacity" in debug

        run(ServerOptions(cost_attribution=True, enable_debug=True), fn)

    def test_off_by_default_parity(self):
        collected = {}

        async def armed(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            collected["armed"] = await res.read()

        async def off(client, _origin, _app):
            res = await client.post("/resize?width=100", data=jpg())
            assert res.status == 200
            collected["off"] = await res.read()
            health = await (await client.get("/health")).json()
            assert "capacity" not in health
            text = await (await client.get("/metrics")).text()
            assert "imaginary_tpu_cost_" not in text
            assert "imaginary_tpu_utilization_" not in text
            topz = await client.get("/topz")
            assert topz.status == 404
            debug = await (await client.get("/debugz")).json()
            assert "capacity" not in debug

        run(ServerOptions(cost_attribution=True), armed)
        run(ServerOptions(enable_debug=True), off)
        # the image path is byte-identical with the plane disarmed
        assert collected["armed"] == collected["off"]

    def test_capacity_render_is_strict_and_normalized(self):
        # synthetic capacity block straight through render_metrics: the
        # exposition stays strict and tenant label values are escaped
        from imaginary_tpu.web.metrics import render_metrics

        text = render_metrics({
            "capacity": {
                "topk": 2, "folds": 3, "booked": 9,
                "windows": {"10s": {"device_ms": 1.0, "requests": 2}},
                "tenants": {
                    'we"ird': {"device_ms": 1.5, "host_ms": 0.0,
                               "wire_bytes": 10, "copied_bytes": 4,
                               "cache_bytes": 0, "requests": 2},
                },
                "utilization": {
                    "age_s": 1.0,
                    "wait_cum_ms": {"batch_form": 1.0, "drain": 2.0},
                    "lanes": {"0": 0.5, "all": 0.1},
                    "chip_busy": 0.3, "host_pool": 0.25, "link": 0.1,
                },
                "bound_by": {"verdict": "chip"},
            },
            "eventLoop": {"lagMsLast": 12.0, "lagMsMax": 80.0,
                          "samples": 5},
        })
        types, samples = parse_exposition_strict(text)
        assert types["imaginary_tpu_cost_device_ms_total"] == "counter"
        tenants = {labels["tenant"] for n, labels, _ in samples
                   if n == "imaginary_tpu_cost_device_ms_total"}
        # the strict parser keeps label values raw: the quote arrived
        # backslash-escaped on the wire, which is the point
        assert tenants == {'we\\"ird'}
        lane = {labels["lane"]: v for n, labels, v in samples
                if n == "imaginary_tpu_utilization_lane_busy"}
        assert lane == {"0": 0.5, "all": 0.1}
        gauges = {n: v for n, _l, v in samples}
        assert gauges["imaginary_tpu_utilization_chip_busy"] == 0.3
        assert gauges["imaginary_tpu_event_loop_lag_last_seconds"] \
            == pytest.approx(0.012)
        assert gauges["imaginary_tpu_event_loop_lag_max_seconds"] \
            == pytest.approx(0.080)


class TestLoopLag:
    def test_probe_samples_and_snapshot(self):
        from imaginary_tpu.obs import looplag

        async def probe():
            task = looplag.start(0.01)
            await asyncio.sleep(0.08)
            looplag.stop(task)

        asyncio.run(probe())
        snap = looplag.snapshot()
        assert snap is not None
        assert snap["samples"] >= 1
        assert snap["lagMsMax"] >= snap["lagMsLast"] >= 0.0
        assert looplag.last_ms() == pytest.approx(
            snap["lagMsLast"], abs=1e-3)

    def test_health_carries_event_loop_block(self):
        async def fn(client, _origin, _app):
            # the probe runs at 20 Hz from app startup; wait a few periods
            await asyncio.sleep(0.3)
            health = await (await client.get("/health")).json()
            assert health["eventLoop"]["samples"] >= 1

        run(ServerOptions(), fn)


class TestFleetCapacityMerge:
    def test_fleetz_merges_capacity_across_workers(self):
        from imaginary_tpu.obs.aggregate import build_fleetz

        def health(verdict, device_ms, folds=0):
            return {
                "worker": 0, "epoch": 1,
                "capacity": {
                    "folds": folds,
                    "windows": {"10s": {"device_ms": device_ms,
                                        "requests": 2}},
                    "bound_by": {"verdict": verdict},
                },
            }

        view = {0: {"pid": 10, "alive": True}, 1: {"pid": 11, "alive": True}}
        out = build_fleetz(
            view,
            {0: health("chip", 10.0, folds=1),
             1: health("link", 5.0, folds=2)},
            missed=set(), now=123.0)
        cap = out["capacity"]
        assert cap["workers"] == [0, 1]
        assert cap["folds"] == 3
        assert cap["windows"]["10s"]["device_ms"] == pytest.approx(15.0)
        assert cap["windows"]["10s"]["requests"] == 4
        assert cap["bound_by"] == {"0": "chip", "1": "link"}

    def test_fleetz_parity_without_capacity(self):
        from imaginary_tpu.obs.aggregate import build_fleetz

        out = build_fleetz({0: {"pid": 10}}, {0: {"worker": 0}},
                           missed=set(), now=123.0)
        assert "capacity" not in out
