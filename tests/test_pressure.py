"""Memory-pressure resilience suite (ISSUE 7).

Covers the governor (levels, hysteresis, transition accounting, the
memory.rss chaos site), the brownout ladder (cache budget shrink, batch
shed, pixel-admission clamps), OOM-recovering batch execution (bisect
depths, host routing, capacity-not-fault health accounting, ledgers at
rest), the decode-bomb corpus (crafted huge-dimension PNG/GIF/JPEG
headers rejected pre-allocation on multipart AND ?url= paths), the
pdf_mini inflate-budget pin, the bounded SVG size memo, and byte parity
with every pressure flag off.
"""

from __future__ import annotations

import json
import struct
import time
import zlib

import numpy as np
import pytest
from aiohttp import FormData

from imaginary_tpu import codecs, failpoints
from imaginary_tpu.codecs import CodecError
from imaginary_tpu.engine import pressure as pm
from imaginary_tpu.engine.executor import Executor, ExecutorConfig
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.plan import plan_operation
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.web.config import ServerOptions
from tests.test_server import run


def _cfg(**kw) -> pm.PressureConfig:
    kw.setdefault("rss_limit_mb", 1000.0)
    kw.setdefault("sample_interval_s", 0.0)  # every level() call re-samples
    return pm.PressureConfig(**kw)


# --- bomb corpus: headers that DECLARE giant frames ---------------------------

def png_bomb(w: int = 60000, h: int = 60000) -> bytes:
    """Structurally valid PNG declaring w x h (IHDR + token IDAT + IEND):
    header parsers report the giant dimensions; a naive decoder allocates
    w*h*3 bytes before discovering the stream holds one row of zeros."""
    def chunk(tag: bytes, payload: bytes) -> bytes:
        body = tag + payload
        return (struct.pack(">I", len(payload)) + body
                + struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF))

    return (b"\x89PNG\r\n\x1a\n"
            + chunk(b"IHDR", struct.pack(">IIBBBBB", w, h, 8, 2, 0, 0, 0))
            + chunk(b"IDAT", zlib.compress(b"\x00"))
            + chunk(b"IEND", b""))


def gif_bomb(w: int = 65500, h: int = 65500) -> bytes:
    """GIF89a logical screen descriptor at (near) the format maximum:
    65500^2 = 4290 megapixels from 13 header bytes."""
    return b"GIF89a" + struct.pack("<HH", w, h) + b"\x00\x00\x00"


def jpeg_bomb(w: int = 60000, h: int = 60000) -> bytes:
    """SOI + JFIF APP0 + SOF0 declaring w x h + empty SOS + EOI."""
    app0 = b"\xff\xe0\x00\x10JFIF\x00\x01\x01\x00\x00\x01\x00\x01\x00\x00"
    sof0 = b"\xff\xc0" + struct.pack(">HBHHB", 11, 8, h, w, 1) + b"\x01\x11\x00"
    sos = b"\xff\xda\x00\x08\x01\x01\x00\x00\x3f\x00"
    return b"\xff\xd8" + app0 + sof0 + sos + b"\xff\xd9"


def small_jpeg(w: int = 320, h: int = 240) -> bytes:
    import io

    from PIL import Image

    arr = np.linspace(0, 255, w * h * 3).reshape(h, w, 3).astype(np.uint8)
    out = io.BytesIO()
    Image.fromarray(arr).save(out, "JPEG", quality=85)
    return out.getvalue()


# --- the governor ------------------------------------------------------------

class TestGovernor:
    def test_levels_and_hysteresis(self):
        vals = {"v": 100.0}
        g = pm.MemoryGovernor(_cfg(), rss_fn=lambda: vals["v"])
        assert g.level() == pm.LEVEL_OK
        vals["v"] = 800.0  # 0.80 >= 0.75
        assert g.level() == pm.LEVEL_ELEVATED
        vals["v"] = 950.0  # 0.95 >= 0.90
        assert g.level() == pm.LEVEL_CRITICAL
        # hysteresis: 0.87 is below critical (0.90) but above the demote
        # band (0.85) — the rung LATCHES instead of flapping
        vals["v"] = 870.0
        assert g.level() == pm.LEVEL_CRITICAL
        vals["v"] = 840.0
        assert g.level() == pm.LEVEL_ELEVATED
        # same latch one rung down: 0.72 >= 0.70 stays elevated
        vals["v"] = 720.0
        assert g.level() == pm.LEVEL_ELEVATED
        vals["v"] = 600.0
        assert g.level() == pm.LEVEL_OK
        snap = g.snapshot()
        assert snap["transitions"] == {"ok": 1, "elevated": 2, "critical": 1}
        assert snap["level"] == "ok"
        assert len(snap["recent_transitions"]) == 4

    def test_sampling_interval_caches(self):
        calls = [0]

        def rss():
            calls[0] += 1
            return 100.0

        g = pm.MemoryGovernor(_cfg(sample_interval_s=60.0), rss_fn=rss)
        for _ in range(50):
            g.level()
        assert calls[0] == 1  # one /proc read, not fifty

    def test_host_and_device_signals(self):
        g = pm.MemoryGovernor(
            _cfg(hbm_limit_mb=100.0), rss_fn=lambda: 100.0)
        assert g.level() == pm.LEVEL_OK
        # host in-flight bytes count WITH rss (imminent RSS)
        g.bind_sources(host_mb_fn=lambda: 800.0)
        assert g.level() == pm.LEVEL_CRITICAL
        g.bind_sources(host_mb_fn=lambda: 0.0, device_mb_fn=lambda: 80.0)
        assert g.level() == pm.LEVEL_ELEVATED  # 80/100 HBM

    def test_memory_rss_failpoint_forces_critical(self):
        g = pm.MemoryGovernor(_cfg(), rss_fn=lambda: 1.0)
        assert g.level() == pm.LEVEL_OK
        failpoints.activate("memory.rss=error")
        try:
            assert g.level() == pm.LEVEL_CRITICAL
        finally:
            failpoints.deactivate()
        assert g.level() == pm.LEVEL_OK

    def test_transition_callbacks_and_batch_cap(self):
        vals = {"v": 100.0}
        seen = []
        g = pm.MemoryGovernor(_cfg(batch_mb=40.0), rss_fn=lambda: vals["v"])
        g.on_transition(lambda old, new: seen.append((old, new)))
        assert g.batch_cap_mb() == 0.0  # ok: uncapped
        vals["v"] = 800.0
        assert g.batch_cap_mb() == 40.0
        vals["v"] = 950.0
        assert g.batch_cap_mb() == 20.0  # critical halves
        assert seen == [(0, 1), (1, 2)]

    def test_from_options_off_by_default(self):
        assert pm.from_options(ServerOptions()) is None
        g = pm.from_options(ServerOptions(pressure_rss_mb=512.0))
        assert g is not None and g.config.rss_limit_mb == 512.0

    def test_release_memory_reports(self):
        got = pm.release_memory()
        assert "collected" in got and "trimmed" in got


# --- cache brownout ----------------------------------------------------------

class TestCacheBrownout:
    def test_set_budget_evicts_down(self):
        from imaginary_tpu.cache import ByteBudgetLRU

        evicted = []
        lru = ByteBudgetLRU(1000, on_evict=lambda n: evicted.append(n))
        for i in range(10):
            lru.put(i, b"x", 100)
        assert lru.bytes_used == 1000
        lru.set_budget(300)
        assert lru.bytes_used <= 300
        assert sum(evicted) == 7
        assert lru.get(9) is not None  # most-recent survives
        assert lru.get(0) is None  # LRU went first

    def test_apply_pressure_ladder(self):
        from imaginary_tpu.cache import CacheSet

        cs = CacheSet(result_mb=1.0, frame_mb=1.0, coalesce=False,
                      source_ttl_s=60.0, source_mb=1.0)
        base = cs.result.budget
        cs.apply_pressure(pm.LEVEL_ELEVATED)
        assert cs.result.budget == base // 2
        assert cs.source.budget > 0
        cs.apply_pressure(pm.LEVEL_CRITICAL)
        assert cs.result.budget == base // 4
        assert cs.source.budget == 0 and not cs.source.enabled
        cs.apply_pressure(pm.LEVEL_OK)
        assert cs.result.budget == base and cs.source.enabled
        assert cs.stats.pressure_shrinks == 2
        assert cs.to_dict()["pressure_shrinks"] == 2

    def test_critical_flushes_source_entries(self):
        from imaginary_tpu.cache import CacheSet

        cs = CacheSet(source_ttl_s=60.0, source_mb=1.0)
        cs.source.put("k", b"body", 4)
        assert cs.source.get("k") == b"body"
        cs.apply_pressure(pm.LEVEL_CRITICAL)
        assert cs.source.get("k") is None  # evicted, not just disabled


# --- OOM-recovering execution ------------------------------------------------

def _resize_plan(src=64, dst=32):
    return plan_operation("resize", ImageOptions(width=dst, height=dst),
                          src, src, 0, 3)


def _submit_n(ex, n, src=64, dst=32):
    arr = np.random.randint(0, 255, (src, src, 3), np.uint8)
    return [ex.submit(arr.copy(), _resize_plan(src, dst)) for _ in range(n)]


class TestOomRecovery:
    def _patched_executor(self, monkeypatch, fail_over: int, **cfg):
        """Executor whose launches MemoryError whenever the batch holds
        more than `fail_over` items — the deterministic split-depth rig
        (device.oom at split depths 0/1/2 per the chunk size)."""
        orig = chain_mod.launch_batch

        def flaky(arrs, plans, sharding=None, device=None):
            if len(arrs) > fail_over:
                raise MemoryError("RESOURCE_EXHAUSTED: out of memory (rig)")
            return orig(arrs, plans, sharding=sharding, device=device)

        monkeypatch.setattr(chain_mod, "launch_batch", flaky)
        return Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0,
                                       **cfg))

    def _assert_at_rest(self, ex):
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with ex._owed_lock:
                if ex._device_items == 0 and abs(ex._device_owed_mb) < 1e-6:
                    return
            time.sleep(0.02)
        with ex._owed_lock:
            raise AssertionError(
                f"ledger not at rest: items={ex._device_items} "
                f"owed_mb={ex._device_owed_mb}")

    @pytest.mark.parametrize("fail_over,min_splits", [(4, 1), (2, 3), (1, 7)])
    def test_bisect_depths(self, monkeypatch, fail_over, min_splits):
        ex = self._patched_executor(monkeypatch, fail_over)
        try:
            outs = [f.result(timeout=60) for f in _submit_n(ex, 8)]
            assert all(o.shape == (32, 32, 3) for o in outs)
            assert ex.stats.oom_events >= 1
            assert ex.stats.oom_splits >= min_splits
            assert ex.stats.oom_failed == 0
            # capacity, NOT fault: breaker state untouched
            rec = ex.devhealth.record(0)
            assert rec.consecutive_failures == 0
            assert rec.oom_events >= 1
            assert ex.stats.breaker_opens == 0
            self._assert_at_rest(ex)
        finally:
            ex.shutdown()

    def test_single_item_oom_routes_to_host(self, monkeypatch):
        # every device launch OOMs: bisect exhausts, items serve from host
        ex = self._patched_executor(monkeypatch, 0)
        try:
            futs = _submit_n(ex, 4)
            outs = [f.result(timeout=60) for f in futs]
            assert all(o.shape == (32, 32, 3) for o in outs)
            assert ex.stats.oom_host_routed == 4
            assert ex.stats.oom_failed == 0
            # placement override rides the future like a hedge win
            assert all(getattr(f, "_hedge_placement", None) == "host"
                       for f in futs)
            self._assert_at_rest(ex)
        finally:
            ex.shutdown()

    def test_device_oom_failpoint_storm(self):
        """The chaos shape: device.oom armed at p=1 fires on the dispatch
        AND on every bisect level, so recovery rides host routing — every
        request still completes, nothing trips the breaker."""
        ex = Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0))
        failpoints.activate("device.oom=error")
        try:
            outs = [f.result(timeout=60) for f in _submit_n(ex, 6)]
            assert all(o.shape == (32, 32, 3) for o in outs)
            assert ex.stats.oom_host_routed == 6
            assert ex.stats.breaker_opens == 0
            assert ex.devhealth.record(0).consecutive_failures == 0
            self._assert_at_rest(ex)
        finally:
            failpoints.deactivate()
            ex.shutdown()

    def test_keyed_device_oom_spelling(self):
        ex = Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0))
        failpoints.activate("device.oom[0]=once(error)")
        try:
            outs = [f.result(timeout=60) for f in _submit_n(ex, 2)]
            assert all(o.shape == (32, 32, 3) for o in outs)
            assert ex.stats.oom_events == 1
        finally:
            failpoints.deactivate()
            ex.shutdown()

    def test_non_oom_errors_still_fail(self, monkeypatch):
        def broken(arrs, plans, sharding=None, device=None):
            raise RuntimeError("chip on fire")  # NOT an OOM marker

        monkeypatch.setattr(chain_mod, "launch_batch", broken)
        ex = Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0))
        try:
            fut = _submit_n(ex, 1)[0]
            with pytest.raises(Exception, match="chip on fire"):
                fut.result(timeout=30)
            assert ex.stats.oom_events == 0
        finally:
            ex.shutdown()

    def test_pressure_batch_byte_cap(self, monkeypatch):
        """Elevated pressure slices groups by wire bytes, not just item
        count — launches shrink BEFORE the chip overflows."""
        gov = pm.MemoryGovernor(_cfg(batch_mb=0.05),
                                rss_fn=lambda: 800.0)  # elevated
        ex = Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0,
                                     pressure=gov))
        try:
            outs = [f.result(timeout=60) for f in _submit_n(ex, 8)]
            assert all(o.shape == (32, 32, 3) for o in outs)
            assert ex.stats.pressure_capped_batches > 0
        finally:
            ex.shutdown()

    def test_pressure_oversize_forced_to_host(self):
        gov = pm.MemoryGovernor(_cfg(oversize_mpix=0.001),
                                rss_fn=lambda: 800.0)  # elevated
        ex = Executor(ExecutorConfig(host_spill=False, max_form_ms=1.0,
                                     pressure=gov))
        try:
            out = ex.process(
                np.random.randint(0, 255, (64, 64, 3), np.uint8),
                _resize_plan())
            assert out.shape == (32, 32, 3)
            assert ex.stats.pressure_host_forced == 1
            assert ex.stats.spilled == 1  # rode the spill branch
        finally:
            ex.shutdown()

    def test_is_oom_classification(self):
        assert chain_mod.is_oom_error(MemoryError())
        assert chain_mod.is_oom_error(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                         "to allocate 1073741824 bytes"))
        assert chain_mod.is_oom_error(
            failpoints.FailpointError("failpoint device.oom: injected error"))
        assert not chain_mod.is_oom_error(RuntimeError("connection reset"))


# --- decode-bomb hardening ---------------------------------------------------

class TestBombGate:
    @pytest.fixture(autouse=True)
    def _reset_cap(self):
        token = codecs.set_decode_pixel_cap(0.0)
        yield
        codecs._DECODE_PIXEL_CAP.reset(token)

    @pytest.mark.parametrize("bomb,fmt", [
        (png_bomb(), "png"), (gif_bomb(), "gif"), (jpeg_bomb(), "jpeg"),
    ])
    def test_corpus_rejected_before_allocation(self, bomb, fmt):
        codecs.set_decode_pixel_cap(18.0)
        with pytest.raises(CodecError) as ei:
            codecs.decode(bomb)
        assert ei.value.code == 413
        assert "megapixel" in ei.value.message

    def test_cap_zero_gate_disarmed(self):
        # gate off: the decoder itself reports the (truncated) bomb —
        # whatever error that is, it must not be the 413 gate
        try:
            codecs.decode(gif_bomb(200, 200))
        except CodecError as e:
            assert e.code != 413

    def test_small_image_passes_gate(self):
        codecs.set_decode_pixel_cap(18.0)
        d = codecs.decode(small_jpeg())
        assert d.array.shape[:2] == (240, 320)

    def test_codec_bomb_failpoint(self):
        codecs.set_decode_pixel_cap(0.0)
        failpoints.activate("codec.bomb=error")
        try:
            with pytest.raises(CodecError) as ei:
                codecs.decode(small_jpeg())
            assert ei.value.code == 413
        finally:
            failpoints.deactivate()

    def test_pdf_mini_inflate_budget_pin(self):
        """The decompression-bomb budget in the vendored PDF renderer:
        a stream inflating past the budget is refused at the budget, not
        materialized."""
        from imaginary_tpu.codecs import pdf_mini

        raw = zlib.compress(b"\x00" * 2_000_000)  # ~2 MB from ~2 KB
        with pytest.raises(pdf_mini.UnsupportedPdf, match="budget"):
            pdf_mini._bounded_inflate(raw, budget=100_000)
        # under budget passes untouched
        assert pdf_mini._bounded_inflate(raw, budget=4_000_000) == \
            b"\x00" * 2_000_000


class TestSvgSizeMemo:
    def test_lru_bounded_with_eviction_accounting(self, monkeypatch):
        from imaginary_tpu.codecs import vector_backend as vb

        monkeypatch.setattr(vb, "_svg_handle", lambda buf: 1)
        monkeypatch.setattr(vb, "_svg_size_from_handle", lambda h: (2, 3))

        class _G:
            @staticmethod
            def g_object_unref(p):
                pass

        monkeypatch.setattr(vb, "_gobject", _G)
        monkeypatch.setattr(vb, "_SVG_SIZE_CACHE_MAX", 16)
        vb._SVG_SIZE_CACHE.clear()
        before = vb.svg_size_cache_stats()["evictions"]
        for i in range(40):
            assert vb.svg_intrinsic_size(b"<svg %d>" % i) == (2, 3)
        stats = vb.svg_size_cache_stats()
        assert stats["items"] <= 16
        assert stats["evictions"] - before == 24
        # hits refresh recency: re-read the newest, then overflow by one
        vb.svg_intrinsic_size(b"<svg 39>")
        vb.svg_intrinsic_size(b"<svg fresh>")
        assert vb.svg_intrinsic_size(b"<svg 39>") == (2, 3)


# --- HTTP: the brownout ladder end to end ------------------------------------

QOS_CFG = json.dumps({
    "default": {"class": "standard"},
    "tenants": [
        {"name": "bulk", "class": "batch", "api_keys": ["bulk-key"]},
    ],
})

PRESSURE_OPTS = dict(pressure_rss_mb=1_000_000.0)  # governor on, rung ok


def _arm_critical(client):
    """Force the service's governor to critical via the memory.rss chaos
    site (the sample interval is zeroed so the next request re-samples)."""
    svc = client.server.app["service"]
    svc.pressure.config.sample_interval_s = 0.0
    failpoints.activate("memory.rss=error")


class TestHttpLadder:
    def test_parity_defaults_build_no_governor(self):
        async def fn(client, _):
            assert client.server.app["service"].pressure is None
            res = await client.get("/health")
            body = await res.json()
            assert "pressure" not in body
            # /metrics carries no pressure families either
            mres = await client.get("/metrics")
            assert "imaginary_tpu_pressure" not in await mres.text()

        run(ServerOptions(), fn)

    def test_health_and_metrics_pressure_block(self):
        async def fn(client, _):
            res = await client.get("/health")
            body = await res.json()
            assert body["pressure"]["level"] == "ok"
            assert body["pressure"]["rss_mb"] > 0
            text = await (await client.get("/metrics")).text()
            assert "imaginary_tpu_pressure_state 0" in text
            assert "imaginary_tpu_oom_splits_total 0" in text
            assert ('imaginary_tpu_pressure_transitions_total'
                    '{level="critical"} 0') in text

        run(ServerOptions(**PRESSURE_OPTS), fn)

    def test_multipart_bomb_rejected_413(self):
        async def fn(client, _):
            for bomb, name, ctype in (
                (png_bomb(), "b.png", "image/png"),
                (gif_bomb(), "b.gif", "image/gif"),
                (jpeg_bomb(), "b.jpg", "image/jpeg"),
            ):
                form = FormData()
                form.add_field("file", bomb, filename=name,
                               content_type=ctype)
                res = await client.post("/resize?width=100&height=100",
                                        data=form)
                assert res.status == 413, (name, await res.text())

        run(ServerOptions(**PRESSURE_OPTS), fn)

    def test_url_bomb_rejected_413(self):
        from aiohttp import web as aioweb

        async def origin(request):
            return aioweb.Response(body=png_bomb(),
                                   content_type="image/png")

        async def fn(client, origin_url):
            res = await client.get(
                f"/resize?width=100&height=100&url={origin_url}/bomb.png")
            assert res.status == 413, await res.text()

        run(ServerOptions(enable_url_source=True, **PRESSURE_OPTS), fn,
            origin_handler=origin)

    def test_bomb_is_422_without_governor(self):
        # parity: flags off keeps the reference's 422 resolution error
        async def fn(client, _):
            form = FormData()
            form.add_field("file", png_bomb(), filename="b.png",
                           content_type="image/png")
            res = await client.post("/resize?width=100&height=100",
                                    data=form)
            assert res.status == 422

        run(ServerOptions(), fn)

    def test_critical_sheds_batch_class_only(self):
        async def fn(client, _):
            _arm_critical(client)
            try:
                form = FormData()
                form.add_field("file", small_jpeg(), filename="s.jpg",
                               content_type="image/jpeg")
                res = await client.post(
                    "/resize?width=64&height=64&key=bulk-key", data=form)
                assert res.status == 503
                assert "Retry-After" in res.headers
                body = await res.json()
                assert "memory pressure" in body["message"]
                # standard class still serves
                form = FormData()
                form.add_field("file", small_jpeg(), filename="s.jpg",
                               content_type="image/jpeg")
                res = await client.post("/resize?width=64&height=64",
                                        data=form)
                assert res.status == 200
            finally:
                failpoints.deactivate()
            svc = client.server.app["service"]
            snap = svc.pressure.snapshot()
            assert snap["batch_sheds"] >= 1

        run(ServerOptions(qos_config=QOS_CFG, **PRESSURE_OPTS), fn)

    def test_critical_clamps_output_resolution(self):
        async def fn(client, _):
            _arm_critical(client)
            try:
                # 6000x6000 = 36 MP output > 18 * 0.25 = 4.5 MP clamp
                form = FormData()
                form.add_field("file", small_jpeg(), filename="s.jpg",
                               content_type="image/jpeg")
                res = await client.post(
                    "/enlarge?width=6000&height=6000", data=form)
                assert res.status == 413
                assert "Retry-After" in res.headers
                # modest output still serves under critical
                form = FormData()
                form.add_field("file", small_jpeg(), filename="s.jpg",
                               content_type="image/jpeg")
                res = await client.post("/resize?width=64&height=64",
                                        data=form)
                assert res.status == 200
            finally:
                failpoints.deactivate()
            snap = client.server.app["service"].pressure.snapshot()
            assert snap["pixel_clamps"] >= 1

        run(ServerOptions(**PRESSURE_OPTS), fn)

    def test_critical_shrinks_cache_budgets(self):
        async def fn(client, _):
            svc = client.server.app["service"]
            base = svc.caches.result.budget
            assert base > 0 and svc.caches.source.enabled
            _arm_critical(client)
            try:
                res = await client.get("/health")
                assert (await res.json())["pressure"]["level"] == "critical"
                assert svc.caches.result.budget == base // 4
                assert not svc.caches.source.enabled
            finally:
                failpoints.deactivate()
            # recovery restores the configured budgets
            res = await client.get("/health")
            assert (await res.json())["pressure"]["level"] == "ok"
            assert svc.caches.result.budget == base
            assert svc.caches.source.enabled

        run(ServerOptions(cache_result_mb=4.0, cache_source_ttl=60.0,
                          **PRESSURE_OPTS), fn)

    def test_wide_event_carries_pressure_level(self):
        import io

        stream = io.StringIO()

        async def runner():
            from aiohttp.test_utils import TestClient, TestServer

            from imaginary_tpu.web.app import create_app

            app = create_app(
                ServerOptions(wide_events=True, **PRESSURE_OPTS),
                log_stream=stream)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                form = FormData()
                form.add_field("file", small_jpeg(), filename="s.jpg",
                               content_type="image/jpeg")
                res = await client.post("/resize?width=64&height=64",
                                        data=form)
                assert res.status == 200
            finally:
                await client.close()

        import asyncio

        asyncio.run(runner())
        events = [json.loads(line) for line in stream.getvalue().splitlines()
                  if line.startswith("{")]
        assert any(e.get("pressure") == "ok" for e in events)


@pytest.mark.slow
class TestMallocTrim:
    def test_release_memory_drops_rss(self):
        """The --mrelease satellite: gc.collect alone leaves freed pages
        in glibc's arena; release_memory's malloc_trim returns them to
        the OS. Asserted as an RSS drop after releasing a 256 MB buffer."""
        from imaginary_tpu.web.health import _rss_mb

        if not pm._malloc_trim():  # non-glibc host: nothing to assert
            pytest.skip("malloc_trim unavailable on this libc")
        buf = bytearray(256 * 1024 * 1024)
        buf[::4096] = b"x" * len(buf[::4096])  # touch every page
        high = _rss_mb()
        del buf
        got = pm.release_memory()
        assert got["trimmed"]
        time.sleep(0.1)
        low = _rss_mb()
        assert high - low > 128.0, (high, low)
