"""Compressed-domain ingest (--transport-dct) tests.

Covers the ISSUE 14 surface: golden decode parity against libjpeg's own
scaled decode (PIL draft mode) at every shrink-on-load fraction, the
odd-dimension / edge-block cases, off-by-default byte parity, the
u8/int16 staging tripwire (no float ever crosses the link), the
device-resident frame cache + pressure governor integration, and the
wire-bytes ledger surfaces on /health //metrics //debugz.

Parity basis: the packed transport replays libjpeg's reduced-size IDCT
exactly — the k-point fold carries jidctred's per-frequency cosine
weights and 4:2:0 chroma folds at 2k (libjpeg scales subsampled
components at twice the luma factor, landing them at output resolution
with no upsample). Measured corpus residual is <= 3 grey levels; the
assertions below leave a small margin but stay far inside the dual
integrity tolerance (max 96 / mean 16, engine/integrity.py).
"""

import asyncio
import hashlib
import io

import numpy as np
import pytest
from PIL import Image

from imaginary_tpu import pipeline
from imaginary_tpu.cache import CacheSet, DeviceFrameCache, FrameCache
from imaginary_tpu.codecs import jpeg_dct
from imaginary_tpu.engine.timing import WIRE
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.buckets import dct_packed_geometry
from imaginary_tpu.ops.plan import (
    ImagePlan,
    StageInstance,
    plan_operation,
    wrap_plan_dct,
)
from imaginary_tpu.ops.stages import FromDctSpec
from tests.conftest import fixture_bytes

CORPUS = ["imaginary.jpg", "medium.jpg", "large.jpg", "smart-crop.jpg",
          "exif-orient-6.jpg"]
SHRINKS = [1, 2, 4, 8]


@pytest.fixture(autouse=True)
def _reset_transport(testdata):
    yield
    pipeline.set_transport_dct(False)
    chain_mod.set_device_frame_cache(None)


_COEFF_CACHE: dict = {}


def _coefficients(name_or_buf):
    """Entropy decode is the slow pure-Python stage — cache per source."""
    if isinstance(name_or_buf, str):
        key, buf = name_or_buf, fixture_bytes(name_or_buf)
    else:
        buf = name_or_buf
        key = hashlib.sha256(buf).hexdigest()
    if key not in _COEFF_CACHE:
        _COEFF_CACHE[key] = jpeg_dct.decode_coefficients(buf)
    return _COEFF_CACHE[key]


def _pil_draft_rgb(buf: bytes, shrink: int) -> np.ndarray:
    """libjpeg's own scaled decode (the ground truth the transport must
    reproduce): draft mode selects the same 1/shrink reduced IDCT."""
    im = Image.open(io.BytesIO(buf))
    if shrink > 1:
        im.draft("RGB", (im.width // shrink, im.height // shrink))
    return np.asarray(im.convert("RGB"))


def _device_decode_rgb(coeffs, shrink: int) -> np.ndarray:
    """Run ONLY the decode leg of the transport — pack_dct on the host,
    FromDctSpec (IDCT + upsample + color convert) on the device — through
    the real chain, returning full-resolution-at-scale RGB."""
    packed = jpeg_dct.pack_dct(coeffs, shrink)
    k, h2, w2, hb, wb = dct_packed_geometry(coeffs.h, coeffs.w, shrink)
    plan = ImagePlan(
        stages=[StageInstance(FromDctSpec(hb, wb, k), {})],
        out_h=h2, out_w=w2, transport="rgb",
        in_bucket=(hb + hb // 2, wb) if shrink == 1 else (hb, wb),
        in_h=h2, in_w=w2, out_bucket=(hb, wb),
    )
    return np.asarray(chain_mod.run_single(packed, plan))


class TestDecodeParity:
    @pytest.mark.parametrize("name", CORPUS)
    @pytest.mark.parametrize("shrink", SHRINKS)
    def test_corpus_parity_vs_libjpeg(self, name, shrink):
        buf = fixture_bytes(name)
        c = _coefficients(name)
        assert c is not None, f"{name} should be in decoder scope"
        got = _device_decode_rgb(c, shrink)
        ref = _pil_draft_rgb(buf, shrink)
        assert got.shape == ref.shape
        d = np.abs(got.astype(np.int16) - ref.astype(np.int16))
        # measured corpus-wide residual is <= 3 (libjpeg's fixed-point
        # color convert); the dual integrity tolerance is 96 / 16
        assert int(d.max()) <= 8, f"{name} 1/{shrink}: max {int(d.max())}"
        assert float(d.mean()) <= 2.0, f"{name} 1/{shrink}: mean {d.mean():.2f}"

    @pytest.mark.parametrize("shrink", SHRINKS)
    def test_odd_dimensions_edge_blocks(self, shrink):
        # 117x203: both dims odd, neither a multiple of the 16x16 MCU —
        # exercises the partial edge blocks and the ceil() geometry at
        # every fold factor
        rng = np.random.default_rng(7)
        base = rng.integers(0, 256, (117, 203, 3), dtype=np.uint8)
        # smooth it: random noise is the decoder's worst case for
        # quantization error masking real geometry bugs
        im = Image.fromarray(base).resize((203, 117), Image.BILINEAR)
        b = io.BytesIO()
        im.save(b, "JPEG", quality=92, subsampling=2)
        buf = b.getvalue()
        c = _coefficients(buf)
        assert c is not None
        assert (c.h, c.w) == (117, 203)
        got = _device_decode_rgb(c, shrink)
        ref = _pil_draft_rgb(buf, shrink)
        assert got.shape == ref.shape
        d = np.abs(got.astype(np.int16) - ref.astype(np.int16))
        assert int(d.max()) <= 8 and float(d.mean()) <= 2.0

    def test_out_of_scope_streams_bail(self):
        # progressive JPEG: in-scope subsampling but SOF2 — the decoder
        # must return None (runtime fallback to yuv/rgb), never garbage
        im = Image.open(io.BytesIO(fixture_bytes("medium.jpg"))).convert("RGB")
        b = io.BytesIO()
        im.save(b, "JPEG", quality=85, subsampling=2, progressive=True)
        assert jpeg_dct.decode_packed(b.getvalue(), 1) is None
        # 4:4:4 joined the decoder's scope (gray/444/422/420 all ride);
        # verify it decodes and self-identifies
        b2 = io.BytesIO()
        im.save(b2, "JPEG", quality=85, subsampling=0)
        got = jpeg_dct.decode_packed(b2.getvalue(), 1)
        assert got is not None
        assert got[3] == "444"
        # arithmetic-coded and CMYK streams stay out of scope
        b3 = io.BytesIO()
        im.convert("CMYK").save(b3, "JPEG", quality=85)
        assert jpeg_dct.decode_packed(b3.getvalue(), 1) is None


class TestEndToEnd:
    def test_resize_parity_on_vs_off(self):
        buf = fixture_bytes("medium.jpg")
        o = ImageOptions(width=160)
        pipeline.set_transport_dct(False)
        off = pipeline.process_operation("resize", buf, o)
        pipeline.set_transport_dct(True)
        on = pipeline.process_operation("resize", buf, o)
        assert on.mime == off.mime == "image/jpeg"
        a = np.asarray(Image.open(io.BytesIO(off.body)).convert("RGB"))
        b = np.asarray(Image.open(io.BytesIO(on.body)).convert("RGB"))
        assert a.shape == b.shape
        from imaginary_tpu.engine.integrity import outputs_match

        assert outputs_match(b, a, exact=False)

    def test_thumbnail_deep_shrink_parity(self):
        # thumbnail on a 1080p-class source picks the deepest fold
        buf = fixture_bytes("large.jpg")
        o = ImageOptions(width=100)
        pipeline.set_transport_dct(False)
        off = pipeline.process_operation("thumbnail", buf, o)
        pipeline.set_transport_dct(True)
        on = pipeline.process_operation("thumbnail", buf, o)
        a = np.asarray(Image.open(io.BytesIO(off.body)).convert("RGB"))
        b = np.asarray(Image.open(io.BytesIO(on.body)).convert("RGB"))
        assert a.shape == b.shape
        from imaginary_tpu.engine.integrity import outputs_match

        assert outputs_match(b, a, exact=False)

    def test_pipeline_endpoint_rides_dct(self):
        from imaginary_tpu.options import PipelineOperation

        buf = fixture_bytes("medium.jpg")
        ops = [PipelineOperation(name="resize", params={"width": 200}),
               PipelineOperation(name="crop",
                                 params={"width": 120, "height": 90})]
        o = ImageOptions(operations=ops)
        pipeline.set_transport_dct(False)
        off = pipeline.process_pipeline(buf, o)
        pipeline.set_transport_dct(True)
        on = pipeline.process_pipeline(buf, o)
        a = np.asarray(Image.open(io.BytesIO(off.body)).convert("RGB"))
        b = np.asarray(Image.open(io.BytesIO(on.body)).convert("RGB"))
        assert a.shape == b.shape == (90, 120, 3)
        from imaginary_tpu.engine.integrity import outputs_match

        assert outputs_match(b, a, exact=False)

    def test_non_jpeg_output_stays_off_transport(self, monkeypatch):
        pipeline.set_transport_dct(True)
        monkeypatch.setattr(
            jpeg_dct, "decode_packed",
            lambda *_a, **_k: pytest.fail("dct decode consulted for png out"))
        out = pipeline.process_operation(
            "resize", fixture_bytes("medium.jpg"),
            ImageOptions(width=100, type="png"))
        assert out.mime == "image/png"


class TestOffByDefault:
    def test_switch_defaults_off_everywhere(self):
        assert pipeline.transport_dct_enabled() is False
        from imaginary_tpu.web.config import ServerOptions

        o = ServerOptions()
        assert o.transport_dct is False
        assert o.cache_device_mb == 0.0

    def test_off_state_never_consults_decoder(self, monkeypatch):
        # byte parity pin: with the flag off the dct module is never even
        # consulted, so responses are bit-for-bit the pre-transport build's
        monkeypatch.setattr(
            jpeg_dct, "decode_packed",
            lambda *_a, **_k: pytest.fail("dct decode ran with switch off"))
        out = pipeline.process_operation(
            "resize", fixture_bytes("medium.jpg"), ImageOptions(width=100))
        assert out.mime == "image/jpeg"

    def test_off_state_responses_deterministic(self):
        buf = fixture_bytes("imaginary.jpg")
        o = ImageOptions(width=120)
        a = pipeline.process_operation("resize", buf, o)
        b = pipeline.process_operation("resize", buf, o)
        assert a.body == b.body


class TestStagingTripwire:
    def test_no_float_ever_staged_h2d(self, monkeypatch):
        """Across every launch_batch transport the staged H2D batch
        operand is u8 (rgb, yuv420) or int16 (dct) — a float32 operand
        would 4x the wire bytes and silently void the transport's reason
        to exist. Per-plan dyn parameters (a handful of f32 scalars per
        stage) are exempt: the tripwire watches anything big enough to be
        pixel data, not the few-byte argument vectors."""
        import jax

        staged = []
        real = jax.device_put

        def spy(x, *a, **k):
            # a launch puts its operands in one call: watch every leaf
            for leaf in jax.tree_util.tree_leaves(x):
                dt = getattr(leaf, "dtype", None)
                if dt is not None and getattr(leaf, "size", 0) >= 4096:
                    staged.append(np.dtype(dt))
            return real(x, *a, **k)

        monkeypatch.setattr(jax, "device_put", spy)
        buf = fixture_bytes("medium.jpg")
        c = _coefficients("medium.jpg")

        # rgb transport
        arr = np.asarray(Image.open(io.BytesIO(buf)).convert("RGB"))
        plan = plan_operation("resize", ImageOptions(width=64),
                              arr.shape[0], arr.shape[1], 0, 3)
        staged.clear()
        chain_mod.run_batch([arr, arr], [plan, plan])
        assert staged, "expected at least one staged transfer"
        bad = [d for d in staged if d.kind == "f"]
        assert not bad, f"float operand staged on rgb path: {bad}"

        # dct transport, folded and full-scale layouts
        for shrink in (1, 4):
            packed = jpeg_dct.pack_dct(c, shrink)
            _, h2, w2, _, _ = dct_packed_geometry(c.h, c.w, shrink)
            p = plan_operation("resize", ImageOptions(width=64), h2, w2, 0, 3)
            wrapped = wrap_plan_dct(p, c.h, c.w, shrink)
            staged.clear()
            chain_mod.run_batch([packed, packed], [wrapped, wrapped])
            assert staged
            bad = [d for d in staged if d.kind == "f"]
            assert not bad, f"float operand staged on dct path: {bad}"
            assert np.dtype(np.int16) in staged

    def test_packed_buffer_is_int16(self):
        c = _coefficients("imaginary.jpg")
        for shrink in SHRINKS:
            assert jpeg_dct.pack_dct(c, shrink).dtype == np.int16


class TestDeviceFrameCache:
    def _serve_twice(self, cs):
        dc = DeviceFrameCache(cs.device, cs.stats)
        chain_mod.set_device_frame_cache(dc)
        fc = FrameCache(cs.frames, cs.stats)
        pipeline.set_transport_dct(True)
        buf = fixture_bytes("medium.jpg")
        digest = hashlib.sha256(buf).hexdigest()
        o = ImageOptions(width=100)
        w0 = WIRE.snapshot()
        r1 = pipeline.process_operation("resize", buf, o,
                                        frame_cache=fc, source_digest=digest)
        w1 = WIRE.snapshot()
        r2 = pipeline.process_operation("resize", buf, o,
                                        frame_cache=fc, source_digest=digest)
        w2 = WIRE.snapshot()
        assert r1.body == r2.body
        return dc, (w0, w1, w2)

    def test_hot_source_pays_zero_h2d(self):
        cs = CacheSet(frame_mb=8.0, device_mb=8.0)
        dc, (w0, w1, w2) = self._serve_twice(cs)
        assert w1["h2d"] > w0["h2d"]  # first request staged the input
        assert w2["h2d"] == w1["h2d"]  # repeat request: zero H2D
        assert w2["d2h"] > w1["d2h"]  # the result still drains
        assert cs.stats.device_misses == 1 and cs.stats.device_hits == 1
        assert dc.bytes_used > 0
        assert cs.to_dict()["device_bytes"] == dc.bytes_used

    def test_pressure_ladder_shrinks_then_disables(self):
        cs = CacheSet(frame_mb=8.0, device_mb=8.0)
        dc, _ = self._serve_twice(cs)
        base = cs.device.budget
        assert base == int(8.0 * 1e6)
        cs.apply_pressure(1)  # elevated: halve
        assert cs.device.budget == base // 2
        assert dc.enabled
        cs.apply_pressure(2)  # critical: disable + flush (HBM goes back)
        assert not dc.enabled
        assert dc.bytes_used == 0 and len(dc) == 0
        # disabled cache: serving continues, inputs just re-stage
        w_before = WIRE.snapshot()["h2d"]
        buf = fixture_bytes("medium.jpg")
        digest = hashlib.sha256(buf).hexdigest()
        fc = FrameCache(cs.frames, cs.stats)
        pipeline.process_operation("resize", buf, ImageOptions(width=100),
                                   frame_cache=fc, source_digest=digest)
        assert WIRE.snapshot()["h2d"] > w_before
        cs.apply_pressure(0)  # recovery: budget restored
        assert cs.device.budget == base and dc.enabled

    def test_no_digest_no_device_caching(self):
        cs = CacheSet(device_mb=8.0)
        dc = DeviceFrameCache(cs.device, cs.stats)
        chain_mod.set_device_frame_cache(dc)
        pipeline.set_transport_dct(True)
        pipeline.process_operation("resize", fixture_bytes("medium.jpg"),
                                   ImageOptions(width=100))
        # without a content digest there is no stable identity to pin
        assert len(dc) == 0 and cs.stats.device_hits == 0


class TestHttpSurfaces:
    def test_health_metrics_debugz_carry_device_and_wire(self):
        from aiohttp.test_utils import TestClient, TestServer

        from imaginary_tpu.web.app import create_app
        from imaginary_tpu.web.config import ServerOptions

        # host spill off: a request the cost model spills to the host
        # (under a loaded CPU, every one) never meets the device cache
        opts = ServerOptions(transport_dct=True, cache_frame_mb=8.0,
                             cache_device_mb=8.0, enable_debug=True,
                             host_spill=False)

        async def runner():
            app = create_app(opts, log_stream=io.StringIO())
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                body = fixture_bytes("medium.jpg")
                for _ in range(2):
                    res = await client.post(
                        "/resize?width=100", data=body,
                        headers={"Content-Type": "image/jpeg"})
                    assert res.status == 200
                h = await (await client.get("/health")).json()
                # the device frame key carries the placement's device
                # descriptor, so a repeat that lands on a DIFFERENT chip
                # misses (placement shifts with load EWMAs); keep posting
                # the identical request until one lands where the frame
                # is resident — the wiring, not the placement, is under
                # test here
                for _ in range(6):
                    if h["cache"]["device_hits"] >= 1:
                        break
                    res = await client.post(
                        "/resize?width=100", data=body,
                        headers={"Content-Type": "image/jpeg"})
                    assert res.status == 200
                    h = await (await client.get("/health")).json()
                assert h["cache"]["device_bytes"] > 0
                assert h["cache"]["device_hits"] >= 1
                assert h["executor"]["wire_bytes"]["d2h"] > 0
                m = await (await client.get("/metrics")).text()
                assert 'imaginary_tpu_wire_bytes_total{direction="h2d"}' in m
                assert 'imaginary_tpu_wire_transfers_total{direction="d2h"}' in m
                assert "imaginary_tpu_cache_device_bytes" in m
                d = await (await client.get("/debugz")).json()
                assert d["cache"]["device_bytes"] > 0
            finally:
                await client.close()

        asyncio.run(runner())


class TestPrewarmCoverage:
    def test_compile_misses_zero_after_warm(self):
        from imaginary_tpu import prewarm
        from imaginary_tpu.engine.executor import Executor, ExecutorConfig

        pipeline.set_transport_dct(True)
        # smallest corpus source (300x400) so the warm stays cheap
        src_h, src_w = 300, 400
        o = ImageOptions(width=120)
        built = prewarm.warm_chain("resize", o, src_h, src_w, (1,))
        assert built >= 2  # at least the rgb and dct programs
        c = _coefficients("exif-orient-6.jpg")
        from imaginary_tpu.ops.plan import choose_decode_shrink

        shrink = choose_decode_shrink("resize", o, src_h, src_w, 0, 3)
        packed = jpeg_dct.pack_dct(c, shrink)
        _, h2, w2, _, _ = dct_packed_geometry(c.h, c.w, shrink)
        plan = plan_operation("resize", o, h2, w2, 0, 3)
        wrapped = wrap_plan_dct(plan, c.h, c.w, shrink)
        ex = Executor(ExecutorConfig())
        try:
            ex.process(packed, wrapped)
            assert ex.stats.to_dict()["compile_misses"] == 0
        finally:
            ex.shutdown()
