"""Windowed decode for /extract: the native decoder packs only the area's
window of a 4:2:0 JPEG and stops below it, and the plan extracts inside
the window. Pinned here: the window's planes are the same bytes as the
same slice of the full decode, a windowed /extract answers the same bytes
as the full-frame path under every placement, every request that cannot
take a window decodes the whole frame, and `roi_decodes` counts exactly
the windowed decodes.
"""

import asyncio
import hashlib
import io
from io import BytesIO

import numpy as np
import pytest
from PIL import Image

from imaginary_tpu import codecs, pipeline
from imaginary_tpu.cache import CacheSet, FrameCache
from imaginary_tpu.ops.buckets import bucket_shape
from imaginary_tpu.options import ImageOptions
from tests.conftest import fixture_bytes

yuv_window = pytest.mark.skipif(
    not codecs.yuv420_window_supported(),
    reason="native YUV420 codec with decode windows not built")

# odd dims: the last iMCU row and column are partial
H, W = 243, 330


def _jpeg(progressive=False, h=H, w=W) -> bytes:
    rng = np.random.default_rng(29)
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack([(xx * 255 // max(w - 1, 1)), (yy * 255 // max(h - 1, 1)),
                    (xx * 7 + yy * 3) % 256], axis=-1).astype(np.uint8)
    img = np.clip(img + rng.integers(-40, 40, img.shape), 0, 255).astype(np.uint8)
    out = BytesIO()
    Image.fromarray(img).save(out, "JPEG", quality=90, subsampling=2,
                              progressive=progressive)
    return out.getvalue()


def _planes(packed, hb, wb, h, w):
    ch, cw = (h + 1) // 2, (w + 1) // 2
    return (packed[:h, :w, 0], packed[hb:hb + ch, :cw, 0],
            packed[hb:hb + ch, wb // 2:wb // 2 + cw, 0])


# --- native level ------------------------------------------------------------

@yuv_window
@pytest.mark.parametrize("progressive", [False, True], ids=["baseline", "progressive"])
@pytest.mark.parametrize("window", [
    (0, 0, 16, 16),          # top-left corner
    (96, 96, 48, 80),        # 16-px aligned, inside
    (2, 6, 20, 34),          # even but not 16-px aligned
    (224, 0, 19, 330),       # the last (partial) iMCU row, full width
    (240, 320, 3, 10),       # bottom-right corner, odd edges on the frame's
    (0, 314, 243, 16),       # right edge, full height
    (16, 0, 32, 2),          # two columns
], ids=lambda w: "x".join(map(str, w)))
def test_native_window_is_the_full_decodes_slice(progressive, window):
    buf = _jpeg(progressive)
    y0, x0, wh, ww = window
    fhb, fwb = bucket_shape(H, W)
    full, fh, fw, _ = codecs.decode_yuv420(buf, 1, fhb, fwb)
    hb, wb = bucket_shape(wh, ww)
    win, h, w, _ = codecs.decode_yuv420(buf, 1, hb, wb, window)
    assert (h, w) == (wh, ww) and win.shape == (hb + hb // 2, wb, 1)
    fy, fu, fv = _planes(full, fhb, fwb, fh, fw)
    cy, cx, ch, cw = y0 // 2, x0 // 2, (wh + 1) // 2, (ww + 1) // 2
    want = (fy[y0:y0 + wh, x0:x0 + ww], fu[cy:cy + ch, cx:cx + cw],
            fv[cy:cy + ch, cx:cx + cw])
    for got, ref in zip(_planes(win, hb, wb, wh, ww), want):
        np.testing.assert_array_equal(got, ref)


@yuv_window
@pytest.mark.parametrize("shrink,window", [
    (1, (1, 0, 16, 16)),      # odd top
    (1, (0, 3, 16, 16)),      # odd left
    (1, (0, 0, 15, 16)),      # odd height away from the frame's edge
    (1, (232, 0, 16, 16)),    # past the bottom
    (1, (0, 320, 16, 16)),    # past the right
    (1, (0, 0, 0, 16)),       # empty
    (2, (0, 0, 16, 16)),      # scaled decodes take no window
], ids=["odd-top", "odd-left", "odd-height", "below", "right", "empty", "scaled"])
def test_native_refuses_a_bad_window(shrink, window):
    with pytest.raises(codecs.CodecError):
        codecs.decode_yuv420(_jpeg(), shrink, 64, 64, window)


# --- the window rule -----------------------------------------------------------

def _meta(orientation=0, h=1080, w=1920):
    return codecs.ImageMetadata(w, h, "jpeg", "srgb", False, False, 3,
                                orientation, "420")


@yuv_window
@pytest.mark.parametrize("name,opts,meta,want", [
    # the reference's own /extract: rows 96-512, columns 96-704
    ("extract", dict(top=100, left=100, area_height=400, area_width=600),
     _meta(), (96, 96, 416, 608)),
    # the margin crosses an iMCU edge: row 16 needs row 14's neighbour
    ("extract", dict(top=16, left=16, area_height=16, area_width=16),
     _meta(), (0, 0, 48, 48)),
    # clipped to an odd frame edge
    ("extract", dict(top=1070, left=1900, area_height=9, area_width=19),
     _meta(h=1079, w=1919), (1056, 1888, 23, 31)),
    ("extract", dict(top=100, left=100, area_height=400, area_width=600),
     _meta(orientation=6), None),
    ("extract", dict(top=100, left=100, area_height=400, area_width=600,
                     no_rotation=True), _meta(orientation=6), (96, 96, 416, 608)),
    ("extract", dict(top=100, left=100, area_height=400, area_width=600, flip=True),
     _meta(), None),
    ("extract", dict(top=100, left=100, area_height=400, area_width=600, rotate=90),
     _meta(), None),
    ("extract", dict(top=1, left=1, area_height=1078, area_width=1918), _meta(), None),
    ("extract", dict(top=900, left=100, area_height=400, area_width=600), _meta(), None),
    ("extract", dict(top=0, left=0, area_height=0, area_width=600), _meta(), None),
    ("crop", dict(width=400, height=300), _meta(), None),
], ids=["reference", "margin", "odd-edge", "exif6", "exif6-norotation", "flip",
        "rotate", "whole-frame", "outside", "no-area", "crop"])
def test_window_rule(name, opts, meta, want):
    assert pipeline._pick_window(name, ImageOptions(**opts), meta, 1) == want


@yuv_window
def test_window_rule_needs_an_unscaled_decode():
    o = ImageOptions(top=100, left=100, area_height=400, area_width=600)
    assert pipeline._pick_window("extract", o, _meta(), 2) is None


# --- HTTP level ----------------------------------------------------------------

def _serve(options, fn):
    async def runner():
        from aiohttp.test_utils import TestClient, TestServer

        from imaginary_tpu.web.app import create_app

        client = TestClient(TestServer(create_app(options, log_stream=io.StringIO())))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


async def _post(client, query, body):
    res = await client.post("/extract?" + query, data=body,
                            headers={"Content-Type": "image/jpeg"})
    return res.status, res.headers.get("X-Imaginary-Backend"), await res.read()


async def _codec_counts(client):
    return (await (await client.get("/health")).json())["codecs"]


def _extract_both_ways(monkeypatch, options, body, queries):
    """Each query served with the window rule as it is, then with it
    forced off: (status, backend, bytes) pairs per query, and how far the
    server's codec counters moved."""
    async def fn(client):
        out = []
        c0 = await _codec_counts(client)
        for q in queries:
            with monkeypatch.context() as m:
                windowed = await _post(client, q, body)
                m.setattr(pipeline, "_pick_window", lambda *a, **k: None)
                full = await _post(client, q, body)
            out.append((windowed, full))
        c1 = await _codec_counts(client)
        return out, {k: c1[k] - c0[k] for k in c1}

    return _serve(options, fn)


def _q(top, left, ah, aw):
    return f"top={top}&left={left}&areaheight={ah}&areawidth={aw}"


PLACEMENTS = {"device": dict(host_spill=False), "host": dict(force_host=True)}

AREAS = {
    "one-px": (0, 0, 1, 1),
    "one-px-bottom-right": (H - 1, W - 1, 1, 1),
    "inside": (100, 100, 40, 60),
    "odd-offsets": (31, 47, 17, 33),
    "top-border": (0, 57, 20, 101),
    "left-border": (77, 0, 41, 15),
    "bottom-border": (H - 7, 13, 7, 200),
    "right-border": (5, W - 5, 200, 5),
    "a-row": (120, 0, 1, W),
    "whole-frame": (0, 0, H, W),
    "nearly-whole-frame": (5, 7, H - 5, W - 7),
}


@yuv_window
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
@pytest.mark.parametrize("area", sorted(AREAS))
def test_extract_bytes_match_the_full_frame_path(monkeypatch, placement, area):
    from imaginary_tpu.web.config import ServerOptions

    [(windowed, full)], _ = _extract_both_ways(
        monkeypatch, ServerOptions(**PLACEMENTS[placement]), _jpeg(),
        [_q(*AREAS[area])])
    assert windowed[0] == full[0] == 200
    assert windowed[1] == full[1]
    if area != "whole-frame":  # an identity plan re-encodes the planes
        assert windowed[1] == placement
    assert windowed[2] == full[2]


@yuv_window
@pytest.mark.parametrize("source,query", [
    ("exif-orient-6.jpg", _q(10, 20, 50, 60)),
    ("plain", _q(10, 20, 50, 60) + "&flip=true"),
    ("plain", _q(10, 20, 50, 60) + "&flop=true"),
    ("plain", _q(10, 20, 50, 60) + "&rotate=90"),
], ids=["exif6", "flip", "flop", "rotate"])
def test_prelude_falls_back_to_the_full_decode(monkeypatch, source, query):
    from imaginary_tpu.web.config import ServerOptions

    body = _jpeg() if source == "plain" else fixture_bytes(source)
    [(windowed, full)], delta = _extract_both_ways(
        monkeypatch, ServerOptions(host_spill=False), body, [query])
    assert windowed[0] == 200 and windowed[2] == full[2]
    assert delta == {"decodes": 2, "roi_decodes": 0}


@yuv_window
def test_roi_decodes_counts_exactly_the_windowed_requests():
    from imaginary_tpu.web.config import ServerOptions

    body = _jpeg()
    queries = [
        ("extract", _q(100, 100, 40, 60), True),
        ("extract", _q(0, 0, H, W), False),
        ("resize", "width=100", False),
        ("extract", _q(3, 5, 7, 9), True),
        ("extract", _q(3, 5, 7, 9) + "&flip=true", False),
        ("extract", _q(200, 300, 100, 100), False),  # outside: a 400
    ]

    async def fn(client):
        c0 = await _codec_counts(client)
        for route, q, _ in queries:
            await client.post(f"/{route}?{q}", data=body,
                              headers={"Content-Type": "image/jpeg"})
        return c0, await _codec_counts(client)

    c0, c1 = _serve(ServerOptions(host_spill=False), fn)
    assert c1["roi_decodes"] - c0["roi_decodes"] == sum(w for *_, w in queries)
    assert c1["decodes"] - c0["decodes"] == len(queries)


# --- frame cache -----------------------------------------------------------------

@yuv_window
def test_frame_cache_keeps_each_window_apart(monkeypatch):
    body = _jpeg()
    digest = hashlib.sha256(body).hexdigest()
    cs = CacheSet(frame_mb=8.0)
    fc = FrameCache(cs.frames, cs.stats)
    areas = [(100, 100, 40, 60), (3, 5, 7, 9)]

    def serve(area, **kw):
        top, left, ah, aw = area
        o = ImageOptions(top=top, left=left, area_height=ah, area_width=aw)
        return pipeline.process_operation("extract", body, o, **kw).body

    cached = [serve(a, frame_cache=fc, source_digest=digest) for a in areas * 2]
    assert cs.stats.frame_misses == 2 and cs.stats.frame_hits == 2
    assert len(cs.frames) == 2
    monkeypatch.setattr(pipeline, "_pick_window", lambda *a, **k: None)
    full = [serve(a) for a in areas]
    assert cached == full * 2
    assert full[0] != full[1]
