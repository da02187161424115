"""Operation registry and the synchronous processing path.

This is the role of image.go: the 16 named transforms + `info` + `pipeline`,
all funnelling into one processing core. Where the reference's core is a
per-request cgo call into libvips (image.go:81-113), ours is: host decode ->
geometry plan -> ONE jit-compiled device program -> host encode. A JSON
/pipeline fuses every stage of every op into that single program — decode
once, encode once — where the reference pays a full decode+encode per op
(SURVEY.md section 3.3).

The async micro-batching executor (engine/) reuses exactly these plans;
this module is the single-image path used by tests and CLI tools.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable, Optional

import numpy as np

from imaginary_tpu import codecs
from imaginary_tpu import deadline as deadline_mod
from imaginary_tpu import failpoints
from imaginary_tpu.engine import timing
from imaginary_tpu.engine.timing import COPIES
from imaginary_tpu.obs import trace as obs_trace
from imaginary_tpu.codecs import EncodeOptions, YuvPlanes
from imaginary_tpu.errors import ImageError, new_error
from imaginary_tpu.imgtype import ImageType, get_image_mime_type, image_type
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.params import build_params_from_operation
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.buckets import bucket_shape
from imaginary_tpu.ops.plan import (
    OPERATION_NAMES,
    ImagePlan,
    choose_decode_shrink,
    plan_operation,
    wrap_plan_dct,
    wrap_plan_yuv420,
)

# Ops servable over HTTP (ref: OperationsMap image.go:15-32 + /info + /pipeline)
ALL_OPERATIONS = OPERATION_NAMES + ("info", "pipeline")

MAX_PIPELINE_OPERATIONS = 10  # ref: image.go:383-385

# Type values under which a request's output stays JPEG (imgtype.py maps the
# "jpg" alias; "" and "auto" inherit a JPEG source) — the packed-YUV420
# transport gate.
_JPEG_TYPE_NAMES = ("", "jpeg", "jpg", "auto")

# Compressed-domain ingest (--transport-dct): host entropy decode ships
# dequantized DCT coefficients to the device, which runs the IDCT + color
# convert itself (codecs/jpeg_dct.py + ops FromDctSpec). OFF by default —
# every new transport is opt-in so off-state responses stay byte-identical.
_TRANSPORT_DCT = False


def set_transport_dct(on: bool) -> None:
    """Flip the dct transport on/off (wired from --transport-dct)."""
    global _TRANSPORT_DCT
    _TRANSPORT_DCT = bool(on)


def transport_dct_enabled() -> bool:
    return _TRANSPORT_DCT


# Compressed-domain egress (--transport-dct-egress): the device chain ends
# in a forward DCT + quantization (ops ToDctSpec) and the host entropy
# encoder drains int16 coefficients instead of pixels — the link carries
# quantized coefficients in BOTH directions. Rides on the dct transport
# (requires --transport-dct) and is OFF by default for the same
# byte-identical-off-state reason.
_TRANSPORT_DCT_EGRESS = False


def set_transport_dct_egress(on: bool) -> None:
    """Flip dct egress on/off (wired from --transport-dct-egress)."""
    global _TRANSPORT_DCT_EGRESS
    _TRANSPORT_DCT_EGRESS = bool(on)


def transport_dct_egress_enabled() -> bool:
    return _TRANSPORT_DCT_EGRESS


def _pick_egress(o: ImageOptions, target: ImageType) -> str:
    """"dct" when this request should drain quantized coefficients.

    Baseline-JPEG output only: encode_quantized writes baseline 4:2:0
    scans, so progressive (interlace) requests keep the pixel readback
    and the normal encoder."""
    if not _TRANSPORT_DCT_EGRESS:
        return ""
    if target is not ImageType.JPEG or o.interlace:
        return ""
    return "dct"

# Injected by the web layer: url -> RGBA ndarray (watermarkimage fetch,
# image.go:343-370). Kept injectable so the ops layer stays network-free.
WatermarkFetcher = Callable[[str], np.ndarray]


@dataclasses.dataclass
class ProcessedImage:
    body: bytes
    mime: str
    # Output geometry stamped from the plan (the single source of
    # geometry truth): the result-cache meta then carries it, so a
    # ?returnSize=1 cache hit serves its headers without re-probing —
    # or copying — the stored body. 0 = unknown (legacy/shm entries);
    # the serving edge probes a bounded header prefix for those.
    width: int = 0
    height: int = 0


def _encode_type(o: ImageOptions, source: ImageType) -> ImageType:
    """Output format resolution (ref: Process type handling + type.go)."""
    from imaginary_tpu.imgtype import ENCODABLE

    if o.type and o.type != "auto":
        t = image_type(o.type)
        if t is ImageType.UNKNOWN:
            raise new_error("Unsupported output image format", 400)
        return t
    # no explicit type: keep source format where encodable, else JPEG
    return source if source in ENCODABLE else ImageType.JPEG


def _encode(arr, o: ImageOptions, target: ImageType) -> ProcessedImage:
    """Encode with the WEBP/HEIF/AVIF -> JPEG fallback (image.go:99-103).

    arr is an HWC uint8 array, YuvPlanes from the packed transport (those
    encode through the raw-plane JPEG path — no host color math), or
    QuantizedBlocks from the dct egress (entropy-coded directly: the host
    never touches pixels at all). A non-JPEG target (mid-pipeline type
    switch) or raw-encode failure reconstructs pixels and takes the
    normal path.
    """
    # last stage boundary before the response: a request whose budget
    # expired during device execute must not pay for an encode nobody
    # will receive (no-op without an active deadline)
    deadline_mod.check("encode")
    failpoints.hit("codec.encode")
    opts = EncodeOptions(
        type=target,
        quality=o.quality,
        compression=o.compression,
        interlace=o.interlace,
        palette=o.palette,
        speed=o.speed,
        strip_metadata=o.strip_metadata,
    )
    from imaginary_tpu.codecs.jpeg_dct import QuantizedBlocks

    with timing.stage("encode"):
        if isinstance(arr, QuantizedBlocks):
            if target is ImageType.JPEG and not o.interlace:
                try:
                    body = codecs.jpeg_dct.encode_quantized(arr)
                    COPIES.add("encode", len(body))
                    return ProcessedImage(body=body,
                                          mime=get_image_mime_type(target))
                except ImageError:
                    pass  # fall through to the pixel reconstruction
            y, u, v = codecs.jpeg_dct.blocks_to_planes(arr)
            arr = YuvPlanes(y=y, u=u, v=v)
        if isinstance(arr, YuvPlanes):
            if target is ImageType.JPEG:
                try:
                    body = codecs.encode_yuv(arr, opts)
                    COPIES.add("encode", len(body))
                    return ProcessedImage(body=body, mime=get_image_mime_type(target))
                except ImageError:
                    pass  # fall through to the RGB encoder
            arr = codecs.yuv_planes_to_rgb(arr)
        try:
            body = codecs.encode(arr, opts)
            actual = target
        except ImageError:
            if target in (ImageType.WEBP, ImageType.HEIF, ImageType.AVIF):
                opts.type = ImageType.JPEG
                body = codecs.encode(arr, opts)
                actual = ImageType.JPEG
            else:
                raise
    COPIES.add("encode", len(body))
    return ProcessedImage(body=body, mime=get_image_mime_type(actual))


def _carry_metadata(src_buf: bytes, strip: bool, out: ProcessedImage,
                    orientation_applied: bool, out_w: int = 0,
                    out_h: int = 0) -> ProcessedImage:
    """Preserve source EXIF/ICC on JPEG output unless stripmeta is set
    (ref: options.go:139 — StripMetadata defaults false; libvips keeps
    metadata). Orientation resets to 1 when the chain already applied the
    EXIF rotation, and PixelX/YDimension re-sync to the output geometry —
    both exactly as libvips does on save."""
    # every op path funnels through here with the plan's output geometry:
    # stamp it so the serving edge never re-probes the body for dims
    out.width = out_w
    out.height = out_h
    if strip or out.mime != "image/jpeg":
        return out
    segs = codecs.jpeg_metadata_segments(src_buf)
    if not segs:
        return out
    segs = [
        codecs.patch_exif_segment(
            s,
            orientation=1 if orientation_applied else None,
            pixel_w=out_w or None,
            pixel_h=out_h or None,
        )
        if s[4:10] == b"Exif\x00\x00" else s
        for s in segs
    ]
    body = codecs.insert_jpeg_segments(out.body, segs)
    # metadata carry re-materializes the body (splice copy): ledger it
    COPIES.add("encode", len(body))
    return ProcessedImage(body=body, mime=out.mime,
                          width=out_w, height=out_h)


def _run_stages(arr: np.ndarray, plan: ImagePlan, runner=None) -> np.ndarray:
    """Device execution with the panic guard (ref: Process recover(),
    image.go:82-94): backend failures surface as 400s, not 500s.

    runner: (arr, plan) -> arr; defaults to the direct single-image path,
    the web layer passes Executor.process for micro-batched dispatch."""
    if not plan.stages:
        from imaginary_tpu.engine.executor import note_placement

        note_placement("device")  # no transform -> no host/device divergence
        return arr
    try:
        # the "execute" span covers submit -> result: micro-batch queue
        # wait + device H2D/compute/drain, OR the host-spill path (whose
        # host_gate/host_spill sub-spans attribute via the timing hook).
        # Off the profiler capture: this thread only waits, and the
        # executor's own threads annotate what the wait is made of.
        with obs_trace.span("execute", annotate=False):
            out = (runner or chain_mod.run_single)(arr, plan)
            # the transform stage's one materialized frame (device drain
            # or host-interpreter output); structured results (YuvPlanes/
            # QuantizedBlocks) book at their encode instead
            nb = getattr(out, "nbytes", 0)
            if nb:
                COPIES.add("transform", int(nb))
            return out
    except ImageError:
        raise
    except Exception as e:  # XLA/compile/runtime errors
        raise new_error(f"image processing error: {e}", 400) from None


def info(buf: bytes, o: ImageOptions) -> ProcessedImage:
    """ref: Info, image.go:56-79."""
    try:
        meta = codecs.probe(buf)
    except ImageError as e:
        raise new_error("Cannot retrieve image metadata: " + e.message, 400) from None
    return ProcessedImage(body=json.dumps(meta.to_dict()).encode(), mime="application/json")


def process_operation(
    name: str,
    buf: bytes,
    o: ImageOptions,
    watermark_fetcher: Optional[WatermarkFetcher] = None,
    runner=None,
    meta=None,
    frame_cache=None,
    source_digest=None,
) -> ProcessedImage:
    """Run one named operation end-to-end (decode -> device -> encode).

    meta: an ImageMetadata the caller already probed (the web layer's
    resolution guard), so the hot path parses headers exactly once.
    frame_cache/source_digest: the web layer's decoded-frame LRU
    (imaginary_tpu/cache.py) plus the sha256 of `buf` — different ops on
    the same hot source then skip the decode stage."""
    if name == "info":
        return info(buf, o)
    if name == "pipeline":
        return process_pipeline(buf, o, watermark_fetcher, runner=runner,
                                meta=meta, frame_cache=frame_cache,
                                source_digest=source_digest)
    if name not in OPERATION_NAMES:
        raise new_error(f"Unsupported operation: {name}", 400)

    from imaginary_tpu.imgtype import determine_image_type

    # "total" encloses every stage below: it stays off the profiler
    # capture, so a device idle gap is labelled by the stage inside it
    with timing.stage("total", annotate=False):
        with timing.stage("probe"):
            src_type = determine_image_type(buf)
            if meta is None and src_type in (ImageType.JPEG, ImageType.SVG):
                try:
                    meta = codecs.probe_fast(buf)
                except ImageError:
                    meta = None  # decode below raises the user-facing error
            shrink = _pick_shrink(name, buf, o, meta)

        if _dct_eligible(src_type, meta, o):
            out = _process_dct(name, buf, o, meta, shrink,
                               watermark_fetcher, runner,
                               frame_cache, source_digest)
            if out is not None:
                return out

        if _yuv_eligible(src_type, meta, o):
            out = _process_yuv420(name, buf, o, meta, shrink,
                                  watermark_fetcher, runner,
                                  frame_cache, source_digest)
            if out is not None:
                return out

        d = _decode_cached(buf, shrink, frame_cache, source_digest)
        wm = _fetch_watermark(name, o, watermark_fetcher)
        plan = plan_operation(
            name, o, d.array.shape[0], d.array.shape[1], d.orientation,
            d.array.shape[2], watermark_rgba=wm,
        )
        arr = _run_stages(d.array, plan, runner)
        out = _encode(arr, o, _encode_type(o, d.type))
        return _carry_metadata(buf, o.strip_metadata, out, not o.no_rotation,
                               plan.out_w, plan.out_h)


def _dct_eligible(src_type, meta, o: ImageOptions) -> bool:
    """Gate for the compressed-domain transport: baseline JPEG in
    (4:2:0/4:2:2/4:4:4/grayscale), JPEG out, and the switch on. Coarser
    than the entropy decoder's own scope check (baseline, 8-bit, no odd
    sampling factors) — decode_packed re-verifies and returns None on
    anything it can't prove, falling back to yuv/rgb. No native codec
    needed: the entropy decode falls back to pure Python/numpy."""
    if not _TRANSPORT_DCT:
        return False
    if src_type is not ImageType.JPEG or meta is None:
        return False
    if meta.subsampling not in ("420", "422", "444", "gray"):
        return False
    return o.type in _JPEG_TYPE_NAMES


def _yuv_eligible(src_type, meta, o: ImageOptions) -> bool:
    """Gate for the packed-YUV420 transport: plain 4:2:0 JPEG in, JPEG out,
    native raw codec available. Everything else rides the RGB path."""
    if src_type is not ImageType.JPEG or meta is None:
        return False
    if meta.subsampling != "420":
        return False
    if o.type not in _JPEG_TYPE_NAMES:
        return False
    try:
        return codecs.yuv420_supported()
    except Exception:
        return False


def _decode_cached(buf, shrink, frame_cache=None, digest=None):
    """codecs.decode fronted by the decoded-frame LRU (cache.py). Cached
    arrays are marked read-only before sharing: every consumer (device
    launch copies into the batch stack, the host interpreter and encoders
    only read) treats inputs as immutable, and a hot frame served to many
    concurrent requests must stay that way."""
    with timing.stage("decode"):
        key = None
        if frame_cache is not None and digest is not None:
            key = (digest, shrink, "rgb")
            d = frame_cache.get(key)
            if d is not None:
                return d
        failpoints.hit("codec.decode")
        d = codecs.decode(buf, shrink)
        COPIES.add("decode", d.array.nbytes)
        if key is not None:
            d.array.setflags(write=False)
            frame_cache.put(key, d, d.array.nbytes)
        return d


def _decode_yuv_packed(buf, shrink, sh, sw, frame_cache=None, digest=None,
                       window=None):
    """Raw-decode into the packed layout; None means 'use the RGB path'
    (non-420 surprises, raw decode trouble, probe/decode disagreement —
    the RGB decode then raises any user-facing error itself). The packed
    transport buffer caches under its own kind tag — it is a different
    pixel layout than the RGB decode of the same digest — and its window
    (`_pick_window`; (sh, sw) are then the window's dims)."""
    hb, wb = bucket_shape(sh, sw)
    key = None
    if frame_cache is not None and digest is not None:
        key = (digest, shrink, "yuv", hb, wb, window)
        hit = frame_cache.get(key)
        if hit is not None:
            return hit
    try:
        with timing.stage("decode"):
            failpoints.hit("codec.decode")
            packed, h, w, _orient = codecs.decode_yuv420(buf, shrink, hb, wb,
                                                         window)
    except ImageError:
        return None
    if (h, w) != (sh, sw):
        return None
    COPIES.add("decode", packed.nbytes)
    if key is not None:
        packed.setflags(write=False)
        frame_cache.put(key, (packed, hb, wb), packed.nbytes)
    return packed, hb, wb


def _decode_dct_packed(buf, shrink, frame_cache=None, digest=None):
    """Entropy-decode + dequantize + fold + pack coefficients for device
    IDCT; None means 'use the yuv/rgb paths' (out-of-scope stream). The
    packed coefficient buffer caches under its own kind tag, and the same
    digest-scoped key doubles as the DEVICE frame-cache key (ops/chain.py
    pins the staged device buffer under it, so a hot source pays zero H2D
    on repeat requests). Returns (packed, h2, w2, layout, frame_key) or
    None."""
    key = None
    if frame_cache is not None and digest is not None:
        key = (digest, shrink, "dct")
        hit = frame_cache.get(key)
        if hit is not None:
            packed, h2, w2, layout = hit
            return packed, h2, w2, layout, key
    from imaginary_tpu.codecs import jpeg_dct

    with timing.stage("decode"):
        failpoints.hit("codec.decode")
        got = jpeg_dct.decode_packed(buf, shrink)
    if got is None:
        return None
    packed, h2, w2, layout = got
    COPIES.add("decode", packed.nbytes)
    fkey = (digest, shrink, "dct") if digest is not None else None
    if key is not None:
        packed.setflags(write=False)
        frame_cache.put(key, (packed, h2, w2, layout), packed.nbytes)
    return packed, h2, w2, layout, fkey


def _process_dct(name, buf, o, meta, shrink, watermark_fetcher, runner,
                 frame_cache=None,
                 source_digest=None) -> Optional[ProcessedImage]:
    """Serve a JPEG->JPEG request over the compressed-domain transport.

    Returns None to fall back (yuv420 then rgb): out-of-scope stream,
    probe/SOF0 dims disagreement, or an identity chain — the packed
    transports short-circuit identity better (raw planes straight to the
    encoder), and dct coefficients have no encoder-facing unpacked form.
    Parameter-validation errors still raise, exactly as the other paths
    would, since the plan math is identical.
    """
    sh = -(-meta.height // shrink)
    sw = -(-meta.width // shrink)
    got = _decode_dct_packed(buf, shrink, frame_cache, source_digest)
    if got is None:
        return None
    packed, h2, w2, layout, fkey = got
    if (h2, w2) != (sh, sw):
        return None
    wm = _fetch_watermark(name, o, watermark_fetcher)
    plan = plan_operation(name, o, sh, sw, meta.orientation, 3,
                          watermark_rgba=wm)
    if not plan.stages:
        return None
    target = _encode_type(o, ImageType.JPEG)
    wrapped = wrap_plan_dct(plan, meta.height, meta.width, shrink,
                            frame_key=fkey, layout=layout,
                            egress=_pick_egress(o, target),
                            egress_quality=o.quality if o.quality > 0 else 80)
    result = _run_stages(packed, wrapped, runner)
    out = _encode(result, o, target)
    return _carry_metadata(buf, o.strip_metadata, out, not o.no_rotation,
                           plan.out_w, plan.out_h)


def _process_yuv420(name, buf, o, meta, shrink, watermark_fetcher, runner,
                    frame_cache=None,
                    source_digest=None) -> Optional[ProcessedImage]:
    """Serve a JPEG->JPEG request over the packed-plane transport.

    Returns None to fall back to the RGB path — parameter-validation errors
    still raise, exactly as the RGB path would, since the plan math is
    identical. Decode runs before the watermark fetch so a fallback never
    double-fetches the watermark or double-counts the decode stage. An
    /extract that reads only part of the frame decodes just its window
    and plans on the window's dims, its area moved into the window.
    """
    sh = -(-meta.height // shrink)
    sw = -(-meta.width // shrink)
    window = _pick_window(name, o, meta, shrink)
    if window is not None:
        y0, x0, sh, sw = window
        o = dataclasses.replace(o, top=o.top - y0, left=o.left - x0)
    got = _decode_yuv_packed(buf, shrink, sh, sw, frame_cache, source_digest,
                             window)
    if got is None:
        return None
    packed, hb, wb = got
    wm = _fetch_watermark(name, o, watermark_fetcher)
    plan = plan_operation(name, o, sh, sw, meta.orientation, 3,
                          watermark_rgba=wm)
    if not plan.stages:
        # identity chain (e.g. /convert jpeg->jpeg quality change): planes
        # go straight back to the raw encoder — no device round-trip at all
        from imaginary_tpu.engine.executor import note_placement

        note_placement("device")
        planes = codecs.unpack_planes(packed, sh, sw, hb, wb)
        out = _encode(planes, o, _encode_type(o, ImageType.JPEG))
    else:
        wrapped = wrap_plan_yuv420(plan, sh, sw)
        result = _run_stages(packed, wrapped, runner)
        out = _encode(result, o, _encode_type(o, ImageType.JPEG))
    return _carry_metadata(buf, o.strip_metadata, out, not o.no_rotation,
                           plan.out_w, plan.out_h)


# Luma pixels a decode window keeps beyond the area on each side: the
# device's centred chroma upsample (ops/stages.FromYuv420Spec) reads one
# chroma neighbour, two luma pixels, past every pixel it rebuilds.
_WINDOW_MARGIN = 2
# Window edges fall on iMCU rows and columns (16 px for 4:2:0).
_WINDOW_ALIGN = 16


def _pick_window(name: str, o: ImageOptions, meta, shrink: int):
    """The (y0, x0, wh, ww) luma window of the source an /extract reads,
    or None to decode the whole frame.

    Only an unscaled /extract whose area is the first thing its plan
    touches qualifies: no EXIF orientation to apply, no rotate, flip or
    flop. The window is the area widened by _WINDOW_MARGIN where the frame
    allows and aligned outward to _WINDOW_ALIGN, so every pixel inside the
    area comes out of the device exactly as from the whole frame. An area
    outside the frame gets no window: the plan refuses it as before."""
    if name != "extract" or shrink != 1:
        return None
    if (meta.orientation > 1 and not o.no_rotation) or o.rotate or o.flip or o.flop:
        return None
    fh, fw = meta.height, meta.width
    top, left, ah, aw = o.top, o.left, o.area_height, o.area_width
    if ah <= 0 or aw <= 0 or top < 0 or left < 0 or top + ah > fh or left + aw > fw:
        return None
    a = _WINDOW_ALIGN
    y0 = max(0, top - _WINDOW_MARGIN) // a * a
    x0 = max(0, left - _WINDOW_MARGIN) // a * a
    y1 = min(fh, -(-(top + ah + _WINDOW_MARGIN) // a) * a)
    x1 = min(fw, -(-(left + aw + _WINDOW_MARGIN) // a) * a)
    if (y1 - y0, x1 - x0) == (fh, fw) or not codecs.yuv420_window_supported():
        return None
    return y0, x0, y1 - y0, x1 - x0


def _pick_shrink(name: str, buf: bytes, o: ImageOptions, meta=None) -> int:
    """JPEG shrink-on-load denominator for this request (1 = full decode).

    A header-only probe supplies source dims/orientation; the planner then
    proves (by re-planning) that decoding at 1/N preserves the output —
    avoiding decoding/moving up to 64x the pixels the chain will
    immediately throw away. Applies to JPEG (DCT scaling) and SVG (vector
    render straight into the 1/N box). The web layer passes its
    resolution-guard probe as `meta` so no second header parse happens."""
    from imaginary_tpu.imgtype import determine_image_type

    if determine_image_type(buf) not in (ImageType.JPEG, ImageType.SVG):
        return 1
    try:
        if meta is None:
            meta = codecs.probe_fast(buf)
        return choose_decode_shrink(name, o, meta.height, meta.width,
                                    meta.orientation, max(3, meta.channels))
    except ImageError:
        return 1


def process_pipeline(
    buf: bytes,
    o: ImageOptions,
    watermark_fetcher: Optional[WatermarkFetcher] = None,
    runner=None,
    meta=None,
    frame_cache=None,
    source_digest=None,
) -> ProcessedImage:
    """Fused multi-op pipeline (ref: Pipeline, image.go:379-410).

    All ops' stages concatenate into ONE device program; `ignore_failure`
    skips an op whose planning fails (the reference skips ops whose
    execution fails — planning is where our validation happens).
    """
    if not o.operations:
        raise new_error("Missing pipeline operations", 400)
    if len(o.operations) > MAX_PIPELINE_OPERATIONS:
        raise new_error(f"Maximum pipeline operations ({MAX_PIPELINE_OPERATIONS}) exceeded", 400)

    from imaginary_tpu.imgtype import determine_image_type

    src_type = determine_image_type(buf)
    if meta is None and src_type is ImageType.JPEG:
        try:
            meta = codecs.probe_fast(buf)
        except ImageError:
            meta = None  # decode below raises the user-facing error

    # Shrink-on-load keyed to the FIRST op: its planner proof guarantees the
    # op's output dims are unchanged at 1/N decode, and every later op sees
    # only that output — so the whole pipeline's geometry is preserved while
    # the decode (and the first device stage) touch up to 64x fewer pixels.
    shrink = 1
    first = o.operations[0]
    if first.name in OPERATION_NAMES:
        try:
            shrink = _pick_shrink(first.name, buf, build_params_from_operation(first), meta)
        except Exception:
            shrink = 1

    # The packed transport only pays off when the OUTPUT is JPEG too: a
    # mid-pipeline type switch would add a pointless chroma-subsample
    # generation and forfeit the raw encoder, so any op requesting a
    # non-JPEG type keeps the whole request on the RGB path.
    ops_keep_jpeg = all(
        (op.params or {}).get("type") in (None,) + _JPEG_TYPE_NAMES
        for op in o.operations
    )
    if ops_keep_jpeg and _dct_eligible(src_type, meta, o):
        sh = -(-meta.height // shrink)
        sw = -(-meta.width // shrink)
        got = _decode_dct_packed(buf, shrink, frame_cache, source_digest)
        if got is not None and (got[1], got[2]) == (sh, sw):
            packed, _h2, _w2, layout, fkey = got
            combined, final_o, target, rotated, strip = _build_pipeline_plan(
                o, sh, sw, meta.orientation, 3, ImageType.JPEG, watermark_fetcher
            )
            # identity chains fall through: the yuv path below serves them
            # straight from raw planes with no device round-trip at all
            if combined.stages:
                q = final_o.quality if final_o.quality > 0 else 80
                wrapped = wrap_plan_dct(combined, meta.height, meta.width,
                                        shrink, frame_key=fkey, layout=layout,
                                        egress=_pick_egress(final_o, target),
                                        egress_quality=q)
                result = _run_stages(packed, wrapped, runner)
                out = _encode(result, final_o, target)
                return _carry_metadata(buf, strip, out, rotated,
                                       combined.out_w, combined.out_h)

    if ops_keep_jpeg and _yuv_eligible(src_type, meta, o):
        sh = -(-meta.height // shrink)
        sw = -(-meta.width // shrink)
        got = _decode_yuv_packed(buf, shrink, sh, sw, frame_cache,
                                 source_digest)
        if got is not None:
            packed, hb, wb = got
            combined, final_o, target, rotated, strip = _build_pipeline_plan(
                o, sh, sw, meta.orientation, 3, ImageType.JPEG, watermark_fetcher
            )
            if not combined.stages:
                from imaginary_tpu.engine.executor import note_placement

                note_placement("device")
                planes = codecs.unpack_planes(packed, sh, sw, hb, wb)
                out = _encode(planes, final_o, target)
            else:
                wrapped = wrap_plan_yuv420(combined, sh, sw)
                result = _run_stages(packed, wrapped, runner)
                out = _encode(result, final_o, target)
            return _carry_metadata(buf, strip, out, rotated,
                                   combined.out_w, combined.out_h)

    d = _decode_cached(buf, shrink, frame_cache, source_digest)
    combined, final_o, target, rotated, strip = _build_pipeline_plan(
        o, d.array.shape[0], d.array.shape[1], d.orientation,
        d.array.shape[2], d.type, watermark_fetcher,
    )
    arr = _run_stages(d.array, combined, runner)
    out = _encode(arr, final_o, target)
    return _carry_metadata(buf, strip, out, rotated,
                           combined.out_w, combined.out_h)


def _build_pipeline_plan(o, cur_h, cur_w, orientation, channels, src_type,
                         watermark_fetcher):
    """Concatenate every op's stages into one combined plan (pure host
    math — no pixels needed, so both transports share it).

    Also reports whether the EXIF rotation was actually APPLIED by the
    chain: the first successfully-planned op consumes the orientation, and
    only when its own no_rotation is unset does it plan the rotate stages —
    the metadata carry must reset the Orientation tag exactly when the
    pixels were rotated, no more, no less.
    """
    src_h0, src_w0 = cur_h, cur_w
    stages: list = []
    final_o = o
    target = _encode_type(o, src_type)
    orientation_applied = False
    # stripmeta on ANY op (or top-level) strips: the reference re-encodes
    # per op, so a mid-chain StripMetadata permanently removes metadata —
    # and an explicit strip request must never leak EXIF/GPS
    strip = o.strip_metadata
    for i, op in enumerate(o.operations):
        if op.name not in OPERATION_NAMES:  # info/pipeline are not nestable
            raise new_error(f"Unsupported operation: {op.name}", 400)
        try:
            op_opts = build_params_from_operation(op)
        except Exception as e:
            raise new_error(f"pipeline operation {i+1} failed: {e}", 400) from None
        try:
            wm = _fetch_watermark(op.name, op_opts, watermark_fetcher)
            plan = plan_operation(
                op.name, op_opts, cur_h, cur_w, orientation, channels, watermark_rgba=wm
            )
        except ImageError:
            if op.ignore_failure:
                continue
            raise
        if orientation > 1 and not op_opts.no_rotation:
            orientation_applied = True
        strip = strip or op_opts.strip_metadata
        stages.extend(plan.stages)
        cur_h, cur_w = plan.out_h, plan.out_w
        orientation = 0  # EXIF applies once; later ops see upright pixels
        final_o = op_opts
        if op_opts.type:
            target = _encode_type(op_opts, src_type)
    from imaginary_tpu.ops.plan import fuse_adjacent_shrinking_samples

    stages = fuse_adjacent_shrinking_samples(stages, src_h0, src_w0)
    return (ImagePlan(stages=stages, out_h=cur_h, out_w=cur_w), final_o,
            target, orientation_applied, strip)


def _fetch_watermark(name, o, fetcher) -> Optional[np.ndarray]:
    if name != "watermarkImage" or not o.image:
        return None
    if fetcher is None:
        raise new_error("Unable to retrieve watermark image: " + o.image, 400)
    try:
        return fetcher(o.image)
    except ImageError:
        raise
    except Exception:
        raise new_error("Unable to retrieve watermark image: " + o.image, 400) from None
