"""Native C++ codec backend shim.

Wraps the `_imaginary_codecs` extension (imaginary_tpu/native/codecs.cpp —
libjpeg/libpng/libwebp/libtiff plus an in-tree GIF codec and palette
quantizer, all with the GIL released). Every DECODABLE/ENCODABLE raster
format runs natively (SURVEY.md section 2.12: no Python stand-ins on the
pixel path); PIL appears only in probe(), where its header-only open
carries richer /info metadata (ICC/space) than the C parsers report.

Partial builds (native/build.py -DITPU_NO_WEBP, for hosts missing only
libwebp-dev) export FORMATS; formats absent from the build route to the
cv2/PIL backend per call, so a partial native build is strictly faster
than no native build, never less capable.
"""

from __future__ import annotations

import numpy as np

from imaginary_tpu.codecs import CodecError, DecodedImage, EncodeOptions, ImageMetadata
from imaginary_tpu.imgtype import ImageType

NAME = "native"

try:
    from imaginary_tpu.native import _imaginary_codecs as _ext
except ImportError:  # pragma: no cover - extension not built
    _ext = None

# Resample-only fallback module (build.py -DITPU_RESAMPLE_ONLY): hosts
# without the codec dev headers still get the native spill-path resize.
try:
    from imaginary_tpu.native import _imaginary_resample as _rext
except ImportError:
    _rext = None


def available() -> bool:
    return _ext is not None and getattr(_ext, "ABI", 0) >= 3


def _resample_ext():
    if _ext is not None and hasattr(_ext, "resize_separable"):
        return _ext
    if _rext is not None and hasattr(_rext, "resize_separable"):
        return _rext
    return None


def resample_available() -> bool:
    """True when SOME native module carries resize_separable (the full
    codec extension or the dependency-free resample-only build)."""
    return _resample_ext() is not None


def resize_separable(arr: np.ndarray, dst_h: int, dst_w: int,
                     kernel: str) -> np.ndarray:
    """Separable precomputed-tap resize of an HWC uint8 array, GIL
    released. Kernel semantics match the device sampling matrix
    (ops/stages.sample_matrix): per-axis stretch, edge-clamp
    renormalization, round-half-up to uint8."""
    ext = _resample_ext()
    if ext is None:
        raise CodecError("native resampler not built", 500)
    h, w, c = arr.shape
    out = ext.resize_separable(np.ascontiguousarray(arr), h, w, c,
                               dst_h, dst_w, kernel)
    return np.frombuffer(out, dtype=np.uint8).reshape(dst_h, dst_w, c)


_ALL_RASTER_TYPES = {ImageType.JPEG, ImageType.PNG, ImageType.WEBP,
                     ImageType.GIF, ImageType.TIFF}


def _supported_types():
    """Formats THIS build carries. Full builds export all five; a
    -DITPU_NO_WEBP build reports itself via FORMATS and the absent
    format routes to the cv2/PIL fallback per call."""
    if _ext is None:
        return set()
    fmts = getattr(_ext, "FORMATS", None)
    if not fmts:  # pre-FORMATS full build
        return set(_ALL_RASTER_TYPES)
    names = set(fmts.split(","))
    return {t for t in _ALL_RASTER_TYPES if t.value in names}


_NATIVE_TYPES = _supported_types()


def _fallback_backend():
    try:
        from imaginary_tpu.codecs import cv2_backend

        return cv2_backend
    except Exception:  # pragma: no cover - cv2 not installed
        from imaginary_tpu.codecs import pil_backend

        return pil_backend


def decode(buf: bytes, t: ImageType, shrink: int = 1) -> DecodedImage:
    if t not in _NATIVE_TYPES:
        if t in _ALL_RASTER_TYPES:  # absent from this PARTIAL build only
            return _fallback_backend().decode(buf, t, shrink)
        raise CodecError(f"Cannot decode image: unsupported format {t.value}", 400)
    denom = shrink if (t is ImageType.JPEG and shrink in (2, 4, 8)) else 1
    try:
        pixels, h, w, c, orientation, has_alpha = _ext.decode(buf, t.value, denom)
    except Exception as e:
        raise CodecError(f"Cannot decode image: {e}", 400) from None
    # the extension always emits 3- or 4-channel RGB(A)
    arr = np.frombuffer(pixels, dtype=np.uint8).reshape(h, w, c)
    return DecodedImage(array=arr, type=t, orientation=orientation, has_alpha=bool(has_alpha))


def encode(arr: np.ndarray, opts: EncodeOptions) -> bytes:
    t = opts.type
    if t not in _NATIVE_TYPES:
        if t in _ALL_RASTER_TYPES:  # absent from this PARTIAL build only
            return _fallback_backend().encode(arr, opts)
        raise CodecError(f"Cannot encode image: unsupported format {t.value}", 400)
    arr = np.ascontiguousarray(arr)
    h, w, c = arr.shape
    try:
        # 'y*' takes the array via the buffer protocol: no tobytes() copy
        return _ext.encode(
            arr, h, w, c, t.value,
            opts.effective_quality(), opts.effective_compression(),
            1 if opts.interlace else 0,
            1 if opts.palette else 0, max(0, opts.speed),
        )
    except Exception as e:
        raise CodecError(f"Cannot encode image: {e}", 400) from None


def probe(buf: bytes, t: ImageType) -> ImageMetadata:
    # PIL's probe is header-only (no pixel decode) and carries richer
    # metadata (colour space, ICC flag) — it serves /info; the native probe
    # is the fallback here and the PRIMARY for probe_fast below.
    from imaginary_tpu.codecs import pil_backend

    if t not in _NATIVE_TYPES:
        return pil_backend.probe(buf, t)
    try:
        return pil_backend.probe(buf, t)
    except CodecError:
        pass
    return _native_probe(buf, t)


def _native_probe(buf: bytes, t: ImageType) -> ImageMetadata:
    try:
        got = _ext.probe(buf, t.value)
    except Exception as e:
        raise CodecError(f"Cannot retrieve image metadata: {e}", 400) from None
    subsampling = ""
    if len(got) >= 6:  # ABI 2 reports JPEG chroma subsampling
        w, h, c, has_alpha, orientation, subsampling = got[:6]
    else:  # pragma: no cover - stale extension build
        w, h, c, has_alpha, orientation = got
    return ImageMetadata(
        width=w, height=h, type=t.value, space="srgb",
        has_alpha=bool(has_alpha), has_profile=False,
        channels=c, orientation=orientation, subsampling=subsampling,
    )


def yuv420_supported() -> bool:
    """True when the built extension carries the packed-YUV420 transport
    entry points (ABI 2+)."""
    return _ext is not None and hasattr(_ext, "decode_yuv420")


def yuv420_window_supported() -> bool:
    """True when decode_yuv420 takes a window (ABI 5+)."""
    return _ext is not None and getattr(_ext, "ABI", 0) >= 5


def decode_yuv420(buf: bytes, shrink: int, hb: int, wb: int, window=None):
    """Decode a 4:2:0 JPEG straight into the packed transport layout.

    Returns (packed [hb + hb/2, wb, 1] uint8, h, w, orientation); raises
    CodecError("not-420") when the source isn't plain 4:2:0 YCbCr — callers
    fall back to the RGB decode path. window = (y0, x0, wh, ww) in luma
    pixels (unscaled decodes only) packs just that area of the frame and
    stops the decode below it; h, w are then the window's dims.
    """
    denom = shrink if shrink in (2, 4, 8) else 1
    try:
        packed, h, w, orientation = _ext.decode_yuv420(
            buf, denom, hb, wb, *(window or ()))
    except Exception as e:
        raise CodecError(f"Cannot decode image: {e}", 400) from None
    arr = np.frombuffer(packed, dtype=np.uint8).reshape(hb + hb // 2, wb, 1)
    return arr, h, w, orientation


def encode_yuv420(y: np.ndarray, u: np.ndarray, v: np.ndarray,
                  quality: int, progressive: bool) -> bytes:
    """Raw-plane JPEG encode (no host color conversion / subsampling)."""
    h, w = y.shape[:2]
    try:
        return _ext.encode_yuv420(
            np.ascontiguousarray(y), np.ascontiguousarray(u),
            np.ascontiguousarray(v), h, w, quality, 1 if progressive else 0,
        )
    except Exception as e:
        raise CodecError(f"Cannot encode image: {e}", 400) from None


def arena_stats():
    """Scratch-arena counters from whichever native module carries the
    arena ABI (full codecs ABI 4+, resample-only ABI 2+), or None when
    neither does — callers treat None as 'feature absent' so a stale
    prebuilt .so keeps serving without the counters."""
    for ext in (_ext, _rext):
        fn = getattr(ext, "arena_stats", None)
        if fn is not None:
            try:
                return fn()
            except Exception:  # pragma: no cover - defensive
                return None
    return None


def set_arena_cap(mb: float) -> bool:
    """Set the per-thread scratch-arena cap in MB (0 = unlimited) on every
    native module that supports it. True when at least one accepted."""
    ok = False
    for ext in (_ext, _rext):
        fn = getattr(ext, "set_arena_cap", None)
        if fn is not None:
            try:
                fn(float(mb))
                ok = True
            except (TypeError, ValueError, OverflowError):
                continue  # bad value for one module must not block the rest
    return ok


def probe_fast(buf: bytes, t: ImageType) -> ImageMetadata:
    """Dims/orientation-only probe on the request hot path (shrink-on-load
    selection needs nothing else). The C++ header parser runs with the GIL
    released and skips PIL's lazy-open machinery; PIL remains the fallback
    and the rich /info probe."""
    if t in _NATIVE_TYPES:
        try:
            return _native_probe(buf, t)
        except CodecError:
            pass
    from imaginary_tpu.codecs import pil_backend

    return pil_backend.probe(buf, t)
