"""Pixel helpers of the yardstick: seeded photo-like texture, decode, and
the plain resampling, blur and compositing the oracles are built from.

Nothing here imports the program under test.
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor

import cv2
import numpy as np
from PIL import Image

# texture threads: cv2 and numpy release the GIL on whole frames
GEN_THREADS = 8


def texture(w: int, h: int, seed: int, index: int, roughness: float,
            contrast: float) -> np.ndarray:
    """Photo-like RGB uint8 frame: octaves of smooth noise from 256 px cells
    down to single pixels, amplitude falling by `roughness` per octave
    (a 1/f-like spectrum), mostly luminance with some colour. Its coded
    size grows with roughness and contrast, as a photo's does with detail."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, index])
    acc = None
    amp = 1.0
    cell = 256
    while cell >= 1:
        gh, gw = h // cell + 2, w // cell + 2
        g = rng.standard_normal((gh, gw, 3), dtype=np.float32)
        g *= 0.35
        g += rng.standard_normal((gh, gw, 1), dtype=np.float32)
        if cell > 1:
            kind = cv2.INTER_LINEAR if cell <= 4 else cv2.INTER_CUBIC
            g = cv2.resize(g, (gw * cell, gh * cell), interpolation=kind)
        g = g[:h, :w]
        if acc is None:
            acc = g * amp
        else:
            cv2.scaleAdd(g, amp, acc, dst=acc)
        amp *= roughness
        cell //= 2
    rgb = cv2.convertScaleAbs(acc, alpha=contrast, beta=128.0)
    return np.ascontiguousarray(rgb)


def many(fn, items):
    """fn over items on a few threads, results in order."""
    with ThreadPoolExecutor(GEN_THREADS) as pool:
        return list(pool.map(fn, items))


def decode(data: bytes) -> np.ndarray:
    """RGB uint8 of an encoded image, by PIL (libjpeg-turbo, libwebp, zlib)."""
    with Image.open(io.BytesIO(data)) as im:
        return np.asarray(im.convert("RGB"))


def image_size(data: bytes) -> tuple:
    """(format, width, height) from the header alone."""
    with Image.open(io.BytesIO(data)) as im:
        return im.format, im.width, im.height


def lanczos(src: np.ndarray, w: int, h: int) -> np.ndarray:
    """Lanczos-3 resample (support widened on reduction, as libvips' reduce),
    on float32 planes so nothing is rounded before the end."""
    planes = [np.asarray(Image.fromarray(src[..., c].astype(np.float32), "F")
                         .resize((w, h), Image.LANCZOS)) for c in range(src.shape[2])]
    return np.stack(planes, -1).astype(np.float64)


def gaussian_blur(x: np.ndarray, sigma: float) -> np.ndarray:
    """Separable gaussian, edges clamped, taps out to 4 sigma, float64."""
    radius = max(1, int(np.ceil(4.0 * sigma)))
    xs = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (xs / sigma) ** 2)
    k /= k.sum()
    x = np.asarray(x, np.float64)
    pad = np.pad(x, ((radius, radius), (0, 0), (0, 0)), mode="edge")
    y = sum(k[i] * pad[i:i + x.shape[0]] for i in range(k.size))
    pad = np.pad(y, ((0, 0), (radius, radius), (0, 0)), mode="edge")
    return sum(k[i] * pad[:, i:i + x.shape[1]] for i in range(k.size))


def to_u8(x: np.ndarray) -> np.ndarray:
    return np.clip(np.round(x), 0, 255).astype(np.uint8)
