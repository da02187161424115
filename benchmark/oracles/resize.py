"""imaginary's /resize: with width and height the frame is scaled to fit
inside the box and embedded, centred, with the edge pixels copied outward
(bimg's builder default extend); with one dimension it scales to it.
Reduction only: a frame is never enlarged. A JPEG is first decoded at the
smallest DCT scale that still covers the output (libvips' shrink-on-load)."""

from __future__ import annotations

import numpy as np


def expected(src: np.ndarray, q: dict, ops) -> np.ndarray:
    sh, sw = ops.size(src)
    width, height = int(q.get("width", 0)), int(q.get("height", 0))
    if width and height:
        scale = min(width / sw, height / sh)
    else:
        scale = width / sw if width else height / sh
    scale = min(scale, 1.0)
    rw, rh = max(1, round(sw * scale)), max(1, round(sh * scale))
    out = ops.resample(ops.load(src, rw, rh), rw, rh)
    cw, ch = width or rw, height or rh
    if (cw, ch) == (rw, rh):
        return out
    top, left = max(0, (ch - rh) // 2), max(0, (cw - rw) // 2)
    return np.pad(out, ((top, ch - rh - top), (left, cw - rw - left), (0, 0)), mode="edge")
