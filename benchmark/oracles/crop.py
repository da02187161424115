"""imaginary's /crop: scale to cover the box (never enlarging), then take
the centred window (gravity centre). A JPEG is first decoded at the
smallest DCT scale that still covers the cover size (shrink-on-load)."""

from __future__ import annotations

import numpy as np


def expected(src: np.ndarray, q: dict, ops) -> np.ndarray:
    sh, sw = ops.size(src)
    tw, th = int(q.get("width", 0)) or sw, int(q.get("height", 0)) or sh
    scale = min(max(tw / sw, th / sh), 1.0)
    rw, rh = max(1, round(sw * scale)), max(1, round(sh * scale))
    cover = ops.resample(ops.load(src, rw, rh), rw, rh)
    ew, eh = min(tw, rw), min(th, rh)
    top, left = (rh - eh) // 2, (rw - ew) // 2
    return cover[top:top + eh, left:left + ew]
