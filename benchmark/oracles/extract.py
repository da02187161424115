"""imaginary's /extract: the area at (top, left) of areawidth x
areaheight, copied pixel for pixel."""

from __future__ import annotations

import numpy as np


def expected(src: np.ndarray, q: dict, ops) -> np.ndarray:
    top, left = int(q.get("top", 0)), int(q.get("left", 0))
    aw, ah = int(q["areawidth"]), int(q["areaheight"])
    return ops.load(src)[top:top + ah, left:left + aw].astype(np.float64)
