"""Seeded photo-like PNGs: `count` distinct RGB frames of `width` x
`height`, deflated at `compression` (zlib level) by cv2. PNG is lossless,
so the generated pixels are the source the oracle starts from."""

from __future__ import annotations

import cv2

from harness.imaging import many, texture


def generate(p: dict, seed: int) -> list:
    def one(i):
        rgb = texture(p["width"], p["height"], seed, i, p["roughness"], p["contrast"])
        ok, buf = cv2.imencode(".png", rgb[..., ::-1],
                               [cv2.IMWRITE_PNG_COMPRESSION, p["compression"]])
        if not ok:
            raise RuntimeError("PNG encode failed")
        return {"data": buf.tobytes(), "mime": "image/png", "pixels": rgb}
    return many(one, range(p["count"]))
