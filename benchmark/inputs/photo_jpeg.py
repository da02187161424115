"""Seeded photo-like JPEGs: `count` distinct frames of `width` x `height`,
coded at `quality` by libjpeg-turbo (cv2), 4:2:0 as cameras and exports
write them. `roughness` and `contrast` set the detail and so the coded
size, which decode cost follows."""

from __future__ import annotations

import cv2

from harness.imaging import many, texture


def generate(p: dict, seed: int) -> list:
    def one(i):
        rgb = texture(p["width"], p["height"], seed, i, p["roughness"], p["contrast"])
        ok, buf = cv2.imencode(".jpg", rgb[..., ::-1],
                               [cv2.IMWRITE_JPEG_QUALITY, p["quality"]])
        if not ok:
            raise RuntimeError("JPEG encode failed")
        # the oracle decodes the bytes itself: the coded frame is the source
        return {"data": buf.tobytes(), "mime": "image/jpeg", "pixels": None}
    return many(one, range(p["count"]))
