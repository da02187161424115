"""imaginary's text watermark: `text` set in the `font` spec (pango's
"sans 12" at 72 dpi: DejaVu Sans at 12 px, the family the deploy image
carries), white unless `color` says otherwise, on a transparent block
with 2 px of padding, the block tiled over the whole frame from
(margin, margin), blended at `opacity` (0.25 when unset).

The block is rendered here with PIL's FreeType binding from the font
file on the host, not taken from the program."""

from __future__ import annotations

import glob

import numpy as np
from PIL import Image, ImageDraw, ImageFont

FONT_GLOBS = ("/usr/share/fonts/**/DejaVuSans.ttf",
              "/usr/local/share/fonts/**/DejaVuSans.ttf")


def _font(px: int):
    for pattern in FONT_GLOBS:
        for path in glob.glob(pattern, recursive=True):
            return ImageFont.truetype(path, px)
    raise RuntimeError("DejaVu Sans is not installed: the watermark oracle needs it")


def block(text: str, px: int, rgb: tuple) -> np.ndarray:
    """RGBA float64 block holding one line of text."""
    fnt = _font(px)
    draw = ImageDraw.Draw(Image.new("RGBA", (8, 8)))
    asc, desc = fnt.getmetrics()
    bw = max(8, int(draw.textlength(text, font=fnt) + 4))
    bh = max(8, asc + desc + 2 + 4)
    img = Image.new("RGBA", (bw, bh), (0, 0, 0, 0))
    ImageDraw.Draw(img).text((2, 2), text, font=fnt, fill=tuple(rgb) + (255,))
    return np.asarray(img, np.float64)


def expected(x, q: dict, ops):
    size = 12.0
    words = (q.get("font") or "sans 12").split()
    if words:
        try:
            size = float(words[-1])
        except ValueError:
            pass
    dpi = int(q.get("dpi", 0) or 72)
    px = max(6, int(round(size * dpi / 72.0)))
    color = q.get("color") or (255, 255, 255)
    blk = block(q["text"], px, tuple(int(c) for c in color[:3]))
    x = ops.load(x)
    h, w = x.shape[:2]
    bh, bw = min(blk.shape[0], h), min(blk.shape[1], w)
    blk = blk[:bh, :bw]
    margin = int(q.get("margin", 0))
    ys = np.remainder(np.arange(h) - margin, bh)
    xs = np.remainder(np.arange(w) - margin, bw)
    canvas = blk[ys][:, xs]
    opacity = float(q.get("opacity", 0) or 0.25)
    alpha = canvas[..., 3:4] / 255.0 * min(max(opacity, 0.0), 1.0)
    return np.asarray(x, np.float64) * (1.0 - alpha) + canvas[..., :3] * alpha
