"""imaginary's /blur: a gaussian of `sigma`, edges clamped. The reference
takes the whole gaussian (taps to 4 sigma); libvips cuts its mask where
it falls under min_ampl, which changes a pixel by well under a level at
the sigmas served here."""

from __future__ import annotations


def expected(src, q: dict, ops):
    return ops.blur(ops.load(src), float(q["sigma"]))
