"""The program's encode span, mean per reply (backfill cells)."""

from harness import layer


def read(ctx):
    return layer.span_mean(ctx, ("encode",))
