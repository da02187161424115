"""Client latency less the server's request span, mean per reply (backfill cells)."""

from harness import inside


def read(ctx):
    return inside.unseen_ms(ctx)
