"""Device time of the chain programs per item served, profiled sub-window (backfill cells)."""

from harness import layer


def read(ctx):
    return layer.chain_ms_per_item(ctx)
