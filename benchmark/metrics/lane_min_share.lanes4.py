"""Smallest lane's share of the items served in the window (four chips)."""

from harness import layer


def read(ctx):
    return layer.lane_min_share(ctx)
