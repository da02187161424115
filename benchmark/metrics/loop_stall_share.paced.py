"""Share of the untraced part of the window the event loop lost to stalls of 50 ms or more (paced cells)."""

from harness import inside


def read(ctx):
    return inside.loop_stall_share(ctx)
