"""Wall ms of one executor launch (stack, H2D, dispatch), untraced part of the window (backfill cells)."""

from harness import inside


def read(ctx):
    return inside.launch_ms(ctx)
