"""Share of the profiled sub-window with no op on the device (closed cells)."""

from harness import layer


def read(ctx):
    return layer.device_idle_share(ctx)
