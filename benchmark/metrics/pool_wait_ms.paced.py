"""Wait for a host-pool thread: the pool_wait span, mean per reply (paced cells)."""

from harness import layer


def read(ctx):
    return layer.span_mean(ctx, ("pool_wait",))
