"""The event loop's delay in resuming a handler whose pool thread returned: the resume span, mean per reply (paced cells)."""

from harness import layer


def read(ctx):
    return layer.span_mean(ctx, ("resume",))
