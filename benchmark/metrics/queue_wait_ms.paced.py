"""The executor's batch_form plus dispatch_wait spans, mean per reply (paced cells)."""

from harness import layer


def read(ctx):
    return layer.span_mean(ctx, ("batch_form", "dispatch_wait"))
