"""The executor's drain span, mean per reply (backfill cells)."""

from harness import layer


def read(ctx):
    return layer.span_mean(ctx, ("drain",))
