"""Set-up: native build if stale, inputs, server boot, warm-up."""

from harness import stats


def read(ctx):
    return ctx.setup_s
