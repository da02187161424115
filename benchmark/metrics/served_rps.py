"""Replies completed in the window with a 200 from the device, per second of the window."""

from harness import stats


def read(ctx):
    return sum(r["ok"] for r in ctx.records) / ctx.seconds
