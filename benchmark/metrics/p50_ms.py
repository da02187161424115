"""Median latency from the due time, over every request due in the window; a failure is +inf."""

from harness import stats


def read(ctx):
    return stats.pctl(stats.latencies_from_due(ctx.records, ctx.seconds), 0.50)
