"""95th percentile latency from the due time, over the requests due before
the profiled sub-window began (a failure is +inf): the tail of the paced
cells, which the host's stalls make too unsteady for an end-to-end bound."""

from harness import stats


def read(ctx):
    close = ctx.profile["interval"][0] if ctx.profile else ctx.seconds
    lat = stats.latencies_from_due(ctx.records, min(close, ctx.seconds))
    return stats.pctl(lat, 0.95) if lat else None
