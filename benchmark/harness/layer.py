"""Arithmetic shared by the per-layer metric readers. Span means are taken
over the window's replies that were served by the device and completed
before the profiled sub-window began (the profiler slows the host while
it records, and the backlog drains for seconds after)."""

from __future__ import annotations

from harness import health

CHAIN_MODULE = "jit__run_chain"


def _replies(ctx) -> list:
    return [r for r in ctx.untraced if r["ok"] and r["spans"]]


def span_mean(ctx, names: tuple) -> float | None:
    """Mean per reply of the summed Server-Timing spans `names`."""
    recs = [r for r in _replies(ctx) if any(n in r["spans"] for n in names)]
    if not recs:
        return None
    return sum(sum(r["spans"].get(n, 0.0) for n in names) for r in recs) / len(recs)


def web_wait_ms(ctx) -> float | None:
    """Mean of the client's latency (sent to reply read) less the server's
    `total` span: body upload and read, the event loop, the handler pool."""
    recs = [r for r in _replies(ctx) if "total" in r["spans"]]
    if not recs:
        return None
    return sum((r["done"] - r["sent"]) * 1000.0 - r["spans"]["total"] for r in recs) / len(recs)


def chain_ms_per_item(ctx) -> float | None:
    """Device time of the chain programs in the profiled sub-window over the
    items the executor served in it."""
    if not ctx.trace or not ctx.profile:
        return None
    mod = ctx.trace["modules"].get(CHAIN_MODULE)
    items = health.executor_delta(ctx.profile["health_a"], ctx.profile["health_b"], "items")
    if not mod or items <= 0:
        return None
    return mod["seconds"] * 1000.0 / items


def device_idle_share(ctx) -> float | None:
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]


def lane_min_share(ctx) -> float | None:
    lanes = health.lane_items_delta(ctx.health_start, ctx.health_end)
    total = sum(lanes)
    if len(lanes) < 2 or total <= 0:
        return None
    return min(lanes) / total
