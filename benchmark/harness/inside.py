"""Readers of what the program measures from inside, for the split of the
web wait: the client's latency less the server's own `request` span, and
the program's cumulative counters over the untraced part of the window.

The counters are read as a difference of two /health snapshots: the
window's first (`ctx.health_start`) and the one taken just before the
capture began (`ctx.profile["health_a"]`), the same replies the span
means read. A program that lacks a counter or span gives None, never an
error."""

from __future__ import annotations


def unseen_ms(ctx) -> float | None:
    """Mean per reply of the client's latency (sent to reply read) less
    the server's `request` span: the time the server process never sees
    (the wire, the socket queues, the client's own loop)."""
    recs = [r for r in ctx.untraced if r["ok"] and "request" in r["spans"]]
    if not recs:
        return None
    return sum((r["done"] - r["sent"]) * 1000.0 - r["spans"]["request"]
               for r in recs) / len(recs)


def _untraced_snapshots(ctx) -> tuple | None:
    if not ctx.profile:
        return None
    return ctx.health_start, ctx.profile["health_a"]


def launch_ms(ctx) -> float | None:
    """Mean wall ms of one launch (stack, H2D, dispatch): the executor's
    `launch_ms` over its `launches`."""
    snaps = _untraced_snapshots(ctx)
    if snaps is None:
        return None
    e0, e1 = (h.get("executor", {}) for h in snaps)
    if "launches" not in e0 or "launches" not in e1:
        return None
    n = e1["launches"] - e0["launches"]
    if n <= 0:
        return None
    return (e1["launch_ms"] - e0["launch_ms"]) / n


def loop_stall_share(ctx) -> float | None:
    """The event loop's summed stall lag (samples of 50 ms or more) over
    the server's own clock between the two snapshots."""
    snaps = _untraced_snapshots(ctx)
    if snaps is None:
        return None
    h0, h1 = snaps
    l0, l1 = h0.get("eventLoop") or {}, h1.get("eventLoop") or {}
    if "stallMsSum" not in l0 or "stallMsSum" not in l1:
        return None
    span_ms = (h1.get("uptime", 0.0) - h0.get("uptime", 0.0)) * 1000.0
    if span_ms <= 0:
        return None
    return (l1["stallMsSum"] - l0["stallMsSum"]) / span_ms
