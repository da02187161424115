"""Percentiles and spreads."""

from __future__ import annotations

import math


def pctl(values, q: float) -> float:
    """Nearest-rank percentile (bench_util.pctl's rule, unrounded):
    the value at index int(q * (n - 1)) of the sorted list; inf sorts last."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[min(len(s) - 1, int(q * (len(s) - 1)))]


def latencies_from_due(records, close_s: float) -> list:
    """Latency in ms of every request due before `close_s` (seconds on the
    run's clock), from when it was due to when its reply was complete. A
    request that failed, or never completed, is a miss at +inf."""
    out = []
    for r in records:
        if r["due"] >= close_s:
            continue
        if r["ok"] and r["done"] is not None:
            out.append((r["done"] - r["due"]) * 1000.0)
        else:
            out.append(math.inf)
    return out
