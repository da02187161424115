"""Snapshots of the program's /health and the arithmetic across a window.

Only cumulative fields are sound across a window: counters, and each
stage's `count` and `mean_ms` (so the sum count * mean_ms). The stage
p50/p99 come from a ring since boot and are never used here."""

from __future__ import annotations

import json
import urllib.request


def fetch(base: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(f"{base}/health", timeout=timeout) as r:
        return json.loads(r.read())


def executor_delta(h0: dict, h1: dict, key: str) -> float:
    return h1.get("executor", {}).get(key, 0) - h0.get("executor", {}).get(key, 0)


def stage_delta(h0: dict, h1: dict, stage: str) -> tuple:
    """(count, summed ms) of one stage between two snapshots."""
    def acc(h):
        s = h.get("stageTimesMs", {}).get(stage) or {}
        return s.get("count", 0), s.get("count", 0) * s.get("mean_ms", 0.0)
    c0, t0 = acc(h0)
    c1, t1 = acc(h1)
    return c1 - c0, t1 - t0


def lane_items_delta(h0: dict, h1: dict) -> list:
    """served_items of each lane between two snapshots (lanes mode only)."""
    l0 = h0.get("executor", {}).get("lanes") or []
    l1 = h1.get("executor", {}).get("lanes") or []
    if not l1 or len(l0) != len(l1):
        return []
    return [b.get("served_items", 0) - a.get("served_items", 0) for a, b in zip(l0, l1)]
