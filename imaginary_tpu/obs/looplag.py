"""Event-loop lag sampler: the one host signal no other surface covers.

A wedged or merely busy asyncio loop delays *every* request's admission,
header flush and response write, yet none of the stage ledgers see it —
they time work, not the gaps between scheduling opportunities. This
probe measures the gap directly: sleep a fixed interval, compare
`loop.time()` drift against the requested interval, and the overshoot IS
the scheduling lag every coroutine experienced in that window.

Surfaces:
  * `imaginary_tpu_event_loop_lag_seconds` histogram (every sample);
  * `imaginary_tpu_event_loop_lag_last_seconds` / `_max_seconds` gauges
    rendered off the `eventLoop` health block (the Registry is
    histogram/counter-native, so point-in-time values ride the same
    stats->gauge path every other block uses);
  * a `loop_lag_ms` stamp on wide events when the last sample exceeded
    WIDE_EVENT_THRESHOLD_MS — a slow request during a lag spike should
    carry the evidence on the event itself;
  * cumulative `stalls` and `stallMsSum` in the `eventLoop` block: the
    samples at or over WIDE_EVENT_THRESHOLD_MS and their summed lag, so
    a window's share of time lost to stalls is a difference of two
    snapshots. At a 50 ms interval any process stall over 100 ms shows
    as a lag of at least 50 ms.

Always on when the server runs (constant ~20 wakeups/s, no config
surface); state is module-level like TIMES/COPIES — one loop per
serving process.
"""

from __future__ import annotations

import asyncio
import threading

from imaginary_tpu.obs.histogram import REGISTRY

_INTERVAL_S = 0.05
# Wide events only carry the stamp when the loop was measurably wedged:
# scheduling noise below this is normal CPython jitter.
WIDE_EVENT_THRESHOLD_MS = 50.0

# Sub-second buckets: lag is scheduler noise (sub-ms) or a wedge
# (tens of ms to seconds) — the default latency ladder's shape fits.
LOOP_LAG_SECONDS = REGISTRY.histogram(
    "imaginary_tpu_event_loop_lag_seconds",
    "Event-loop scheduling lag per 0.05s probe, in seconds.",
)

_lock = threading.Lock()
_state = {"last_ms": 0.0, "max_ms": 0.0, "samples": 0, "stalls": 0,
          "stall_ms": 0.0}


async def _run(interval: float) -> None:
    loop = asyncio.get_running_loop()
    while True:
        t0 = loop.time()
        await asyncio.sleep(interval)
        lag = max(0.0, loop.time() - t0 - interval)
        LOOP_LAG_SECONDS.observe(lag)
        lag_ms = lag * 1000.0
        with _lock:
            _state["last_ms"] = lag_ms
            if lag_ms > _state["max_ms"]:
                _state["max_ms"] = lag_ms
            _state["samples"] += 1
            if lag_ms >= WIDE_EVENT_THRESHOLD_MS:
                _state["stalls"] += 1
                _state["stall_ms"] += lag_ms


def start(interval: float = _INTERVAL_S):
    """Spawn the probe task on the running loop (call from on_startup).
    Returns the task for `stop`."""
    return asyncio.get_event_loop().create_task(
        _run(interval), name="looplag-probe")


def stop(task) -> None:
    if task is not None:
        task.cancel()


def last_ms() -> float:
    with _lock:
        return _state["last_ms"]


def snapshot():
    """The `eventLoop` health block, or None before the first sample
    (a process that never ran a loop reports nothing rather than
    zeros that look like a measurement)."""
    with _lock:
        if _state["samples"] == 0:
            return None
        return {
            "lagMsLast": round(_state["last_ms"], 3),
            "lagMsMax": round(_state["max_ms"], 3),
            "samples": _state["samples"],
            "stalls": _state["stalls"],
            "stallMsSum": round(_state["stall_ms"], 3),
        }
