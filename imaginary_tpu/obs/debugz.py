"""Runtime introspection for the gated /debugz endpoint.

Everything here reads live process state; nothing mutates it except the
one-shot profiler capture. The endpoint is OFF by default
(`--enable-debug` / IMAGINARY_TPU_DEBUG) because a task dump and cache
summary are an information surface an internet-facing deployment must
opt into.

SLOW is the slow-request exemplar ring: the trace middleware notes every
completed request's wide event; /debugz reports the N slowest of the
recent window with their full span timelines — the exemplars that turn a
histogram tail into a diagnosis.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
from collections import deque

_RING_KEEP = 256  # recent completed requests retained for exemplar mining


class SlowRing:
    """Ring of recent request events, mined for the slowest exemplars."""

    def __init__(self, keep: int = _RING_KEEP):
        self._ring: deque = deque(maxlen=keep)
        self._lock = threading.Lock()

    def note(self, event: dict) -> None:
        with self._lock:
            self._ring.append(event)

    def slowest(self, n: int = 32) -> list:
        with self._lock:
            recent = list(self._ring)
        recent.sort(key=lambda e: e.get("duration_ms", 0.0), reverse=True)
        return recent[:n]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


SLOW = SlowRing()


def task_dump(limit: int = 200) -> list:
    """Summaries of every live asyncio task on the current loop."""
    try:
        tasks = asyncio.all_tasks()
    except RuntimeError:  # no running loop (unit-test context)
        return []
    out = []
    for t in list(tasks)[:limit]:
        frames = []
        try:
            for f in t.get_stack(limit=3):
                frames.append(
                    f"{f.f_code.co_filename}:{f.f_lineno} {f.f_code.co_name}"
                )
        # itpu: allow[ITPU004] best-effort diagnostic: a task completing mid-walk may refuse get_stack
        except Exception:
            pass
        out.append({
            "name": t.get_name(),
            "done": t.done(),
            "stack": frames,
        })
    return out


def debug_payload(service) -> dict:
    """The /debugz JSON body: tasks, executor + host-pool occupancy,
    cache tier summary, slow-request exemplars."""
    from imaginary_tpu import failpoints

    payload: dict = {
        "pid": os.getpid(),
        "threads": threading.active_count(),
        "tasks": task_dump(),
        "slowest_requests": SLOW.slowest(32),
        # chaos harness state (spec + per-site hit/fired counters); the
        # control surface is the sibling /debugz/failpoints GET/PUT.
        # Deadline state per request rides the slow-ring events above
        # (deadline_budget_ms / deadline_remaining_ms / deadline_stages).
        "failpoints": failpoints.snapshot(),
    }
    # end-to-end byte-touch ledger (engine/timing.COPIES): service-free
    # because the ledger is process-wide — a debug dump of a bare worker
    # still shows what the host path copied
    from imaginary_tpu.engine.timing import COPIES

    payload["copies"] = COPIES.snapshot()
    # native codec scratch-arena counters; None (absent) when the built
    # extension predates the arena ABI
    try:
        from imaginary_tpu.codecs import native_backend

        arena = native_backend.arena_stats()
        if arena is not None:
            payload["arena"] = arena
    except Exception:  # itpu: allow[ITPU004] a debug payload never takes down /debugz
        pass
    if service is not None:
        payload["executor"] = service.executor.debug_snapshot()
        payload["executor_counters"] = service.executor.stats.to_dict()
        payload["host_pool"] = {
            "workers": service._pool_workers,
            "inflight": service._inflight,
            "service_ewma_ms": round(service._service_ewma_ms, 3),
            "estimated_queue_ms": round(service.estimated_queue_ms(), 3),
        }
        payload["cache"] = service.caches.to_dict()
        shm = service.caches.shm
        if shm is not None:
            # fleet shared cache: snapshot + file path + the whole epoch
            # table (diagnosing a fencing dispute wants every stamp, not
            # just this worker's)
            payload["fleet"] = shm.debug_snapshot()
        governor = getattr(service, "pressure", None)
        if governor is not None:
            # governor rung + sampled signals + the full recent
            # transition history (health shows the last 8; diagnosis of a
            # flapping ladder wants the whole ring)
            snap = governor.snapshot()
            snap["recent_transitions"] = list(governor._history)
            payload["pressure"] = snap
        qos = getattr(service, "qos", None)
        if qos is not None:
            # secret-free tenant table + per-class counters + live intake
            # depths (imaginary_tpu/qos/tenancy.py QosPolicy.snapshot);
            # api keys appear as COUNTS only
            payload["qos"] = qos.snapshot()
        slo = getattr(service, "slo", None)
        if slo is not None:
            # burn rates per route/window (obs/slo.py) — the same dict
            # /health serves, so the two surfaces cannot drift. Absent
            # with --slo-config unset: the block's presence IS the
            # armed/parity signal.
            payload["slo"] = slo.snapshot()
        cost = getattr(service, "cost", None)
        if cost is not None:
            # per-tenant cost windows + utilization + live bound_by
            # (obs/cost.py) — the same dict /health serves, so the two
            # surfaces cannot drift. Absent with --cost-attribution
            # unset: the block's presence IS the armed/parity signal.
            payload["capacity"] = cost.snapshot()
    return payload


async def profile_capture(query) -> tuple:
    """One-shot jax.profiler capture triggered from a live process:
    GET /debugz/profile?seconds=N starts a trace into ?dir= (defaulting
    to IMAGINARY_TPU_PROFILE_DIR), sleeps N seconds, stops it. Returns
    (json_body, http_status).

    The ?dir= override matters for the no-restart promise: the env var
    can only be set before boot (and when it IS set, cli.py starts a
    whole-serving-loop capture at boot — this trigger then reports 409
    until that capture is stopped at exit)."""
    trace_dir = query.get("dir") or os.environ.get(
        "IMAGINARY_TPU_PROFILE_DIR", "")
    if not trace_dir:
        return {
            "error": "no capture directory: pass ?dir= or export "
                     "IMAGINARY_TPU_PROFILE_DIR"
        }, 400
    try:
        seconds = float(query.get("seconds", "3"))
    except (TypeError, ValueError):
        return {"error": "seconds must be a number"}, 400
    seconds = min(max(seconds, 0.05), 120.0)
    from imaginary_tpu.engine import timing

    # starting and stopping block for a while (on one TPU v5e chip under
    # load, stopping a process's first 2 s capture took 4.4-4.8 s, a
    # second one 1.7-2.0 s): off the loop, so the server keeps serving
    # meanwhile. The reply says how long each took.
    t0 = time.monotonic()
    if not await asyncio.to_thread(timing.start_profiler, trace_dir):
        return {
            "error": "a profiler capture is already active (a process "
                     "booted with IMAGINARY_TPU_PROFILE_DIR traces its "
                     "whole serving loop)"
        }, 409
    start_s = time.monotonic() - t0
    try:
        await asyncio.sleep(seconds)
    finally:
        t1 = time.monotonic()
        await asyncio.to_thread(timing.stop_profiler)
    return {"profile_dir": trace_dir, "seconds": seconds,
            "start_s": round(start_s, 3),
            "stop_s": round(time.monotonic() - t1, 3)}, 200
