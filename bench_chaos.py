#!/usr/bin/env python
"""Chaos soaks: concurrent serving traffic with injected faults
(`make chaos`). Two rows:

ROW 1 — flaky origin: arms
IMAGINARY_TPU_FAILPOINTS="source.fetch=error(0.2)" through the same env
path a production chaos drill would use (create_app reads it), then
drives the cache-off zipf hot-URL row with deadlines ON. Invariants —
the "only resilience you have is the resilience you exercise" check:

  * availability: with a 0.2 per-attempt fault rate and the default
    2-retry budget, per-request failure odds are 0.2^3 = 0.8% — the soak
    demands >= 95% 2xx.
  * honesty: every non-2xx is a well-formed 502/503/504, never a 500,
    a hang, or a truncated body.
  * boundedness: no request outlives the 10 s deadline + one tick.
  * rest state: the coalescer group map and the host-pool inflight
    ledger drain to zero after traffic stops.

ROW 2 — chip loss (ISSUE 6): mid-run, `device.chip_error[0]=error`
kills the primary device's fault domain. With >= 2 devices (the Makefile
runs this under XLA_FLAGS=--xla_force_host_platform_device_count=2; real
multi-chip hosts need no flag) dispatch fails over to the surviving
chip, the sick one quarantines ALONE, and after the fault clears the
background probe re-admits it within its cooldown. On a 1-device host
the row degrades to the PR 4 breaker -> host failover story and still
holds availability. Invariants: >= 95% 2xx, zero 5xx storm (500s == 0,
errors only from the breaker's pre-trip window), /health shows the
quarantine, and the device is HEALTHY again after re-admission.

ROW 4 — OOM storm (ISSUE 7): `device.oom=error(0.5)` makes half of all
device launches — including every bisect-retry level — read as
RESOURCE_EXHAUSTED, with host_spill off so everything actually rides the
device path. Invariants: every request completes (>= 95% 2xx, zero raw
5xx) via bisect-retry or host routing, the recovery counters show real
splits AND host routings, the breaker NEVER opens (OOM is capacity, not
fault), and the owed-work ledgers are at rest afterward.

ROW 5 — SDC storm (ISSUE 10): `device.corrupt[0]=error` makes chip 0
silently flip bytes in every drained output, with `--integrity` on at
sample 1.0 so EVERY device chunk is cross-verified before release.
Invariants: zero corrupted bytes reach clients (every mismatch is
transparently re-served from the verified host copy: reserved ==
mismatches), the lying chip takes corruption strikes and quarantines
ALONE while its peer serves, availability >= 99%, and after the fault
clears the golden probe re-admits it only after the configured clean
streak. 1-device hosts degrade to corruption-strike -> breaker -> host
failover and still hold availability.

ROW 6 — fail-slow (ISSUE 10): `device.slow[0]=delay(250ms)` makes chip
0 limp without ever erroring — the failure mode no breaker can see.
With `--failslow-ratio` armed, the golden-probe latency comparison
demotes the chip, production sheds to its healthy peer, and fleet p99
recovers to within 1.5x of the healthy baseline with no availability
loss. 1-device hosts assert the documented no-op degeneration (no
peers, no demotion, availability holds).

ROWS 7-9 — fleet tier (ISSUE 11): real 2-worker SO_REUSEPORT fleets
(subprocesses, each paying a jax boot) with the crash-safe shared cache
armed, driven over HTTP with the LB retry contract (one fast retry on a
503 + Retry-After or a connection reset — exactly what a balancer does).

ROW 7 — SIGKILL mid-write storm: hot zipf load over the shared cache,
one worker SIGKILLed mid-storm. Invariants: >= 99% availability, the
supervisor respawns the dead worker, `fleet_cache_corrupt_served_total`
stays 0 on every worker, and a DETERMINISTIC torn-write proof: a writer
process killed inside the `fleet.write` window (delay failpoint) leaves
a WRITING slot that readers skip and `sweep()` reclaims.

ROW 8 — SIGSTOP zombie fencing: a worker SIGSTOPped past the (bench-
shortened) liveness window is replaced at epoch+1; the shm epoch table
must show the new stamp, and a client wearing the ZOMBIE's identity
(old epoch) must be able to read but not publish — the revived zombie
is fenced. SIGCONT then releases it into the supervisor's queued
SIGTERM/SIGKILL; the process must actually exit.

ROW 9 — SIGHUP rolling restart: open-loop load through a full fleet
roll. Invariants: 100% ultimate availability (the retry contract may
be used, zero requests lost), per-index epochs strictly monotonic, and
both indices finish on fresh epochs.

ROW 10 — lanes chip loss (ISSUE 15): a 4-device child process (this
one is pinned at 2) runs a `--mesh-policy lanes` executor, kills chip 0
mid-run with `device.chip_error[0]=error`, and holds 100% availability
while exactly one lane quarantines, the mesh generation bumps exactly
once per topology epoch, and the probe re-admits the chip afterwards.

Prints one JSON line per row on stdout; human detail on stderr; nonzero
exit on any violated invariant. Integrity/fail-slow counters from rows
5-6 are archived to artifacts/chaos_integrity.json; fleet counters from
rows 7-9 to artifacts/chaos_fleet.json; the lane drill's row to
artifacts/chaos_lanes.json.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import aiohttp


async def _soak(duration: float, concurrency: int) -> dict:
    from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
    from bench_util import make_1080p_jpeg
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, request_timeout_s=10.0))
    service = app["service"]
    counts: dict = {}
    worst_ms = [0.0]
    bad_bodies = [0]
    try:
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        deadline = time.monotonic() + duration
        async with aiohttp.ClientSession(connector=conn) as session:

            async def worker():
                while time.monotonic() < deadline:
                    t0 = time.monotonic()
                    try:
                        async with session.get(next(urls)) as res:
                            body = await res.read()
                            counts[res.status] = counts.get(res.status, 0) + 1
                            if res.status == 200 and not body:
                                bad_bodies[0] += 1
                    except Exception:
                        counts["exc"] = counts.get("exc", 0) + 1
                    worst_ms[0] = max(
                        worst_ms[0], (time.monotonic() - t0) * 1000.0)

            await asyncio.gather(*[worker() for _ in range(concurrency)])
        # rest-state invariants after traffic stops
        for _ in range(100):
            with service._inflight_lock:
                inflight = service._inflight
            if inflight == 0 and service.caches.flight.inflight() == 0:
                break
            await asyncio.sleep(0.02)
        with service._inflight_lock:
            inflight = service._inflight
        groups = service.caches.flight.inflight()
    finally:
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"counts": counts, "worst_ms": worst_ms[0],
            "bad_bodies": bad_bodies[0], "inflight_after": inflight,
            "groups_after": groups}


async def _chip_loss_soak(duration: float, concurrency: int) -> dict:
    """Three phases against one server: warm (all domains healthy),
    fault (chip_error armed on the primary device), recovery (fault
    cleared; the probe must re-admit)."""
    from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
    from bench_util import make_1080p_jpeg
    from imaginary_tpu import failpoints
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    # host_spill OFF pins traffic to the device path: on the CPU-fallback
    # backend the cost model would otherwise spill everything to host and
    # the chip fault would never be exercised (the breaker's host
    # FAILOVER is independent of the spill policy and still works)
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, request_timeout_s=10.0, host_spill=False))
    ex = app["service"].executor
    counts: dict = {}
    try:
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:

            async def drive(seconds: float) -> None:
                deadline = time.monotonic() + seconds

                async def worker():
                    while time.monotonic() < deadline:
                        try:
                            async with session.get(next(urls)) as res:
                                await res.read()
                                counts[res.status] = counts.get(res.status, 0) + 1
                        except Exception:
                            counts["exc"] = counts.get("exc", 0) + 1

                await asyncio.gather(*[worker() for _ in range(concurrency)])

            # phase 1: warm — resolves the device set, prices the link
            await drive(max(duration / 4, 1.0))
            multi = len(ex.devhealth) > 1
            # a bench-sized cooldown so recovery happens inside the run
            ex.devhealth.cooldown_s = 1.5
            spec = "device.chip_error[0]=error" if multi else "device.chip_error=error"
            print(f"[chaos] chip-loss: arming {spec!r} "
                  f"({len(ex.devhealth)} device(s))", file=sys.stderr)
            failpoints.activate(spec)
            # Sample the registry DURING the fault, not once at its end:
            # the bench-shortened cooldown (1.5 s) can expire inside the
            # fault window — the sick chip then reads half_open until the
            # next probe re-strikes it, and a single end-of-phase snapshot
            # races that probe cycle (measured flaking once the continuous
            # collector started tripping the quarantine earlier in the
            # phase). The invariant is "at some point the sick chip was
            # quarantined ALONE while a healthy peer served", which only a
            # running sampler can observe race-free.
            mid = {"quarantined": 0, "healthy": 0}
            fault_s = max(duration / 2, 2.0)

            async def sample(deadline: float) -> None:
                while time.monotonic() < deadline:
                    s = ex.devhealth.snapshot()
                    if s["quarantined"] == 1:
                        mid["quarantined"] = 1
                        mid["healthy"] = max(mid["healthy"], s["healthy"])
                    await asyncio.sleep(0.05)

            await asyncio.gather(drive(fault_s),
                                 sample(time.monotonic() + fault_s))
            failpoints.deactivate()
            # phase 3: fault cleared — probe (multi) or half-open request
            # (single) must re-admit the device
            await drive(max(duration / 4, 1.0))
            end_t = time.monotonic() + 10.0
            readmitted = False
            while time.monotonic() < end_t:
                snap = ex.devhealth.snapshot()
                if snap["quarantined"] == 0 and snap["healthy"] == snap["count"]:
                    readmitted = True
                    break
                await asyncio.sleep(0.1)
                await drive(0.2)  # single-device half-open needs traffic
            final = ex.devhealth.snapshot()
    finally:
        failpoints.deactivate()
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"counts": counts, "multi_device": multi,
            "quarantined_mid_fault": mid["quarantined"],
            "healthy_mid_fault": mid["healthy"],
            "readmitted": readmitted,
            "final_devices": final,
            "breaker_opens": ex.stats.breaker_opens,
            "breaker_host_served": ex.stats.breaker_host_served}


def _chip_loss_row(duration: float, concurrency: int) -> int:
    got = asyncio.run(_chip_loss_soak(duration, concurrency))
    counts = got["counts"]
    total = sum(counts.values())
    ok = counts.get(200, 0)
    server_errors = sum(v for k, v in counts.items()
                        if isinstance(k, int) and 500 <= k < 600 and k not in (502, 503, 504))
    allowed = sum(counts.get(s, 0) for s in (400, 502, 503, 504))
    surprises = total - ok - allowed - server_errors
    row = {
        "metric": "chaos_chip_loss",
        "requests": total,
        "ok": ok,
        "ok_ratio": round(ok / total, 4) if total else 0.0,
        "multi_device": got["multi_device"],
        "quarantined_mid_fault": got["quarantined_mid_fault"],
        "healthy_mid_fault": got["healthy_mid_fault"],
        "readmitted": got["readmitted"],
        "breaker_opens": got["breaker_opens"],
        "breaker_host_served": got["breaker_host_served"],
        "counts": {str(k): v for k, v in sorted(counts.items(), key=str)},
    }
    print(json.dumps(row))

    fails = []
    if total == 0:
        fails.append("chip-loss soak produced zero requests")
    if total and ok / total < 0.95:
        fails.append(f"availability {ok}/{total} below 95% under chip loss")
    if server_errors:
        fails.append(f"{server_errors} raw 5xx responses (5xx storm)")
    if surprises:
        fails.append(f"{surprises} responses outside 200/400/502/503/504")
    if got["multi_device"]:
        if got["quarantined_mid_fault"] != 1:
            fails.append("sick chip did not quarantine alone "
                         f"(quarantined={got['quarantined_mid_fault']})")
        if got["healthy_mid_fault"] < 1:
            fails.append("no healthy device kept serving during the fault")
    if not got["readmitted"]:
        fails.append("device not re-admitted after the fault cleared")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1
    mode = "failover to peer chip" if got["multi_device"] else "breaker->host"
    print(f"[chaos] PASS (chip loss, {mode}): {ok}/{total} ok, "
          f"quarantined_mid_fault={got['quarantined_mid_fault']}, "
          "re-admitted after cooldown", file=sys.stderr)
    return 0


_HEDGE_ROW_BUDGET = 1.0


async def _hedge_arm(duration: float, concurrency: int, hedge_on: bool) -> dict:
    """One closed-loop arm against a server whose device path carries an
    injected 250 ms delay (device.execute=delay) — the slow-chip/slow-link
    shape hedging exists for."""
    from bench_cache import N_URLS, _start_origin, _start_server
    from bench_util import make_1080p_jpeg
    from imaginary_tpu import failpoints
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, host_spill=False,
        hedge_threshold_ms=60.0 if hedge_on else 0.0,
        # a demonstration-sized budget: EVERY stuck item may hedge, so
        # the p99 (not just the p50) shows the effect — the default 5%
        # protects production overload, but in a short closed-loop row it
        # would cap at one concurrent twin and leave the tail device-bound
        hedge_budget=_HEDGE_ROW_BUDGET))
    ex = app["service"].executor
    lats: list = []
    counts: dict = {}
    try:
        failpoints.activate("device.execute=delay(250ms)")
        url = f"{base}/resize?width=300&height=200&url={origin_base}/img/0"
        conn = aiohttp.TCPConnector(limit=0)
        deadline = time.monotonic() + duration
        async with aiohttp.ClientSession(connector=conn) as session:

            async def worker():
                while time.monotonic() < deadline:
                    t0 = time.monotonic()
                    try:
                        async with session.get(url) as res:
                            await res.read()
                            counts[res.status] = counts.get(res.status, 0) + 1
                    except Exception:
                        counts["exc"] = counts.get("exc", 0) + 1
                    lats.append((time.monotonic() - t0) * 1000.0)

            await asyncio.gather(*[worker() for _ in range(concurrency)])
    finally:
        failpoints.deactivate()
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"lats": lats, "counts": counts,
            "device_items": ex.stats.items,
            "hedges_won": ex.stats.hedges_won,
            "hedges_launched": ex.stats.hedges_launched}


def _hedge_row(duration: float, concurrency: int) -> int:
    from bench_util import pctl

    per_arm = max(duration / 2, 2.0)
    off = asyncio.run(_hedge_arm(per_arm, concurrency, hedge_on=False))
    on = asyncio.run(_hedge_arm(per_arm, concurrency, hedge_on=True))
    n_off, n_on = len(off["lats"]), len(on["lats"])
    p99_off = pctl(off["lats"], 0.99)
    p99_on = pctl(on["lats"], 0.99)
    # device dispatches PER REQUEST: hedge twins run on the HOST, so the
    # device-side work per request must not grow past the budget
    dpr_off = off["device_items"] / max(1, n_off)
    dpr_on = on["device_items"] / max(1, n_on)
    row = {
        "metric": "chaos_hedge_slow_device",
        "unit": "ms",
        "p99_ms_hedge_off": p99_off,
        "p99_ms_hedge_on": p99_on,
        "p50_ms_hedge_off": pctl(off["lats"], 0.50),
        "p50_ms_hedge_on": pctl(on["lats"], 0.50),
        "requests_off": n_off,
        "requests_on": n_on,
        "device_items_per_request_off": round(dpr_off, 3),
        "device_items_per_request_on": round(dpr_on, 3),
        "hedges_launched": on["hedges_launched"],
        "hedges_won": on["hedges_won"],
    }
    print(json.dumps(row))
    fails = []
    if n_off == 0 or n_on == 0:
        fails.append("hedge row produced zero requests in an arm")
    if on["hedges_won"] == 0:
        fails.append("no hedge twin ever won against a 250ms-delayed device")
    if p99_on >= p99_off:
        fails.append(f"hedging did not improve slow-device p99 "
                     f"({p99_off:.0f} -> {p99_on:.0f} ms)")
    if dpr_on > dpr_off * (1.0 + _HEDGE_ROW_BUDGET) + 0.1:
        fails.append(f"device dispatches per request grew past the hedge "
                     f"budget ({dpr_off:.2f} -> {dpr_on:.2f})")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1
    print(f"[chaos] PASS (hedge): slow-device p99 {p99_off:.0f} -> "
          f"{p99_on:.0f} ms, {on['hedges_won']} twins won, device work "
          f"per request {dpr_off:.2f} -> {dpr_on:.2f}", file=sys.stderr)
    return 0


async def _oom_storm_soak(duration: float, concurrency: int) -> dict:
    from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
    from bench_util import make_1080p_jpeg
    from imaginary_tpu import failpoints
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    # host_spill OFF pins traffic to the device path so the storm hits
    # real launches (recovery's HOST ROUTING is independent of the spill
    # policy and still engages for items that OOM at the bisect floor)
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, request_timeout_s=10.0, host_spill=False))
    ex = app["service"].executor
    counts: dict = {}
    try:
        failpoints.activate("device.oom=error(0.5)")
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        deadline = time.monotonic() + duration
        async with aiohttp.ClientSession(connector=conn) as session:

            async def worker():
                while time.monotonic() < deadline:
                    try:
                        async with session.get(next(urls)) as res:
                            await res.read()
                            counts[res.status] = counts.get(res.status, 0) + 1
                    except Exception:
                        counts["exc"] = counts.get("exc", 0) + 1

            await asyncio.gather(*[worker() for _ in range(concurrency)])
        failpoints.deactivate()
        # rest-state: every owed-work charge released
        at_rest = False
        for _ in range(100):
            with ex._owed_lock:
                at_rest = (ex._device_items == 0
                           and abs(ex._device_owed_mb) < 1e-6)
            if at_rest:
                break
            await asyncio.sleep(0.02)
    finally:
        failpoints.deactivate()
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"counts": counts, "at_rest": at_rest,
            "oom_events": ex.stats.oom_events,
            "oom_splits": ex.stats.oom_splits,
            "oom_host_routed": ex.stats.oom_host_routed,
            "oom_failed": ex.stats.oom_failed,
            "breaker_opens": ex.stats.breaker_opens,
            "device_oom_records": ex.devhealth.record(0).oom_events}


def _oom_storm_row(duration: float, concurrency: int) -> int:
    got = asyncio.run(_oom_storm_soak(duration, concurrency))
    counts = got["counts"]
    total = sum(counts.values())
    ok = counts.get(200, 0)
    raw_5xx = sum(v for k, v in counts.items()
                  if isinstance(k, int) and 500 <= k < 600
                  and k not in (503, 504))
    row = {
        "metric": "chaos_oom_storm",
        "requests": total,
        "ok": ok,
        "ok_ratio": round(ok / total, 4) if total else 0.0,
        "oom_events": got["oom_events"],
        "oom_splits": got["oom_splits"],
        "oom_host_routed": got["oom_host_routed"],
        "oom_failed": got["oom_failed"],
        "breaker_opens": got["breaker_opens"],
        "ledgers_at_rest": got["at_rest"],
        "counts": {str(k): v for k, v in sorted(counts.items(), key=str)},
    }
    print(json.dumps(row))

    fails = []
    if total == 0:
        fails.append("OOM storm produced zero requests")
    if total and ok / total < 0.95:
        fails.append(f"availability {ok}/{total} below 95% under OOM storm")
    if raw_5xx:
        fails.append(f"{raw_5xx} raw 5xx responses under OOM storm")
    if got["oom_events"] == 0:
        fails.append("storm fired but no OOM recovery ever ran")
    if got["oom_splits"] == 0 and got["oom_host_routed"] == 0:
        fails.append("recovery booked neither splits nor host routings")
    if got["breaker_opens"]:
        fails.append(f"OOM tripped the breaker {got['breaker_opens']}x "
                     "(capacity must never read as fault)")
    if not got["at_rest"]:
        fails.append("owed-work ledgers not at rest after the storm")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1
    print(f"[chaos] PASS (OOM storm): {ok}/{total} ok via "
          f"{got['oom_splits']} splits + {got['oom_host_routed']} host "
          f"routings across {got['oom_events']} OOM events, breaker "
          "closed, ledgers at rest", file=sys.stderr)
    return 0


async def _sdc_storm_soak(duration: float, concurrency: int) -> dict:
    """Three phases against one --integrity server: warm (clean
    verification prices in), fault (device.corrupt armed on the primary:
    every chunk it serves is byte-flipped, every mismatch must be caught
    and re-served), recovery (fault cleared; the golden probe must pay
    down the clean streak and re-admit)."""
    from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
    from bench_util import make_1080p_jpeg
    from imaginary_tpu import failpoints
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    # sample 1.0: the "zero corrupted bytes served" invariant only holds
    # when EVERY device chunk is verified; host_spill off pins traffic to
    # the device path so the corruption is actually exercised
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, request_timeout_s=10.0, host_spill=False,
        integrity=True, integrity_sample=1.0, integrity_clean_probes=2))
    ex = app["service"].executor
    integ = ex.integrity
    counts: dict = {}
    try:
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:

            async def drive(seconds: float) -> None:
                deadline = time.monotonic() + seconds

                async def worker():
                    while time.monotonic() < deadline:
                        try:
                            async with session.get(next(urls)) as res:
                                await res.read()
                                counts[res.status] = counts.get(res.status, 0) + 1
                        except Exception:
                            counts["exc"] = counts.get("exc", 0) + 1

                await asyncio.gather(*[worker() for _ in range(concurrency)])

            await drive(max(duration / 4, 1.0))  # warm: clean checks book
            clean_mismatches = integ.mismatches
            multi = len(ex.devhealth) > 1
            ex.devhealth.cooldown_s = 1.5  # recovery inside the run
            spec = ("device.corrupt[0]=error" if multi
                    else "device.corrupt=error")
            print(f"[chaos] SDC storm: arming {spec!r} "
                  f"({len(ex.devhealth)} device(s))", file=sys.stderr)
            failpoints.activate(spec)
            # sample DURING the fault (same race as the chip-loss row:
            # the invariant is "at some point the lying chip was
            # quarantined ALONE while a healthy peer served")
            mid = {"quarantined": 0, "healthy": 0}
            fault_s = max(duration / 2, 2.0)

            async def sample(deadline: float) -> None:
                while time.monotonic() < deadline:
                    s = ex.devhealth.snapshot()
                    if s["quarantined"] == 1:
                        mid["quarantined"] = 1
                        mid["healthy"] = max(mid["healthy"], s["healthy"])
                    await asyncio.sleep(0.05)

            await asyncio.gather(drive(fault_s),
                                 sample(time.monotonic() + fault_s))
            failpoints.deactivate()
            await drive(max(duration / 4, 1.0))
            end_t = time.monotonic() + 15.0
            readmitted = False
            while time.monotonic() < end_t:
                snap = ex.devhealth.snapshot()
                if snap["quarantined"] == 0 and snap["degraded"] == 0:
                    readmitted = True
                    break
                await asyncio.sleep(0.1)
                await drive(0.2)  # single-device half-open needs traffic
        final = ex.devhealth.snapshot()
    finally:
        failpoints.deactivate()
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"counts": counts, "multi_device": multi,
            "quarantined_mid_fault": mid["quarantined"],
            "healthy_mid_fault": mid["healthy"],
            "readmitted": readmitted,
            "clean_mismatches": clean_mismatches,
            "final_devices": final,
            "integrity": integ.snapshot(),
            "corruptions": final["corruptions"]}


def _sdc_storm_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_sdc_storm_soak(duration, concurrency))
    counts = got["counts"]
    total = sum(counts.values())
    ok = counts.get(200, 0)
    integ = got["integrity"]
    row = {
        "metric": "chaos_sdc_storm",
        "requests": total,
        "ok": ok,
        "ok_ratio": round(ok / total, 4) if total else 0.0,
        "multi_device": got["multi_device"],
        "quarantined_mid_fault": got["quarantined_mid_fault"],
        "healthy_mid_fault": got["healthy_mid_fault"],
        "readmitted": got["readmitted"],
        "corruption_strikes": got["corruptions"],
        "integrity": integ,
        "counts": {str(k): v for k, v in sorted(counts.items(), key=str)},
    }
    print(json.dumps(row))

    fails = []
    if total == 0:
        fails.append("SDC storm produced zero requests")
    if total and ok / total < 0.99:
        fails.append(f"availability {ok}/{total} below 99% under SDC storm")
    if got["clean_mismatches"]:
        fails.append(f"{got['clean_mismatches']} false-positive mismatches "
                     "on CLEAN warm traffic (tolerance too tight)")
    if integ["mismatches"] == 0:
        fails.append("corrupt chip never caught by sampled verification")
    if integ["reserved"] != integ["mismatches"]:
        fails.append(
            f"{integ['mismatches'] - integ['reserved']} caught mismatches "
            "NOT re-served from the verified copy (corrupted bytes leaked)")
    if got["corruptions"] == 0:
        fails.append("no corruption strike ever booked")
    if got["multi_device"]:
        if got["quarantined_mid_fault"] != 1:
            fails.append("lying chip did not quarantine alone "
                         f"(quarantined={got['quarantined_mid_fault']})")
        if got["healthy_mid_fault"] < 1:
            fails.append("no healthy device kept serving during the storm")
    if not got["readmitted"]:
        fails.append("chip not re-admitted after the clean-probe streak")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    mode = ("quarantined alone, peer served" if got["multi_device"]
            else "breaker->host failover")
    print(f"[chaos] PASS (SDC storm, {mode}): {ok}/{total} ok, "
          f"{integ['mismatches']} mismatches all re-served verified, "
          f"{got['corruptions']} corruption strikes, re-admitted after "
          "clean streak", file=sys.stderr)
    return 0, row


async def _failslow_soak(duration: float, concurrency: int) -> dict:
    """Baseline -> limp -> demote -> recovered-p99 phases against one
    --failslow server. The limp is device.slow[0]=delay(250ms): chip 0
    never errors, it just drags every chunk (and its golden probes) —
    the failure no breaker can see."""
    from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
    from bench_util import make_1080p_jpeg
    from imaginary_tpu import failpoints
    from imaginary_tpu.web.config import ServerOptions

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
    origin_runner, origin_base = await _start_origin(variants)
    server_runner, app, base = await _start_server(ServerOptions(
        enable_url_source=True, request_timeout_s=10.0, host_spill=False,
        failslow_ratio=2.5, failslow_min_samples=3))
    ex = app["service"].executor
    counts: dict = {}
    base_lats: list = []
    after_lats: list = []
    try:
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:

            async def drive(seconds: float, lats=None) -> None:
                deadline = time.monotonic() + seconds

                async def worker():
                    while time.monotonic() < deadline:
                        t0 = time.monotonic()
                        try:
                            async with session.get(next(urls)) as res:
                                await res.read()
                                counts[res.status] = counts.get(res.status, 0) + 1
                        except Exception:
                            counts["exc"] = counts.get("exc", 0) + 1
                        if lats is not None:
                            lats.append((time.monotonic() - t0) * 1000.0)

                await asyncio.gather(*[worker() for _ in range(concurrency)])

            # phase 1: healthy baseline (devices resolved, probes running)
            await drive(max(duration / 3, 2.0), base_lats)
            multi = len(ex.devhealth) > 1
            print(f"[chaos] fail-slow: arming device.slow[0]=delay(250ms) "
                  f"({len(ex.devhealth)} device(s))", file=sys.stderr)
            failpoints.activate("device.slow[0]=delay(250ms)"
                                if multi else "device.slow=delay(250ms)")
            # phase 2: drive until the probe comparison demotes chip 0
            demoted = False
            end_t = time.monotonic() + max(duration * 2, 25.0)
            while time.monotonic() < end_t and multi:
                await drive(0.5)
                r0 = ex.devhealth.record(0)
                if r0.degraded or ex.devhealth.is_quarantined(0):
                    demoted = True
                    break
            if not multi:
                await drive(max(duration / 3, 2.0))
            # phase 3: recovered p99, measured only after demotion
            await drive(max(duration / 3, 2.0), after_lats)
            failpoints.deactivate()
            snap = ex.devhealth.snapshot()
    finally:
        failpoints.deactivate()
        await server_runner.cleanup()
        await origin_runner.cleanup()
    return {"counts": counts, "multi_device": multi, "demoted": demoted,
            "base_lats": base_lats, "after_lats": after_lats,
            "devices": snap}


def _failslow_row(duration: float, concurrency: int) -> tuple:
    from bench_util import pctl

    got = asyncio.run(_failslow_soak(duration, concurrency))
    counts = got["counts"]
    total = sum(counts.values())
    ok = counts.get(200, 0)
    p99_base = pctl(got["base_lats"], 0.99)
    p99_after = pctl(got["after_lats"], 0.99)
    per = {d["device"]: d for d in got["devices"]["per_device"]}
    row = {
        "metric": "chaos_failslow",
        "unit": "ms",
        "requests": total,
        "ok": ok,
        "ok_ratio": round(ok / total, 4) if total else 0.0,
        "multi_device": got["multi_device"],
        "demoted": got["demoted"],
        "p99_ms_healthy_baseline": p99_base,
        "p99_ms_after_demotion": p99_after,
        "p50_ms_healthy_baseline": pctl(got["base_lats"], 0.50),
        "p50_ms_after_demotion": pctl(got["after_lats"], 0.50),
        "demotions": sum(d["demotions"] for d in per.values()),
        "probe_latency_ewma_ms": {
            str(k): d["probe_latency_ewma_ms"] for k, d in per.items()},
        "counts": {str(k): v for k, v in sorted(counts.items(), key=str)},
    }
    print(json.dumps(row))

    fails = []
    if total == 0:
        fails.append("fail-slow soak produced zero requests")
    if total and ok / total < 0.99:
        fails.append(f"availability {ok}/{total} below 99% (fail-slow must "
                     "cost latency, never availability)")
    if got["multi_device"]:
        if not got["demoted"]:
            fails.append("limping chip was never demoted")
        # the ISSUE bound, with a small absolute floor so a sub-50ms
        # baseline on an idle host doesn't turn scheduler noise into a
        # false failure
        bound = max(1.5 * p99_base, p99_base + 50.0)
        if p99_after > bound:
            fails.append(f"fleet p99 after demotion {p99_after:.0f}ms "
                         f"exceeds bound {bound:.0f}ms "
                         f"(healthy baseline {p99_base:.0f}ms)")
    else:
        # single-device degeneration: no peers, no demotion, ever
        if any(d["demotions"] for d in per.values()):
            fails.append("single-device fleet demoted itself "
                         "(no-op degeneration violated)")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    if got["multi_device"]:
        print(f"[chaos] PASS (fail-slow): demoted, p99 "
              f"{p99_base:.0f}ms baseline -> {p99_after:.0f}ms after "
              f"demotion (bound 1.5x), {ok}/{total} ok", file=sys.stderr)
    else:
        print(f"[chaos] PASS (fail-slow, 1 device): no-op degeneration "
              f"held, {ok}/{total} ok", file=sys.stderr)
    return 0, row


# --- fleet rows (ISSUE 11): real SO_REUSEPORT fleets, process signals --------

ROOT = os.path.dirname(os.path.abspath(__file__))


class _Fleet:
    """A real 2-worker supervisor fleet + an in-bench origin server."""

    def __init__(self, extra_env=None, extra_args=()):
        self.extra_env = extra_env or {}
        self.extra_args = list(extra_args)
        self.sup = None
        self.port = None
        self.fleet_path = None
        self.origin_runner = None
        self.origin_base = None

    async def start(self):
        from bench_cache import N_URLS, _start_origin
        from bench_util import free_port, make_1080p_jpeg

        base_jpeg = make_1080p_jpeg()
        variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
        self.origin_runner, self.origin_base = await _start_origin(variants)
        self.port = free_port()
        fd, self.fleet_path = tempfile.mkstemp(prefix="chaos-fleet-",
                                               suffix=".shm")
        os.close(fd)
        os.unlink(self.fleet_path)  # the supervisor creates it fresh
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        for k in ("IMAGINARY_TPU_WORKER", "IMAGINARY_TPU_WORKER_EPOCH",
                  "IMAGINARY_TPU_FAILPOINTS"):
            env.pop(k, None)
        env["IMAGINARY_TPU_FLEET_PATH"] = self.fleet_path
        env.update(self.extra_env)
        self.sup = subprocess.Popen(
            [sys.executable, "-m", "imaginary_tpu.cli", "--workers", "2",
             "--port", str(self.port), "--enable-url-source",
             "--cache-result-mb", "16", "--fleet-cache-mb", "16",
             "--request-timeout", "10"] + self.extra_args,
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    async def health(self, session, timeout=2.0):
        # Connection: close — every sample opens a FRESH connection so
        # the kernel's SO_REUSEPORT spread reaches every worker; a
        # pooled keep-alive connection would pin sampling to one pid
        async with session.get(
                f"http://127.0.0.1:{self.port}/health",
                headers={"Connection": "close"},
                timeout=aiohttp.ClientTimeout(total=timeout)) as r:
            return await r.json()

    async def wait_workers(self, session, n=2, deadline_s=120.0) -> dict:
        """Sample /health until n distinct worker indices answer;
        returns {idx: {"pid":…, "epoch":…}}."""
        seen: dict = {}
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            if self.sup.poll() is not None:
                raise RuntimeError(
                    f"fleet supervisor exited {self.sup.poll()} during boot")
            try:
                h = await self.health(session)
                seen[h["worker"]] = {"pid": h["pid"], "epoch": h["epoch"]}
                if len(seen) >= n:
                    return seen
            except Exception:
                pass
            await asyncio.sleep(0.2)
        raise RuntimeError(f"fleet never reached {n} workers (saw {seen})")

    def url(self, i: int) -> str:
        return (f"http://127.0.0.1:{self.port}/resize?width=300&height=200"
                f"&url={self.origin_base}/img/{i}")

    async def stop(self):
        if self.sup is not None and self.sup.poll() is None:
            self.sup.send_signal(signal.SIGTERM)
            try:
                await asyncio.get_event_loop().run_in_executor(
                    None, self.sup.wait, 20)
            except subprocess.TimeoutExpired:
                self.sup.kill()
                self.sup.wait()
        if self.origin_runner is not None:
            await self.origin_runner.cleanup()
        if self.fleet_path and os.path.exists(self.fleet_path):
            try:
                os.unlink(self.fleet_path)
            except OSError:
                pass


async def _lb_get(session, url: str, counts: dict, retries: int = 2,
                  timeout_s: float = 8.0) -> bool:
    """One request under the LB retry contract: a 503 + Retry-After or a
    connection error is retried (fast) up to `retries` times — that IS
    the documented drain/shed semantics; what must never happen is an
    ULTIMATE failure. Returns whether the request ultimately succeeded."""
    for attempt in range(retries + 1):
        try:
            # Connection: close = the LB model: every attempt (and every
            # retry in particular) rides a fresh connection the kernel
            # may route to a DIFFERENT worker — a keep-alive retry would
            # re-ask the very worker that just shed us
            async with session.get(
                    url, headers={"Connection": "close"},
                    timeout=aiohttp.ClientTimeout(total=timeout_s)) as r:
                body = await r.read()
                counts[r.status] = counts.get(r.status, 0) + 1
                if r.status == 200 and body:
                    return True
                if r.status not in (502, 503, 504):
                    return False
        except Exception:
            counts["exc"] = counts.get("exc", 0) + 1
        if attempt < retries:
            counts["retries"] = counts.get("retries", 0) + 1
            await asyncio.sleep(0.2)
    return False


async def _fleet_counters(fleet, session, seconds: float = 4.0) -> dict:
    """Sample /health across the fleet and keep each pid's LATEST fleet
    block (counters only ever grow; per-pid last-write-wins)."""
    per_pid: dict = {}
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        try:
            h = await fleet.health(session)
            if "fleet" in h:
                per_pid[h["pid"]] = dict(h["fleet"], worker=h["worker"],
                                         epoch=h["epoch"])
        except Exception:
            pass
        await asyncio.sleep(0.1)
    return per_pid


def _spawn_torn_writer(fleet_path: str) -> subprocess.Popen:
    """A writer that starts a deposit and stalls inside the WRITING
    window (fleet.write delay failpoint) so a SIGKILL leaves a real
    torn slot. Uses a high worker index no serving worker occupies."""
    code = (
        "import hashlib\n"
        "from imaginary_tpu import failpoints\n"
        "from imaginary_tpu.fleet.shmcache import ShmCache\n"
        "failpoints.activate('fleet.write=delay(60s)')\n"
        f"w = ShmCache({fleet_path!r}, create=False, worker=60, epoch=0)\n"
        "print('mid-write', flush=True)\n"
        "w.put(hashlib.sha256(b'chaos-torn').digest(), b'm', b'x' * 2000)\n"
    )
    return subprocess.Popen([sys.executable, "-c", code], cwd=ROOT,
                            stdout=subprocess.PIPE)


async def _fleet_kill_soak(duration: float, concurrency: int) -> dict:
    from bench_cache import N_URLS, ZIPF_S, _zipf_indices
    from imaginary_tpu.fleet.shmcache import FREE, WRITING, ShmCache

    fleet = _Fleet()
    counts: dict = {}
    outcomes = {"ok": 0, "fail": 0}
    try:
        await fleet.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            workers0 = await fleet.wait_workers(session)
            seq = _zipf_indices(50_000, N_URLS, ZIPF_S)
            urls = itertools.cycle([fleet.url(i) for i in seq])
            victim = {"pid": None}

            async def drive(seconds: float) -> None:
                deadline = time.monotonic() + seconds

                async def worker():
                    while time.monotonic() < deadline:
                        ok = await _lb_get(session, next(urls), counts)
                        outcomes["ok" if ok else "fail"] += 1

                await asyncio.gather(*[worker() for _ in range(concurrency)])

            await drive(max(duration / 3, 2.0))  # warm: caches fill

            async def kill_mid_storm():
                await asyncio.sleep(max(duration / 6, 0.7))
                victim["pid"] = workers0[1]["pid"]
                os.kill(victim["pid"], signal.SIGKILL)
                print(f"[chaos] fleet-kill: SIGKILLed worker pid "
                      f"{victim['pid']} mid-storm", file=sys.stderr)

            await asyncio.gather(drive(max(duration, 4.0)), kill_mid_storm())
            # the supervisor must respawn index 1 (fresh pid, fresh epoch)
            respawned = False
            end = time.monotonic() + 60.0
            while time.monotonic() < end:
                try:
                    h = await fleet.health(session)
                    if h["worker"] == 1 and h["pid"] != victim["pid"]:
                        respawned = True
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.2)
            per_pid = await _fleet_counters(fleet, session)
            # deterministic torn-write proof against the LIVE fleet file
            torn = {"left_writing": False, "reclaimed": 0, "final_free": False}
            p = _spawn_torn_writer(fleet.fleet_path)
            try:
                assert b"mid-write" in p.stdout.readline()
                await asyncio.sleep(0.8)
                p.kill()
                p.wait()
                import hashlib

                k = hashlib.sha256(b"chaos-torn").digest()
                client = ShmCache(fleet.fleet_path, create=False, worker=61,
                                  epoch=0)
                try:
                    idx = client._candidates(k)[0]
                    torn["left_writing"] = client._slot_state(idx) == WRITING
                    assert client.get(k) is None  # skipped, never served
                    torn["reclaimed"] = client.sweep()
                    torn["final_free"] = client._slot_state(idx) == FREE
                finally:
                    client.close()
            finally:
                if p.poll() is None:
                    p.kill()
                    p.wait()
    finally:
        await fleet.stop()
    return {"counts": counts, "outcomes": outcomes, "respawned": respawned,
            "per_pid": per_pid, "torn": torn}


def _fleet_kill_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_fleet_kill_soak(duration, concurrency))
    o = got["outcomes"]
    total = o["ok"] + o["fail"]
    corrupt_served = sum(v.get("corrupt_served", 0)
                         for v in got["per_pid"].values())
    corrupt = sum(v.get("corrupt", 0) for v in got["per_pid"].values())
    row = {
        "metric": "chaos_fleet_kill_storm",
        "requests": total,
        "ok": o["ok"],
        "ok_ratio": round(o["ok"] / total, 4) if total else 0.0,
        "retries": got["counts"].get("retries", 0),
        "respawned": got["respawned"],
        "corrupt_served_total": corrupt_served,
        "corrupt_total": corrupt,
        "torn": got["torn"],
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if total == 0:
        fails.append("fleet kill storm produced zero requests")
    if total and o["ok"] / total < 0.99:
        fails.append(f"availability {o['ok']}/{total} below 99% under "
                     "worker SIGKILL")
    if not got["respawned"]:
        fails.append("killed worker never respawned")
    if corrupt_served:
        fails.append(f"{corrupt_served} corrupt-byte serves (tripwire)")
    if not got["torn"]["left_writing"]:
        fails.append("SIGKILLed writer did not leave a WRITING slot "
                     "(torn-write window never exercised)")
    if got["torn"]["reclaimed"] != 1 or not got["torn"]["final_free"]:
        fails.append(f"torn slot not reclaimed by sweep: {got['torn']}")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (fleet SIGKILL storm): {o['ok']}/{total} ok "
          f"({got['counts'].get('retries', 0)} LB retries), worker "
          "respawned, 0 corrupt serves, torn slot swept", file=sys.stderr)
    return 0, row


async def _fleet_zombie_soak(duration: float, concurrency: int) -> dict:
    from imaginary_tpu.fleet.shmcache import ShmCache

    fleet = _Fleet(extra_env={
        "IMAGINARY_TPU_SUPERVISOR_PROBE_INTERVAL": "0.3",
        "IMAGINARY_TPU_SUPERVISOR_PROBE_TIMEOUT": "1.0",
        "IMAGINARY_TPU_SUPERVISOR_LIVENESS_TIMEOUT": "4.0",
        "IMAGINARY_TPU_SUPERVISOR_HANG_GRACE": "2.0",
        # boot on this host is seconds; the default 90 s grace would
        # stall hang detection for a worker the probe had not yet
        # sighted when the SIGSTOP landed
        "IMAGINARY_TPU_SUPERVISOR_BOOT_GRACE": "20.0",
    })
    counts: dict = {}
    out = {"replaced": False, "zombie_exited": False, "fence": {},
           "ok": 0, "fail": 0}
    try:
        await fleet.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            workers0 = await fleet.wait_workers(session)
            # let the SUPERVISOR's own probe sight both workers before
            # the stop: its liveness clock runs from last sighting
            await asyncio.sleep(3.0)
            zpid, zepoch = workers0[1]["pid"], workers0[1]["epoch"]
            print(f"[chaos] zombie: SIGSTOP worker 1 (pid {zpid}, "
                  f"epoch {zepoch})", file=sys.stderr)
            os.kill(zpid, signal.SIGSTOP)
            # the liveness probe must declare it hung and replace it at a
            # fresh epoch (stamped BEFORE the replacement spawns)
            end = time.monotonic() + 90.0
            new_epoch = None
            while time.monotonic() < end:
                try:
                    h = await fleet.health(session, timeout=1.5)
                    if h["worker"] == 1 and h["pid"] != zpid \
                            and h["epoch"] > zepoch:
                        new_epoch = h["epoch"]
                        out["replaced"] = True
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.2)
            # the fence, asserted against the LIVE fleet file: a client
            # wearing the zombie's identity may read but not publish
            client = ShmCache(fleet.fleet_path, create=False, worker=1,
                              epoch=zepoch)
            try:
                stamped = client.epoch_of(1)
                fenced = client.fenced()
                publish_refused = not client.put(b"f" * 32, b"m", b"b")
                read_ok = client.get(b"f" * 32) is None  # miss, not error
                out["fence"] = {
                    "stamped_epoch": stamped, "old_epoch": zepoch,
                    "new_epoch": new_epoch, "fenced": fenced,
                    "publish_refused": publish_refused,
                    "fenced_publishes": client.stats.fenced_publishes,
                    "read_ok": read_ok,
                }
            finally:
                client.close()
            # wake the zombie into the supervisor's queued SIGTERM; it
            # must actually exit (SIGKILL escalation past the grace)
            os.kill(zpid, signal.SIGCONT)
            end = time.monotonic() + 30.0
            while time.monotonic() < end:
                try:
                    os.kill(zpid, 0)
                except ProcessLookupError:
                    out["zombie_exited"] = True
                    break
                await asyncio.sleep(0.2)
            # the fleet serves normally again
            for _ in range(20):
                ok = await _lb_get(session, fleet.url(0), counts)
                out["ok" if ok else "fail"] += 1
    finally:
        await fleet.stop()
    out["counts"] = counts
    return out


def _fleet_zombie_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_fleet_zombie_soak(duration, concurrency))
    f = got["fence"]
    row = {
        "metric": "chaos_fleet_zombie_fence",
        "replaced": got["replaced"],
        "zombie_exited": got["zombie_exited"],
        "fence": f,
        "post_recovery_ok": got["ok"],
        "post_recovery_fail": got["fail"],
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if not got["replaced"]:
        fails.append("SIGSTOPped worker was never replaced by the "
                     "liveness probe")
    if not f.get("fenced"):
        fails.append(f"zombie epoch not fenced (table {f})")
    if not f.get("publish_refused") or f.get("fenced_publishes") != 1:
        fails.append("zombie publish was NOT refused — post-fence "
                     "publishes possible")
    if not f.get("read_ok"):
        fails.append("fenced zombie lost READ access (only publishes "
                     "must be refused)")
    if not got["zombie_exited"]:
        fails.append("revived zombie never exited (SIGTERM/SIGKILL "
                     "escalation failed)")
    if got["fail"]:
        fails.append(f"{got['fail']} post-recovery requests failed")
    if fails:
        for fl in fails:
            print(f"[chaos] FAIL: {fl}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (fleet zombie): replaced at epoch "
          f"{f['new_epoch']} (old {f['old_epoch']}), zombie fenced "
          "(reads ok, publish refused), zombie reaped, "
          f"{got['ok']}/20 post-recovery ok", file=sys.stderr)
    return 0, row


async def _fleet_roll_soak(duration: float, concurrency: int) -> dict:
    fleet = _Fleet(extra_args=["--fleet-roll-grace", "1.5"])
    counts: dict = {}
    out = {"ok": 0, "fail": 0, "rolled": False}
    epochs_seen: dict = {0: [], 1: []}
    try:
        await fleet.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            workers0 = await fleet.wait_workers(session)
            before = {i: w["epoch"] for i, w in workers0.items()}
            stop_flag = {"stop": False}

            async def open_loop_load():
                # open-loop: a new request every tick regardless of
                # completions (rate ~ 5 x concurrency req/s)
                pending = set()
                i = 0
                while not stop_flag["stop"]:
                    i += 1

                    async def one(u=fleet.url(i % 16)):
                        ok = await _lb_get(session, u, counts, retries=3)
                        out["ok" if ok else "fail"] += 1

                    pending.add(asyncio.ensure_future(one()))
                    pending = {t for t in pending if not t.done()}
                    await asyncio.sleep(max(0.01, 0.2 / concurrency))
                if pending:
                    await asyncio.gather(*pending, return_exceptions=True)

            async def sample_epochs():
                while not stop_flag["stop"]:
                    try:
                        h = await fleet.health(session, timeout=1.5)
                        epochs_seen[h["worker"]].append(h["epoch"])
                    except Exception:
                        pass
                    await asyncio.sleep(0.1)

            load = asyncio.ensure_future(open_loop_load())
            sampler = asyncio.ensure_future(sample_epochs())
            await asyncio.sleep(1.0)
            print("[chaos] roll: SIGHUP to the supervisor", file=sys.stderr)
            fleet.sup.send_signal(signal.SIGHUP)
            end = time.monotonic() + 240.0
            while time.monotonic() < end:
                cur = {i: max(v) if v else before[i]
                       for i, v in epochs_seen.items()}
                if cur[0] > before[0] and cur[1] > before[1]:
                    out["rolled"] = True
                    break
                await asyncio.sleep(0.3)
            # settle: the last old worker finishes its grace + drain and
            # exits, so the tail samples prove the steady state is
            # new-epochs-only (its listener closed at SIGUSR1, so no new
            # connection can reach an old epoch from here anyway)
            await asyncio.sleep(12.0)
            stop_flag["stop"] = True
            await asyncio.gather(load, sampler, return_exceptions=True)
            out["before"] = before
            out["after"] = {i: max(v) if v else 0
                            for i, v in epochs_seen.items()}
    finally:
        await fleet.stop()
    out["epochs_seen"] = epochs_seen
    out["counts"] = counts
    return out


def _fleet_roll_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_fleet_roll_soak(duration, concurrency))
    total = got["ok"] + got["fail"]
    # Epoch monotonicity under a roll: during each handover BOTH the old
    # and new holder of an index serve (that is the zero-downtime
    # design), so raw samples interleave the two. The invariants: no
    # index ever shows an epoch OUTSIDE {its old, its new} (nothing
    # regressed, nothing minted off the books), every new epoch is
    # strictly greater, and the steady state after the roll is
    # new-epochs-only (the deposed listeners are gone).
    before, after = got.get("before", {}), got.get("after", {})
    monotonic = True
    for idx, seq in got["epochs_seen"].items():
        allowed = {before.get(idx), after.get(idx)}
        if not seq or not set(seq) <= allowed \
                or after.get(idx, 0) <= before.get(idx, 0) \
                or seq[-3:] != [after.get(idx)] * len(seq[-3:]):
            monotonic = False
    row = {
        "metric": "chaos_fleet_sighup_roll",
        "requests": total,
        "ok": got["ok"],
        "ok_ratio": round(got["ok"] / total, 4) if total else 0.0,
        "retries": got["counts"].get("retries", 0),
        "rolled": got["rolled"],
        "epochs_before": got.get("before", {}),
        "epochs_after": got.get("after", {}),
        "epochs_monotonic": monotonic,
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if total == 0:
        fails.append("roll soak produced zero requests")
    if not got["rolled"]:
        fails.append("SIGHUP roll never completed (epochs did not "
                     "advance on both indices)")
    if got["fail"]:
        fails.append(f"{got['fail']}/{total} requests ultimately failed "
                     "during the roll (must be 100% available)")
    if not monotonic:
        fails.append(f"per-index epochs regressed: {got['epochs_seen']}")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (SIGHUP roll): {got['ok']}/{total} ok at 100% "
          f"({got['counts'].get('retries', 0)} LB retries), epochs "
          f"{got.get('before')} -> {got.get('after')}, monotonic",
          file=sys.stderr)
    return 0, row


# --- row 10 (ISSUE 15): per-chip lanes under chip loss -----------------------


def _lanes_chip_loss_child() -> int:
    """ROW 10 body — runs in a SUBPROCESS with 4 virtual devices (the
    parent fixed XLA's host device count at 2 at first jax import, so a
    4-lane drill cannot run in-process). Direct executor drive, no HTTP:
    a 4-lane executor takes traffic, `device.chip_error[0]=error` kills
    chip 0 mid-run, and the invariants are the lane tier's whole story:

      * availability is 100% — every future completes; the drained
        lane's items re-place onto survivors, nothing errors out;
      * exactly ONE lane quarantines, and the mesh generation bumps
        exactly ONCE for the epoch (the compile-key pin: chip loss is
        one recompile, never a per-request compile storm);
      * after the fault clears, the half-open probe re-admits chip 0 —
        the lane is active again and the generation bumps once more.
    """
    import numpy as np

    from imaginary_tpu import failpoints
    from imaginary_tpu.engine.executor import Executor, ExecutorConfig
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.ops.plan import plan_operation

    # host_spill off: the drill must exercise the LANES under chip loss;
    # the auto cost model would route the fault away to the host SIMD
    # path and the row would test nothing
    ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                 host_spill=False, max_form_ms=1.0,
                                 breaker_threshold=1,
                                 breaker_cooldown_s=1.0))
    ok = total = 0
    try:
        rng = np.random.default_rng(3)
        arr = rng.integers(0, 256, (96, 96, 3), dtype=np.uint8)
        opts = ImageOptions(width=48)
        plan = plan_operation("resize", opts, 96, 96, 0, 3)
        # prewarm the per-lane compile keys: a cold first dispatch books
        # its compile time into that lane's EWMA and the scheduler
        # starves it — the fault on chip 0 would never be exercised
        from imaginary_tpu.prewarm import warm_chain, warm_mesh_paths

        warm_chain("resize", opts, 96, 96, (1, 2, 4, 8, 16))
        warm_mesh_paths(ex, "resize", opts, 96, 96,
                        batch_sizes=(1, 2, 4, 8, 16))
        for _ in range(8):  # warm every lane's EWMA before the fault
            ex.submit(arr, plan).result(timeout=60)
        gen0 = ex._mesh_generation
        failpoints.activate("device.chip_error[0]=error")
        futs = [ex.submit(arr, plan) for _ in range(48)]
        for f in futs:
            total += 1
            try:
                f.result(timeout=60)
                ok += 1
            except Exception:
                pass
        lane0 = ex._lanes.lane(0)
        deadline = time.monotonic() + 10.0
        while lane0.active and time.monotonic() < deadline:
            time.sleep(0.02)
        quarantined_mid = sum(1 for ln in ex._lanes.lanes if not ln.active)
        gen_mid = ex._mesh_generation
        failpoints.deactivate()
        # probe-driven re-admission (cooldown 1 s); light traffic keeps
        # the collectors polling topology
        deadline = time.monotonic() + 30.0
        while not lane0.active and time.monotonic() < deadline:
            total += 1
            try:
                ex.submit(arr, plan).result(timeout=60)
                ok += 1
            except Exception:
                pass
            time.sleep(0.05)
        readmitted = lane0.active
        gen_end = ex._mesh_generation
    finally:
        failpoints.deactivate()
        ex.shutdown()

    row = {
        "metric": "lanes_chip_loss",
        "devices": 4,
        "requests": total,
        "ok": ok,
        "availability": round(ok / total, 4) if total else 0.0,
        "quarantined_mid_fault": quarantined_mid,
        "gen_bumps_mid_fault": gen_mid - gen0,
        "readmitted": readmitted,
        "gen_bumps_total": gen_end - gen0,
    }
    print(json.dumps(row), flush=True)
    fails = []
    if total == 0 or ok != total:
        fails.append(f"availability {ok}/{total} under chip loss "
                     "(lane drain must re-place, not fail)")
    if quarantined_mid != 1:
        fails.append(f"{quarantined_mid} lanes quarantined mid-fault "
                     "(want exactly the sick chip's)")
    if gen_mid - gen0 != 1:
        fails.append(f"mesh generation bumped {gen_mid - gen0}x mid-fault "
                     "(want exactly 1 per topology epoch)")
    if not readmitted:
        fails.append("chip 0's lane never re-admitted after the fault "
                     "cleared")
    elif gen_end - gen0 != 2:
        fails.append(f"generation bumped {gen_end - gen0}x total "
                     "(want 2: out + back in)")
    for f in fails:
        print(f"[chaos] FAIL (lanes child): {f}", file=sys.stderr)
    if not fails:
        print(f"[chaos] lanes child: {ok}/{total} ok, one quarantine, "
              f"gen +{gen_end - gen0}, re-admitted", file=sys.stderr)
    return 1 if fails else 0


def _lanes_chip_loss_row() -> tuple:
    """ROW 10 parent half: re-exec this file with `--lanes-row` under
    XLA_FLAGS=--xla_force_host_platform_device_count=4 (the device count
    is burned in at first jax import, so the 4-lane drill needs its own
    process) and relay the child's JSON row + verdict."""
    print("[chaos] row 10: 4-lane chip-loss drill in a fresh 4-device "
          "child process", file=sys.stderr)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("IMAGINARY_TPU_FAILPOINTS", None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--lanes-row"],
            env=env, capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        row = {"metric": "lanes_chip_loss", "error": "child timed out"}
        print(json.dumps(row))
        print("[chaos] FAIL: lanes chip-loss child timed out",
              file=sys.stderr)
        return 1, row
    sys.stderr.write(proc.stderr)
    row = None
    for ln in proc.stdout.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            cand = json.loads(ln)
        except ValueError:
            continue
        if cand.get("metric") == "lanes_chip_loss":
            row = cand
    if row is not None:
        print(json.dumps(row))
    if proc.returncode or row is None:
        print(f"[chaos] FAIL: lanes chip-loss child rc={proc.returncode}",
              file=sys.stderr)
        return 1, (row or {"metric": "lanes_chip_loss",
                           "error": f"child rc {proc.returncode}"})
    print("[chaos] PASS (lanes chip loss): 100% available, one "
          "quarantine, one generation bump per epoch, re-admitted",
          file=sys.stderr)
    return 0, row


# --- rows 11-12 (ISSUE 19): digest ownership under owner death ---------------


def _spawn_claim_holder(fleet_path: str) -> subprocess.Popen:
    """A claim holder that wins a known digest's claim and stalls — its
    exclusive byte lock stays kernel-held until we SIGKILL it. Wears a
    high worker index no serving worker occupies, so deposing it (epoch
    stamp) fences only this holder."""
    code = (
        "import hashlib, time\n"
        "from imaginary_tpu.fleet.shmcache import ShmCache\n"
        f"w = ShmCache({fleet_path!r}, create=False, worker=50, epoch=0)\n"
        "c = w.claim_acquire(hashlib.sha256(b'chaos-claim').digest())\n"
        "print('claimed' if c.won else 'lost', flush=True)\n"
        "time.sleep(120)\n"
    )
    return subprocess.Popen([sys.executable, "-c", code], cwd=ROOT,
                            stdout=subprocess.PIPE)


async def _ownership_kill_soak(duration: float, concurrency: int) -> dict:
    from imaginary_tpu.fleet.shmcache import ShmCache

    # hop budget sized for cold-compile first waves (a 1-cpu host can
    # serialize several compiles ahead of a hop); coalesce ON so the
    # local flight groups and the fleet claims compose under the storm
    fleet = _Fleet(extra_args=["--fleet-coherence", "--cache-coalesce",
                               "--fleet-hop-ms", "15000"])
    counts: dict = {}
    out = {"ok": 0, "fail": 0, "waves": 0, "respawned": False}
    try:
        await fleet.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            workers0 = await fleet.wait_workers(session)
            victim_pid = workers0[1]["pid"]

            async def storm(seconds: float) -> None:
                # wave storm: every wave is N CONCURRENT IDENTICAL
                # requests to a FRESH url — each wave is one coalesce
                # group per worker and (fleet-wide) one claim, so the
                # publish count meters duplicate executions directly
                deadline = time.monotonic() + seconds
                i = 0
                while time.monotonic() < deadline:
                    u = fleet.url(i % 64)
                    oks = await asyncio.gather(
                        *[_lb_get(session, u, counts)
                          for _ in range(concurrency)])
                    for ok in oks:
                        out["ok" if ok else "fail"] += 1
                    out["waves"] += 1
                    i += 1

            async def kill_mid_storm():
                await asyncio.sleep(max(duration / 3, 1.0))
                os.kill(victim_pid, signal.SIGKILL)
                print(f"[chaos] ownership-kill: SIGKILLed worker pid "
                      f"{victim_pid} mid-coalesce", file=sys.stderr)

            await asyncio.gather(storm(max(duration, 4.0)), kill_mid_storm())
            respawned = False
            end = time.monotonic() + 60.0
            while time.monotonic() < end:
                try:
                    h = await fleet.health(session)
                    if h["worker"] == 1 and h["pid"] != victim_pid:
                        respawned = True
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.2)
            out["respawned"] = respawned
            out["per_pid"] = await _fleet_counters(fleet, session)
            # ledgers at rest, against the LIVE file: after one sweep no
            # claim entry may still read live or dead
            client = ShmCache(fleet.fleet_path, create=False, worker=62,
                              epoch=0)
            try:
                out["claims_swept"] = client.claim_sweep()
                out["claim_scan"] = client.claim_scan()
            finally:
                client.close()
    finally:
        await fleet.stop()
    out["counts"] = counts
    return out


def _ownership_kill_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_ownership_kill_soak(duration, concurrency))
    total = got["ok"] + got["fail"]
    per_pid = got.get("per_pid", {})
    publishes = sum(v.get("publishes", 0) for v in per_pid.values())
    corrupt_served = sum(v.get("corrupt_served", 0)
                         for v in per_pid.values())
    coh = [v.get("coherence", {}) for v in per_pid.values()]

    def csum(field):
        return sum(c.get(field, 0) for c in coh)

    # serve_forwarded counts too: it proves a request crossed the IPC hop
    # and was served by the owner even when the SENDER's clock ran out
    # first (slow-host compile storms book those hops as forward_fails)
    activity = (csum("forwards") + csum("serve_forwarded")
                + csum("claim_waits") + csum("waiter_hits")
                + csum("redispatches") + csum("local_fallbacks"))
    distinct = min(got["waves"], 64)
    row = {
        "metric": "chaos_ownership_kill",
        "requests": total,
        "ok": got["ok"],
        "ok_ratio": round(got["ok"] / total, 4) if total else 0.0,
        "waves": got["waves"],
        "distinct_urls": distinct,
        "publishes": publishes,
        "respawned": got["respawned"],
        "corrupt_served_total": corrupt_served,
        "coherence": {f: csum(f) for f in
                      ("forwards", "forward_fails", "serve_forwarded",
                       "claim_waits", "waiter_hits", "waiter_timeouts",
                       "redispatches", "local_fallbacks")},
        "claims_swept": got.get("claims_swept"),
        "claim_scan": got.get("claim_scan"),
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if total == 0:
        fails.append("ownership kill storm produced zero requests")
    if total and got["ok"] / total < 0.99:
        fails.append(f"availability {got['ok']}/{total} below 99% under "
                     "digest-owner SIGKILL")
    if not got["respawned"]:
        fails.append("killed digest owner never respawned")
    if corrupt_served:
        fails.append(f"{corrupt_served} corrupt-byte serves (tripwire)")
    if activity == 0:
        fails.append("coherence layer never exercised (no forwards, "
                     "claims or fallbacks booked)")
    # duplicates <= waiters: each wave is one digest; the singleflight
    # bound allows at most the wave itself plus the bounded fail-open
    # duplicates (owner death, hop timeout) — 2x + slack covers a kill
    # landing mid-wave on every URL without ever permitting N-x blowup
    if publishes > 2 * distinct + 8:
        fails.append(f"{publishes} publishes for {distinct} distinct "
                     "digests — fleet singleflight did not hold")
    scan = got.get("claim_scan") or {}
    if scan.get("live", 1) != 0 or scan.get("dead", 1) != 0:
        fails.append(f"claim table not at rest after sweep: {scan}")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (ownership SIGKILL): {got['ok']}/{total} ok over "
          f"{got['waves']} waves, {publishes} publishes for {distinct} "
          f"digests, owner respawned, coherence activity {activity}, "
          "claim table at rest", file=sys.stderr)
    return 0, row


async def _ownership_zombie_soak(duration: float, concurrency: int) -> dict:
    from imaginary_tpu.fleet.shmcache import ShmCache

    fleet = _Fleet(
        extra_args=["--fleet-coherence"],
        extra_env={
            "IMAGINARY_TPU_SUPERVISOR_PROBE_INTERVAL": "0.3",
            "IMAGINARY_TPU_SUPERVISOR_PROBE_TIMEOUT": "1.0",
            "IMAGINARY_TPU_SUPERVISOR_LIVENESS_TIMEOUT": "4.0",
            "IMAGINARY_TPU_SUPERVISOR_HANG_GRACE": "2.0",
            "IMAGINARY_TPU_SUPERVISOR_BOOT_GRACE": "20.0",
        })
    counts: dict = {}
    out = {"replaced": False, "zombie_exited": False, "fence": {},
           "stale": {}, "ok": 0, "fail": 0}
    try:
        await fleet.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            workers0 = await fleet.wait_workers(session)
            await asyncio.sleep(3.0)
            zpid, zepoch = workers0[1]["pid"], workers0[1]["epoch"]
            print(f"[chaos] ownership-zombie: SIGSTOP worker 1 (pid {zpid}, "
                  f"epoch {zepoch})", file=sys.stderr)
            os.kill(zpid, signal.SIGSTOP)
            end = time.monotonic() + 90.0
            new_epoch = None
            while time.monotonic() < end:
                try:
                    h = await fleet.health(session, timeout=1.5)
                    if h["worker"] == 1 and h["pid"] != zpid \
                            and h["epoch"] > zepoch:
                        new_epoch = h["epoch"]
                        out["replaced"] = True
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.2)
            # fence, against the LIVE file: a claimant wearing the
            # zombie's identity must be refused at acquire — a deposed
            # owner can never become the fleet's executor for a digest
            zc = ShmCache(fleet.fleet_path, create=False, worker=1,
                          epoch=zepoch)
            try:
                import hashlib

                c = zc.claim_acquire(hashlib.sha256(b"zombie-bid").digest())
                try:
                    out["fence"] = {
                        "old_epoch": zepoch, "new_epoch": new_epoch,
                        "won": c.won, "busy": c.busy,
                        "fenced_claims": zc.stats.fenced_claims,
                    }
                finally:
                    zc.claim_release(c)
            finally:
                zc.close()
            # stale detection: a live-but-deposed holder (SIGSTOP shape:
            # kernel lock still held) must read STALE to the fleet, not
            # busy — and one sweep reclaims the entry
            holder = _spawn_claim_holder(fleet.fleet_path)
            live = ShmCache(fleet.fleet_path, create=False, worker=63,
                            epoch=0)
            try:
                assert b"claimed" in holder.stdout.readline()
                import hashlib

                k = hashlib.sha256(b"chaos-claim").digest()
                live.stamp_epoch(50, 9)  # depose the stalled holder
                c = live.claim_acquire(k)
                try:
                    out["stale"] = {
                        "won": c.won, "busy": c.busy, "stale": c.stale,
                        "claims_stale": live.stats.claims_stale,
                    }
                finally:
                    live.claim_release(c)
                out["stale"]["swept"] = live.claim_sweep()
                out["stale"]["scan"] = live.claim_scan()
            finally:
                live.close()
                holder.kill()
                holder.wait()
            # wake the zombie into its queued SIGTERM; it must exit. The
            # supervisor may have already escalated and reaped it (its
            # liveness probe kills a stopped worker) — also a clean exit.
            try:
                os.kill(zpid, signal.SIGCONT)
            except ProcessLookupError:
                out["zombie_exited"] = True
            end = time.monotonic() + 30.0
            while time.monotonic() < end:
                try:
                    os.kill(zpid, 0)
                except ProcessLookupError:
                    out["zombie_exited"] = True
                    break
                await asyncio.sleep(0.2)
            for _ in range(20):
                ok = await _lb_get(session, fleet.url(0), counts)
                out["ok" if ok else "fail"] += 1
    finally:
        await fleet.stop()
    out["counts"] = counts
    return out


def _ownership_zombie_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_ownership_zombie_soak(duration, concurrency))
    f, s = got["fence"], got["stale"]
    row = {
        "metric": "chaos_ownership_zombie",
        "replaced": got["replaced"],
        "zombie_exited": got["zombie_exited"],
        "fence": f,
        "stale": s,
        "post_recovery_ok": got["ok"],
        "post_recovery_fail": got["fail"],
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if not got["replaced"]:
        fails.append("SIGSTOPped owner was never replaced by the "
                     "liveness probe")
    if f.get("won") or f.get("busy") or f.get("fenced_claims") != 1:
        fails.append(f"zombie identity was NOT refused at claim_acquire "
                     f"({f})")
    if s.get("won") or s.get("busy") or not s.get("stale") \
            or s.get("claims_stale") != 1:
        fails.append(f"deposed live holder not detected STALE ({s})")
    if s.get("swept", 0) < 1 or (s.get("scan") or {}).get("live", 1) != 0:
        fails.append(f"zombie-held claim not reclaimed by sweep ({s})")
    if not got["zombie_exited"]:
        fails.append("revived zombie never exited")
    if got["fail"]:
        fails.append(f"{got['fail']} post-recovery requests failed")
    if fails:
        for fl in fails:
            print(f"[chaos] FAIL: {fl}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (ownership zombie): replaced at epoch "
          f"{f.get('new_epoch')} (old {f.get('old_epoch')}), zombie claim "
          "refused, deposed holder read stale and was swept, "
          f"{got['ok']}/20 post-recovery ok", file=sys.stderr)
    return 0, row


class _MultihostCluster:
    """Two real 2-worker supervisor fleets (distinct host ids, admin
    planes and shm files) cross-pointed via --peers, sharing one origin.
    The smallest honest cluster: gossip, routing and spillover all ride
    real sockets between real supervisors."""

    def __init__(self):
        self.origin_runner = None
        self.origin_base = None
        self.ports = {}
        self.admins = {}
        self.paths = {}
        self.sups = {}

    async def start(self):
        from bench_cache import N_URLS, _start_origin
        from bench_util import free_port, make_1080p_jpeg

        base_jpeg = make_1080p_jpeg()
        variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]
        self.origin_runner, self.origin_base = await _start_origin(variants)
        for h in ("a", "b"):
            self.ports[h] = free_port()
            self.admins[h] = free_port()
            fd, path = tempfile.mkstemp(prefix=f"chaos-mh-{h}-",
                                        suffix=".shm")
            os.close(fd)
            os.unlink(path)
            self.paths[h] = path
        for h in ("a", "b"):
            self.spawn(h)

    def spawn(self, h: str):
        peer = "b" if h == "a" else "a"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        for k in ("IMAGINARY_TPU_WORKER", "IMAGINARY_TPU_WORKER_EPOCH",
                  "IMAGINARY_TPU_FAILPOINTS", "IMAGINARY_TPU_HOST_ID",
                  "IMAGINARY_TPU_HOST_EPOCH"):
            env.pop(k, None)
        env["IMAGINARY_TPU_FLEET_PATH"] = self.paths[h]
        self.sups[h] = subprocess.Popen(
            [sys.executable, "-m", "imaginary_tpu.cli", "--workers", "2",
             "--port", str(self.ports[h]), "--enable-url-source",
             "--cache-result-mb", "16", "--fleet-cache-mb", "16",
             "--request-timeout", "10", "--host-id", f"host-{h}",
             "--fleet-admin-port", str(self.admins[h]),
             "--peers", f"http://127.0.0.1:{self.admins[peer]}",
             "--router", "--peer-probe-interval", "0.3"],
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        return self.sups[h]

    async def health(self, session, h: str, timeout=2.0):
        async with session.get(
                f"http://127.0.0.1:{self.ports[h]}/health",
                headers={"Connection": "close"},
                timeout=aiohttp.ClientTimeout(total=timeout)) as r:
            return await r.json()

    async def wait_workers(self, session, h: str, n=2,
                           deadline_s=120.0) -> dict:
        seen: dict = {}
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            if self.sups[h].poll() is not None:
                raise RuntimeError(
                    f"host {h} supervisor exited {self.sups[h].poll()} "
                    "during boot")
            try:
                hh = await self.health(session, h)
                seen[hh["worker"]] = {"pid": hh["pid"],
                                      "epoch": hh["epoch"]}
                if len(seen) >= n:
                    return seen
            except Exception:
                pass
            await asyncio.sleep(0.2)
        raise RuntimeError(f"host {h} never reached {n} workers ({seen})")

    async def cluster_view(self, session, h: str) -> dict:
        async with session.get(
                f"http://127.0.0.1:{self.admins[h]}/fleetz?scope=cluster",
                headers={"Connection": "close"},
                timeout=aiohttp.ClientTimeout(total=2.0)) as r:
            return await r.json()

    def url(self, i: int) -> str:
        return (f"http://127.0.0.1:{self.ports['a']}/resize?width=300"
                f"&height=200&url={self.origin_base}/img/{i}")

    async def stop(self):
        for sup in self.sups.values():
            if sup is not None and sup.poll() is None:
                sup.send_signal(signal.SIGTERM)
        for sup in self.sups.values():
            if sup is None:
                continue
            try:
                await asyncio.get_event_loop().run_in_executor(
                    None, sup.wait, 20)
            except subprocess.TimeoutExpired:
                sup.kill()
                sup.wait()
        if self.origin_runner is not None:
            await self.origin_runner.cleanup()
        for path in self.paths.values():
            if path and os.path.exists(path):
                try:
                    os.unlink(path)
                except OSError:
                    pass


async def _multihost_kill_soak(duration: float, concurrency: int) -> dict:
    from bench_cache import N_URLS, ZIPF_S, _zipf_indices

    cluster = _MultihostCluster()
    counts: dict = {}
    out = {"ok": 0, "fail": 0, "monotonic": True, "regressions": [],
           "routing": {}, "epoch_bumps": 0, "b_rejoined": False,
           "killed": 0}
    try:
        await cluster.start()
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            await cluster.wait_workers(session, "a")
            workers_b = await cluster.wait_workers(session, "b")
            # gossip convergence: A's admin must see host-b alive before
            # the storm (the workers' own tables ride the same cadence)
            end = time.monotonic() + 60.0
            while time.monotonic() < end:
                view = await cluster.cluster_view(session, "a")
                if view.get("hosts", {}).get("host-b", {}).get("alive"):
                    break
                await asyncio.sleep(0.3)
            else:
                raise RuntimeError("A never gossiped host-b alive")
            await asyncio.sleep(1.0)

            # per-pid multihost counter streams from A's /health: every
            # sample must be >= the last for that pid (counters only grow
            # — a reset would mean state was lost without a worker death)
            last: dict = {}
            stop_sampling = asyncio.Event()

            async def sample_monotonic():
                fields = ("forwards", "forward_fails", "fenced_answers",
                          "spills", "spill_fails", "served_for_peer",
                          "local_fallbacks")
                while not stop_sampling.is_set():
                    try:
                        h = await cluster.health(session, "a", timeout=1.5)
                        snap = h.get("multihost")
                        if isinstance(snap, dict):
                            pid = h["pid"]
                            prev = last.get(pid)
                            cur = {f: snap.get(f, 0) for f in fields}
                            if prev is not None:
                                for f in fields:
                                    if cur[f] < prev[f]:
                                        out["monotonic"] = False
                                        out["regressions"].append(
                                            {"pid": pid, "field": f,
                                             "from": prev[f],
                                             "to": cur[f]})
                            last[pid] = cur
                    except Exception:
                        pass
                    await asyncio.sleep(0.15)

            sampler = asyncio.create_task(sample_monotonic())

            async def client(k: int):
                idx = _zipf_indices(6000 + k, N_URLS, ZIPF_S)
                j = 0
                while time.monotonic() < storm_end:
                    okd = await _lb_get(session, cluster.url(idx[j % len(idx)]),
                                        counts)
                    out["ok" if okd else "fail"] += 1
                    j += 1

            storm_end = time.monotonic() + max(duration, 8.0)
            kill_at = time.monotonic() + max(duration, 8.0) * 0.35
            clients = [asyncio.create_task(client(k))
                       for k in range(concurrency)]

            # mid-storm: SIGKILL the WHOLE of host B — supervisor and
            # both workers, no grace, no drain
            while time.monotonic() < kill_at:
                await asyncio.sleep(0.1)
            victims = [cluster.sups["b"].pid] + \
                [w["pid"] for w in workers_b.values()]
            print(f"[chaos] multihost: SIGKILL host-b entirely "
                  f"(pids {victims})", file=sys.stderr)
            for pid in victims:
                try:
                    os.kill(pid, signal.SIGKILL)
                    out["killed"] += 1
                except ProcessLookupError:
                    pass
            cluster.sups["b"].wait()

            # let the storm run against the half-cluster, then restart
            # host B (same id, FRESH minted epoch) while clients still run
            await asyncio.sleep(max(duration, 8.0) * 0.25)
            if os.path.exists(cluster.paths["b"]):
                os.unlink(cluster.paths["b"])
            cluster.spawn("b")
            await asyncio.gather(*clients)
            stop_sampling.set()
            await sampler

            # B rejoins the cluster under a bumped host epoch
            end = time.monotonic() + 90.0
            while time.monotonic() < end:
                try:
                    view = await cluster.cluster_view(session, "a")
                    hb = view.get("hosts", {}).get("host-b", {})
                    if hb.get("alive") and hb.get("epoch_bumps", 0) >= 1:
                        out["b_rejoined"] = True
                        out["epoch_bumps"] = hb["epoch_bumps"]
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.3)
            out["routing"] = {
                f: sum(c.get(f, 0) for c in last.values())
                for f in ("forwards", "forward_fails", "fenced_answers",
                          "served_for_peer", "local_fallbacks")}
    finally:
        await cluster.stop()
    out["counts"] = counts
    return out


def _multihost_kill_row(duration: float, concurrency: int) -> tuple:
    got = asyncio.run(_multihost_kill_soak(duration, concurrency))
    total = got["ok"] + got["fail"]
    routing = got["routing"]
    row = {
        "metric": "chaos_multihost_kill",
        "requests": total,
        "ok": got["ok"],
        "ok_ratio": round(got["ok"] / total, 4) if total else 0.0,
        "killed_pids": got["killed"],
        "monotonic": got["monotonic"],
        "regressions": got["regressions"][:8],
        "b_rejoined": got["b_rejoined"],
        "epoch_bumps": got["epoch_bumps"],
        "routing": routing,
        "counts": {str(k): v for k, v in sorted(got["counts"].items(),
                                                key=str)},
    }
    print(json.dumps(row))
    fails = []
    if total == 0:
        fails.append("multihost kill storm produced zero requests")
    if total and got["ok"] / total < 0.99:
        fails.append(f"availability {got['ok']}/{total} below 99% with "
                     "host-b SIGKILLed mid-storm (fail-open broke)")
    if got["killed"] < 3:
        fails.append(f"only {got['killed']} host-b pids killed (wanted "
                     "supervisor + 2 workers)")
    if not got["monotonic"]:
        fails.append(f"fleet metrics regressed: {got['regressions'][:3]}")
    if not got["b_rejoined"]:
        fails.append("host-b never rejoined the cluster view with a "
                     "bumped host epoch")
    if sum(routing.values()) == 0:
        fails.append("router never exercised (no forwards, fails or "
                     "fallbacks booked on host-a)")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1, row
    print(f"[chaos] PASS (multihost host-kill): {got['ok']}/{total} ok "
          f"with host-b dead mid-storm, metrics monotonic, rejoined with "
          f"{got['epoch_bumps']} epoch bump(s), routing {routing}",
          file=sys.stderr)
    return 0, row


def main() -> int:
    from imaginary_tpu import failpoints
    from bench_util import ensure_native_built

    ensure_native_built()
    duration = float(os.environ.get("BENCH_DURATION", "6"))
    concurrency = int(os.environ.get("BENCH_CONCURRENCY", "8"))
    os.environ[failpoints.ENV_VAR] = os.environ.get(
        "CHAOS_FAILPOINTS", "source.fetch=error(0.2)")

    print(f"[chaos] soak with {os.environ[failpoints.ENV_VAR]!r}: "
          f"{concurrency} clients x {duration}s", file=sys.stderr)
    got = asyncio.run(_soak(duration, concurrency))
    failpoints.deactivate()
    counts = got["counts"]
    total = sum(counts.values())
    ok = counts.get(200, 0)
    allowed_errors = sum(counts.get(s, 0) for s in (502, 503, 504))
    surprises = total - ok - allowed_errors
    row = {
        "metric": "chaos_soak",
        "failpoints": os.environ[failpoints.ENV_VAR],
        "requests": total,
        "ok": ok,
        "ok_ratio": round(ok / total, 4) if total else 0.0,
        "mapped_errors": allowed_errors,
        "surprises": surprises,
        "worst_ms": round(got["worst_ms"], 1),
        "inflight_after": got["inflight_after"],
        "coalesce_groups_after": got["groups_after"],
        "counts": {str(k): v for k, v in sorted(counts.items(), key=str)},
    }
    print(json.dumps(row))

    fails = []
    if total == 0:
        fails.append("soak produced zero requests")
    if total and ok / total < 0.95:
        fails.append(f"availability {ok}/{total} below 95% under 0.2 fault rate")
    if surprises:
        fails.append(f"{surprises} responses outside 200/502/503/504")
    if got["bad_bodies"]:
        fails.append(f"{got['bad_bodies']} empty 200 bodies")
    if got["worst_ms"] > 12_000.0:
        fails.append(f"worst request {got['worst_ms']:.0f}ms outlived the 10s deadline")
    if got["inflight_after"] != 0:
        fails.append(f"_inflight ledger leaked {got['inflight_after']}")
    if got["groups_after"] != 0:
        fails.append(f"coalescer leaked {got['groups_after']} groups")
    if fails:
        for f in fails:
            print(f"[chaos] FAIL: {f}", file=sys.stderr)
        return 1
    print(f"[chaos] PASS: {ok}/{total} ok, {allowed_errors} mapped errors, "
          f"worst {got['worst_ms']:.0f}ms, ledgers at rest", file=sys.stderr)

    # ROW 2: chip loss. The env-armed source failpoints must not leak
    # into this server (create_app re-arms from the env var).
    os.environ.pop(failpoints.ENV_VAR, None)
    rc = _chip_loss_row(duration, concurrency)
    if rc:
        return rc
    # ROW 3: hedged failover vs a 250 ms-delayed device, A-B
    rc = _hedge_row(duration, concurrency)
    if rc:
        return rc
    # ROW 4: OOM storm — bisect-retry + host routing keep availability
    rc = _oom_storm_row(max(duration / 2, 2.0), concurrency)
    if rc:
        return rc
    # ROW 5 + 6 (ISSUE 10): SDC storm + fail-slow; their integrity/
    # devhealth counters are archived next to the BENCH artifacts
    rc_sdc, sdc_row = _sdc_storm_row(duration, concurrency)
    rc_fs, fs_row = _failslow_row(duration, concurrency)
    try:
        os.makedirs("artifacts", exist_ok=True)
        with open("artifacts/chaos_integrity.json", "w") as f:
            json.dump({"sdc_storm": sdc_row, "failslow": fs_row}, f,
                      indent=2, sort_keys=True)
        print("[chaos] integrity counters archived to "
              "artifacts/chaos_integrity.json", file=sys.stderr)
    except OSError as e:
        print(f"[chaos] WARN: could not archive integrity counters: {e}",
              file=sys.stderr)
    if rc_sdc or rc_fs:
        return rc_sdc or rc_fs
    # ROWS 7-9 (ISSUE 11): the fleet tier — real 2-worker subprocess
    # fleets under process-kill chaos; counters archived per row
    rc_kill, kill_row = _fleet_kill_row(duration, concurrency)
    if rc_kill:
        return rc_kill
    rc_zombie, zombie_row = _fleet_zombie_row(duration, concurrency)
    if rc_zombie:
        return rc_zombie
    rc_roll, roll_row = _fleet_roll_row(duration, concurrency)
    try:
        with open("artifacts/chaos_fleet.json", "w") as f:
            json.dump({"kill_storm": kill_row, "zombie_fence": zombie_row,
                       "sighup_roll": roll_row}, f, indent=2, sort_keys=True)
        print("[chaos] fleet counters archived to "
              "artifacts/chaos_fleet.json", file=sys.stderr)
    except OSError as e:
        print(f"[chaos] WARN: could not archive fleet counters: {e}",
              file=sys.stderr)
    if rc_roll:
        return rc_roll
    # ROW 10 (ISSUE 15): per-chip lanes lose chip 0 mid-run — runs in a
    # 4-device child process (this one is pinned at 2)
    rc_lanes, lanes_row = _lanes_chip_loss_row()
    try:
        with open("artifacts/chaos_lanes.json", "w") as f:
            json.dump({"lanes_chip_loss": lanes_row}, f, indent=2,
                      sort_keys=True)
        print("[chaos] lane counters archived to "
              "artifacts/chaos_lanes.json", file=sys.stderr)
    except OSError as e:
        print(f"[chaos] WARN: could not archive lane counters: {e}",
              file=sys.stderr)
    if rc_lanes:
        return rc_lanes
    # ROWS 11-12 (ISSUE 19): digest ownership under owner death — the
    # SIGKILL-mid-coalesce storm and the SIGSTOP zombie claim fence
    rc_own_kill, own_kill_row = _ownership_kill_row(duration, concurrency)
    rc_own_zombie, own_zombie_row = _ownership_zombie_row(duration,
                                                          concurrency)
    try:
        with open("artifacts/chaos_ownership.json", "w") as f:
            json.dump({"ownership_kill": own_kill_row,
                       "ownership_zombie": own_zombie_row}, f, indent=2,
                      sort_keys=True)
        print("[chaos] ownership counters archived to "
              "artifacts/chaos_ownership.json", file=sys.stderr)
    except OSError as e:
        print(f"[chaos] WARN: could not archive ownership counters: {e}",
              file=sys.stderr)
    if rc_own_kill or rc_own_zombie:
        return rc_own_kill or rc_own_zombie
    # ROW 13 (ISSUE 20): a whole 2-worker host SIGKILLed out of a 2-host
    # cluster mid-storm — availability holds on the survivor, its fleet
    # metrics stay monotonic, and the dead host rejoins under a bumped
    # host epoch
    rc_mh, mh_row = _multihost_kill_row(duration, concurrency)
    try:
        with open("artifacts/chaos_multihost.json", "w") as f:
            json.dump({"multihost_kill": mh_row}, f, indent=2,
                      sort_keys=True)
        print("[chaos] multihost counters archived to "
              "artifacts/chaos_multihost.json", file=sys.stderr)
    except OSError as e:
        print(f"[chaos] WARN: could not archive multihost counters: {e}",
              file=sys.stderr)
    return rc_mh


if __name__ == "__main__":
    if "--lanes-row" in sys.argv:
        sys.exit(_lanes_chip_loss_child())
    sys.exit(main())
