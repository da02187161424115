#!/usr/bin/env python
"""Observability overhead benchmark: the row the tracing layer is graded on.

Reuses bench_cache.py's zipf hot-URL harness with every cache tier OFF —
the cache-off row is the headline number (every request pays fetch ->
decode -> process -> encode), so per-request tracing cost cannot hide
behind cache hits. Two arms on the same host:

  * tracing ON  (the default serving config: request ids, spans,
    Server-Timing, request/stage histograms, slow-request ring)
  * tracing OFF (--disable-tracing: span accumulation and per-request
    surfaces suppressed; metrics histograms — an always-on /metrics
    surface, like TIMES — keep recording in both arms)

A second row exercises the fleet observability plane end to end: a real
2-worker supervisor subprocess with --wide-events-sample 0.02 and
--fleet-admin-port, driven with boring traffic plus deliberate faults
(garbage bodies -> 400) while the supervisor-aggregated /metrics is
scraped under load. Gates: tail sampling keeps 100% of fault events
while total wide-event volume drops >= 10x vs requests served, and
scraping the admin plane moves request p50 by no more than
BENCH_OBS_FLEET_MAX_OVERHEAD_PCT (default 25 — p50 deltas on 1-2s
slices are noisy; the criterion is "within noise", not a tight budget).
The fleet row is archived to artifacts/bench_obs_fleet.jsonl.

Two cost-plane rows ride along (archived to artifacts/bench_obs_cost.jsonl):

  * attribution overhead — the same zipf harness ABBA-toggled on
    --cost-attribution; the gate is paced p50 within
    BENCH_OBS_COST_MAX_OVERHEAD_PCT (default 25 — the fleet row's
    "within noise" criterion, not a tight budget).
  * hog flood — a batch-class tenant floods beside paced interactive
    traffic on a cost-armed server; /topz must rank the hog #1 by
    chip-ms within one 10s window, and the live bound_by advisor must
    return a verdict.

Prints one JSON line per row on stdout; human detail on stderr. Exits
nonzero when the tracing ON arm lost more than
BENCH_OBS_MAX_OVERHEAD_PCT (default 10 — a gross-regression gate
tolerant of short-run noise; the acceptance criterion is <= 2% on a
full-length run), when tracing surfaces are missing from responses, or
when any fleet-row or cost-row gate breaches.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import os
import sys
import time

import aiohttp

from bench_cache import N_URLS, ZIPF_S, _start_origin, _start_server, _zipf_indices
from bench_util import ensure_native_built, make_1080p_jpeg, pctl


async def _arm(options, variants, duration: float, concurrency: int,
               check_headers: bool):
    origin_runner, origin_base = await _start_origin(variants)
    server_runner, app, base = await _start_server(options)
    try:
        seq = _zipf_indices(200_000, N_URLS, ZIPF_S)
        urls = itertools.cycle([
            f"{base}/resize?width=300&height=200&url={origin_base}/img/{i}"
            for i in seq
        ])
        conn = aiohttp.TCPConnector(limit=0)
        lats: list = []
        errors = [0]
        async with aiohttp.ClientSession(connector=conn) as session:
            # warmup outside the timed window (XLA compiles, first fetches)
            for _ in range(4):
                async with session.get(next(urls)) as r:
                    await r.read()
                    if check_headers:
                        assert r.headers.get("X-Request-ID"), \
                            "tracing arm response missing X-Request-ID"
                        assert "decode;dur=" in r.headers.get(
                            "Server-Timing", ""), \
                            "tracing arm response missing Server-Timing spans"
            deadline = time.monotonic() + duration

            async def worker():
                while time.monotonic() < deadline:
                    t0 = time.monotonic()
                    try:
                        async with session.get(next(urls)) as res:
                            await res.read()
                            if res.status != 200:
                                errors[0] += 1
                                continue
                    except Exception:
                        errors[0] += 1
                        continue
                    lats.append((time.monotonic() - t0) * 1000.0)

            t0 = time.monotonic()
            await asyncio.gather(*[worker() for _ in range(concurrency)])
            elapsed = time.monotonic() - t0
        return (len(lats) / elapsed if elapsed else 0.0), lats, errors[0]
    finally:
        await server_runner.cleanup()
        await origin_runner.cleanup()


_FLEET_SAMPLE = 0.02     # firehose cut the fleet row is graded on
_FAULT_EVERY = 25        # every Nth request posts a garbage body (-> 400)


def _fleet_row(duration: float, concurrency: int, jpeg: bytes) -> int:
    """2-worker fleet arm: tail-sampling retention/volume + scrape overhead."""
    import signal
    import subprocess
    import threading
    import urllib.error
    import urllib.request

    from bench_util import free_port
    from imaginary_tpu.obs.aggregate import parse_exposition

    port, admin_port = free_port(), free_port()
    fleet_max = float(os.environ.get("BENCH_OBS_FLEET_MAX_OVERHEAD_PCT", "25"))
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "imaginary_tpu.cli",
         "--workers", "2", "--port", str(port),
         "--wide-events", "--wide-events-sample", str(_FLEET_SAMPLE),
         "--fleet-admin-port", str(admin_port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)

    # drain the supervisor's pipe from a thread: workers inherit this fd for
    # wide events + access log, and an undrained 64KB pipe deadlocks the fleet
    event_lines: list = []
    def _reader():
        for raw in proc.stdout:
            line = raw.decode("utf-8", "replace").strip()
            if line.startswith("{"):
                event_lines.append(line)
    reader = threading.Thread(target=_reader, daemon=True)
    reader.start()

    def _get(url, timeout=15.0):
        req = urllib.request.Request(url, headers={"Connection": "close"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()

    url = f"http://127.0.0.1:{port}/resize?width=64"
    lock = threading.Lock()
    state = {"n": 0, "faults_acked": 0, "client_errors": 0}

    def _traffic(dur: float):
        lats: list = []
        stop = time.monotonic() + dur

        def w():
            while time.monotonic() < stop:
                with lock:
                    state["n"] += 1
                    fault = state["n"] % _FAULT_EVERY == 0
                body = b"deliberately-not-a-jpeg" if fault else jpeg
                req = urllib.request.Request(
                    url, data=body, headers={"Connection": "close"})
                t0 = time.monotonic()
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        r.read()
                        status = r.status
                except urllib.error.HTTPError as e:
                    e.read()
                    status = e.code
                except Exception:
                    with lock:
                        state["client_errors"] += 1
                    continue
                dt = (time.monotonic() - t0) * 1000.0
                with lock:
                    if fault:
                        if status >= 400:
                            state["faults_acked"] += 1
                    elif status == 200:
                        lats.append(dt)

        threads = [threading.Thread(target=w) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats

    scrape = {"count": 0, "lats": [], "last": ""}

    def _scraper(stop_evt: threading.Event):
        # paced at ~4/s — far hotter than any real scrape interval, without
        # degenerating into back-to-back aggregation (each scrape fans out
        # repeated worker fetches, so a zero-gap loop measures a DoS, not a
        # scraper)
        while not stop_evt.is_set():
            t0 = time.monotonic()
            try:
                _, body = _get(
                    f"http://127.0.0.1:{admin_port}/metrics", timeout=20)
                scrape["last"] = body.decode()
                scrape["lats"].append((time.monotonic() - t0) * 1000.0)
                scrape["count"] += 1
            except Exception:
                pass
            stop_evt.wait(0.25)

    try:
        # boot: both workers serving (distinct pids) before anything is timed
        deadline = time.monotonic() + 180
        pids: set = set()
        while time.monotonic() < deadline and len(pids) < 2:
            try:
                _, body = _get(f"http://127.0.0.1:{port}/health", timeout=5)
                pids.add(json.loads(body).get("pid"))
            except Exception:
                time.sleep(0.5)
        if len(pids) < 2:
            print("[obs-bench] FAIL: fleet never reached 2 serving workers",
                  file=sys.stderr)
            return 1
        _traffic(1.0)  # warmup: XLA compiles on both workers, untimed

        slice_s = max(duration / 2.0, 1.0)
        lats_quiet: list = []
        lats_scraped: list = []
        for arm_scrape in (False, True, True, False):  # ABBA, as above
            if arm_scrape:
                stop_evt = threading.Event()
                st = threading.Thread(target=_scraper, args=(stop_evt,))
                st.start()
                lats_scraped.extend(_traffic(slice_s))
                stop_evt.set()
                st.join(timeout=30)
            else:
                lats_quiet.extend(_traffic(slice_s))

        # fleet-wide request total from the aggregated plane itself: the
        # denominator for the volume-cut gate, taken before teardown
        _, body = _get(f"http://127.0.0.1:{admin_port}/metrics", timeout=20)
        fams = parse_exposition(body.decode())
        req_fam = fams.get("imaginary_tpu_requests_total")
        requests_total = sum(req_fam.samples.values()) if req_fam else 0.0

        time.sleep(1.0)  # let the last events cross the pipe
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
    reader.join(timeout=15)

    events = []
    for line in event_lines:
        try:
            events.append(json.loads(line))
        except ValueError:
            pass
    fault_events = [e for e in events
                    if e.get("sampled_reason") == "error"
                    and int(e.get("status", 0)) >= 400]
    stamped = sum(1 for e in events
                  if "worker" in e and "epoch" in e and "sampled_reason" in e)
    volume_cut = (requests_total / len(events)) if events else 0.0
    p50_quiet, p50_scraped = pctl(lats_quiet, 0.50), pctl(lats_scraped, 0.50)
    scrape_overhead = (100.0 * (p50_scraped - p50_quiet) / p50_quiet) \
        if p50_quiet else 0.0

    row = {
        "metric": "obs_fleet_tail_sampling",
        "sample": _FLEET_SAMPLE,
        "requests_total": round(requests_total, 0),
        "events_total": len(events),
        "events_fault": len(fault_events),
        "faults_injected": state["faults_acked"],
        "volume_cut_x": round(volume_cut, 1),
        "scrapes": scrape["count"],
        "scrape_p50_ms": pctl(scrape["lats"], 0.50),
        "p50_ms": p50_scraped,
        "p50_ms_no_scrape": p50_quiet,
        "scrape_overhead_pct": round(scrape_overhead, 2),
        "client_errors": state["client_errors"],
    }
    print(json.dumps(row))
    os.makedirs("artifacts", exist_ok=True)
    with open(os.path.join("artifacts", "bench_obs_fleet.jsonl"), "a") as f:
        f.write(json.dumps(dict(row, ts=round(time.time(), 3))) + "\n")

    ok = True
    if state["faults_acked"] == 0 or not events:
        print("[obs-bench] FAIL: fleet row produced no faults or no events "
              f"(faults={state['faults_acked']}, events={len(events)})",
              file=sys.stderr)
        ok = False
    if len(fault_events) < state["faults_acked"]:
        print(f"[obs-bench] FAIL: tail sampling dropped fault events "
              f"({len(fault_events)}/{state['faults_acked']} retained)",
              file=sys.stderr)
        ok = False
    if stamped != len(events):
        print(f"[obs-bench] FAIL: {len(events) - stamped} events missing "
              "worker/epoch/sampled_reason stamps", file=sys.stderr)
        ok = False
    if volume_cut < 10.0:
        print(f"[obs-bench] FAIL: event volume only cut {volume_cut:.1f}x "
              f"(gate >= 10x; {len(events)} events for "
              f"{requests_total:.0f} requests)", file=sys.stderr)
        ok = False
    if scrape["count"] == 0 or not scrape["last"]:
        print("[obs-bench] FAIL: admin /metrics never scraped under load",
              file=sys.stderr)
        ok = False
    if scrape_overhead > fleet_max:
        print(f"[obs-bench] FAIL: scrape-under-load p50 overhead "
              f"{scrape_overhead:.1f}% exceeds {fleet_max:.1f}% gate",
              file=sys.stderr)
        ok = False
    if ok:
        print(f"[obs-bench] fleet row: {len(fault_events)}/"
              f"{state['faults_acked']} fault events retained, volume cut "
              f"{volume_cut:.1f}x, scrape overhead {scrape_overhead:.1f}% "
              f"over {scrape['count']} scrapes", file=sys.stderr)
    return 0 if ok else 1


def _cost_overhead_row(duration: float, concurrency: int,
                       variants: list) -> int:
    """ABBA overhead row for --cost-attribution: the tracing row's zipf
    cache-off harness, toggling only the cost plane. Gated on paced p50
    (BENCH_OBS_COST_MAX_OVERHEAD_PCT, default 25 — the fleet scrape
    row's "within noise" criterion: booking is a dict update plus a
    ring-bucket add per request, so any real p50 signal here is a bug,
    but p50 deltas on 1-2s slices are noisy)."""
    from imaginary_tpu.web.config import ServerOptions

    cost_max = float(os.environ.get("BENCH_OBS_COST_MAX_OVERHEAD_PCT", "25"))
    slice_s = max(duration / 2.0, 1.0)
    totals = {True: [0.0, [], 0], False: [0.0, [], 0]}  # rps-sum, lats, errs
    for arm_on in (False, True, True, False):  # ABBA, as above
        rps, lats, errs = asyncio.run(_arm(
            ServerOptions(enable_url_source=True, cost_attribution=arm_on),
            variants, slice_s, concurrency, check_headers=True))
        totals[arm_on][0] += rps
        totals[arm_on][1].extend(lats)
        totals[arm_on][2] += errs
    p50_off = pctl(totals[False][1], 0.50)
    p50_on = pctl(totals[True][1], 0.50)
    overhead = (100.0 * (p50_on - p50_off) / p50_off) if p50_off else 0.0
    row = {
        "metric": "obs_cost_attribution_overhead",
        "rps": round(totals[True][0] / 2, 2),
        "rps_cost_off": round(totals[False][0] / 2, 2),
        "p50_ms": p50_on,
        "p50_ms_cost_off": p50_off,
        "p99_ms": pctl(totals[True][1], 0.99),
        "p99_ms_cost_off": pctl(totals[False][1], 0.99),
        "overhead_pct": round(overhead, 2),
        "errors": totals[True][2] + totals[False][2],
    }
    print(json.dumps(row))
    os.makedirs("artifacts", exist_ok=True)
    with open(os.path.join("artifacts", "bench_obs_cost.jsonl"), "a") as f:
        f.write(json.dumps(dict(row, ts=round(time.time(), 3))) + "\n")
    if overhead > cost_max:
        print(f"[obs-bench] FAIL: cost-attribution p50 overhead "
              f"{overhead:.1f}% exceeds {cost_max:.1f}% gate", file=sys.stderr)
        return 1
    print(f"[obs-bench] cost-attribution overhead {overhead:.1f}% "
          f"(p50 {p50_off:.2f} -> {p50_on:.2f} ms)", file=sys.stderr)
    return 0


_HOG_QOS = json.dumps({
    "default": {"class": "standard"},
    "tenants": [
        {"name": "hog", "class": "batch", "api_keys": ["k-hog"]},
        {"name": "inter", "class": "interactive", "api_keys": ["k-inter"]},
    ],
})


async def _hog_arm(duration: float, concurrency: int, jpeg: bytes):
    """Flood a batch-class tenant beside paced interactive traffic on a
    cost-armed server; return per-tenant counts plus the /topz and
    /health views, both read before teardown while the whole flood still
    sits inside the live 10s accounting window."""
    from imaginary_tpu.web.config import ServerOptions

    options = ServerOptions(cost_attribution=True, qos_config=_HOG_QOS)
    server_runner, app, base = await _start_server(options)
    try:
        url = f"{base}/resize?width=300&height=200"
        conn = aiohttp.TCPConnector(limit=0)
        counts = {"hog": 0, "inter": 0, "errors": 0}
        async with aiohttp.ClientSession(connector=conn) as session:
            for _ in range(4):  # warmup: XLA compiles outside the flood
                async with session.post(url, data=jpeg,
                                        headers={"API-Key": "k-hog"}) as r:
                    await r.read()
            deadline = time.monotonic() + duration

            async def worker(name: str, key: str, pace_s: float):
                while time.monotonic() < deadline:
                    try:
                        async with session.post(
                                url, data=jpeg,
                                headers={"API-Key": key}) as res:
                            await res.read()
                            if res.status == 200:
                                counts[name] += 1
                            else:
                                counts["errors"] += 1
                    except Exception:
                        counts["errors"] += 1
                    if pace_s:
                        await asyncio.sleep(pace_s)

            tasks = [worker("hog", "k-hog", 0.0)
                     for _ in range(max(2, concurrency - 2))]
            tasks += [worker("inter", "k-inter", 0.2) for _ in range(2)]
            await asyncio.gather(*tasks)
            async with session.get(f"{base}/topz") as res:
                topz_status, topz = res.status, await res.json()
            async with session.get(f"{base}/health") as res:
                health = await res.json()
        return counts, topz_status, topz, health
    finally:
        await server_runner.cleanup()


def _hog_flood_row(duration: float, concurrency: int, jpeg: bytes) -> int:
    """Cost-plane acceptance row: /topz must rank the flooding batch
    tenant #1 by chip-ms within one 10s window, and the live bound_by
    advisor must return a verdict under the flood."""
    flood_s = min(max(duration, 2.0), 8.0)  # must fit one 10s window
    counts, topz_status, topz, health = asyncio.run(
        _hog_arm(flood_s, concurrency, jpeg))

    adv = (health.get("capacity") or {}).get("bound_by") or {}
    win = ((topz.get("windows") or {}).get("10s") or {}) \
        if topz_status == 200 and isinstance(topz, dict) else {}
    ranked = win.get("by_chip_ms") or []
    top_tenant = ranked[0].get("tenant", "") if ranked else ""

    row = {
        "metric": "obs_cost_hog_flood",
        "flood_s": round(flood_s, 1),
        "hog_requests": counts["hog"],
        "inter_requests": counts["inter"],
        "errors": counts["errors"],
        "topz_top_chip_ms": top_tenant,
        "bound_by_live": adv.get("verdict", ""),
        "advisor_window": adv.get("window", ""),
    }
    print(json.dumps(row))
    os.makedirs("artifacts", exist_ok=True)
    with open(os.path.join("artifacts", "bench_obs_cost.jsonl"), "a") as f:
        f.write(json.dumps(dict(row, ts=round(time.time(), 3))) + "\n")

    ok = True
    if topz_status != 200 or not ranked:
        print(f"[obs-bench] FAIL: /topz unusable under flood "
              f"(status={topz_status}, ranked={len(ranked)})",
              file=sys.stderr)
        ok = False
    elif top_tenant != "hog":
        print(f"[obs-bench] FAIL: /topz 10s chip-ms leader is "
              f"{top_tenant!r}, want the flooding tenant 'hog' "
              f"(rows={ranked[:3]})", file=sys.stderr)
        ok = False
    if not (counts["hog"] > counts["inter"] > 0):
        print(f"[obs-bench] FAIL: flood shape wrong (hog={counts['hog']}, "
              f"inter={counts['inter']} — want hog > inter > 0)",
              file=sys.stderr)
        ok = False
    if adv.get("verdict", "unknown") == "unknown":
        print(f"[obs-bench] FAIL: live bound_by advisor returned no "
              f"verdict under flood (advisor={adv})", file=sys.stderr)
        ok = False
    if ok:
        print(f"[obs-bench] hog-flood row: /topz leader 'hog' "
              f"({counts['hog']} hog vs {counts['inter']} interactive), "
              f"bound_by {adv['verdict']!r}",
              file=sys.stderr)
    return 0 if ok else 1


def main() -> int:
    from imaginary_tpu.web.config import ServerOptions

    ensure_native_built()
    duration = float(os.environ.get("BENCH_DURATION", "8"))
    concurrency = int(os.environ.get("BENCH_CONCURRENCY", "16"))
    max_overhead = float(os.environ.get("BENCH_OBS_MAX_OVERHEAD_PCT", "10"))

    base_jpeg = make_1080p_jpeg()
    variants = [base_jpeg + b"\x00" * (i + 1) for i in range(N_URLS)]

    print(f"[obs-bench] cache-off zipf row, tracing on vs off: "
          f"{concurrency} clients x {duration}s per arm, ABBA-interleaved",
          file=sys.stderr)
    # ABBA slice order: sequential whole arms measured +-15% phantom
    # deltas on a noisy shared host (either sign); interleaving
    # quarter-slices cancels linear load drift
    slice_s = max(duration / 2.0, 1.0)
    totals = {True: [0.0, [], 0], False: [0.0, [], 0]}  # rps-sum, lats, errs
    for arm_on in (False, True, True, False):
        rps, lats, errs = asyncio.run(_arm(
            ServerOptions(enable_url_source=True, trace_enabled=arm_on),
            variants, slice_s, concurrency, check_headers=arm_on))
        totals[arm_on][0] += rps
        totals[arm_on][1].extend(lats)
        totals[arm_on][2] += errs
    rps_off, lats_off, err_off = totals[False][0] / 2, totals[False][1], totals[False][2]
    rps_on, lats_on, err_on = totals[True][0] / 2, totals[True][1], totals[True][2]

    overhead_pct = (100.0 * (rps_off - rps_on) / rps_off) if rps_off else 0.0
    row = {
        "metric": "obs_tracing_overhead",
        "unit": "req/s",
        "value": round(rps_on, 2),
        "value_trace_off": round(rps_off, 2),
        "overhead_pct": round(overhead_pct, 2),
        "p50_ms": pctl(lats_on, 0.50),
        "p99_ms": pctl(lats_on, 0.99),
        "p50_ms_trace_off": pctl(lats_off, 0.50),
        "p99_ms_trace_off": pctl(lats_off, 0.99),
        "errors": err_on + err_off,
    }
    print(json.dumps(row))

    rc = 0
    if overhead_pct > max_overhead:
        print(f"[obs-bench] FAIL: tracing overhead {overhead_pct:.1f}% "
              f"exceeds {max_overhead:.1f}% gate", file=sys.stderr)
        rc = 1
    else:
        print(f"[obs-bench] tracing overhead {overhead_pct:.1f}% "
              f"({rps_off:.1f} -> {rps_on:.1f} req/s)", file=sys.stderr)

    print(f"[obs-bench] cost row: --cost-attribution on vs off, "
          f"ABBA-interleaved", file=sys.stderr)
    cost_rc = _cost_overhead_row(duration, concurrency, variants)

    print("[obs-bench] hog-flood row: batch hog vs interactive tenant, "
          "/topz ranking + live-vs-offline bound_by", file=sys.stderr)
    hog_rc = _hog_flood_row(duration, concurrency, base_jpeg)

    print(f"[obs-bench] fleet row: 2 workers, sample={_FLEET_SAMPLE}, "
          f"fault every {_FAULT_EVERY}th request, admin scrape under load",
          file=sys.stderr)
    fleet_rc = _fleet_row(duration, concurrency, base_jpeg)
    return rc or cost_rc or hog_rc or fleet_rc


if __name__ == "__main__":
    sys.exit(main())
