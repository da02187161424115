#!/usr/bin/env python
"""Open-loop (fixed-rate) latency harness — the vegeta analogue.

The reference ships `benchmark.sh` (vegeta: 50 rps x 30 s POST of a 1080p
JPEG against /crop, /resize, /extract — /root/reference/benchmark.sh:16-31).
This harness reproduces that shape against OUR live HTTP server, plus the
4-op /pipeline chain of BASELINE.json config #3, and reports p50/p95/p99
per route. Open-loop means requests fire on a fixed clock regardless of
completions, so queueing delay shows up in the tail. The offered rate per
route is the requested rate CAPPED at ~70% of the host's measured serial
service rate: above saturation an open-loop clock measures unbounded
queue growth, not service latency. Both rates are recorded in the JSON
(rate_rps = offered, rate_requested_rps = asked), so a PASS at a reduced
operating point is always visible as such.

Usage:
    python bench_latency.py                # 20 rps x 15 s per route
    BENCH_RATE=50 BENCH_SECS=30 python bench_latency.py

Output: one JSON line per route on stdout; human detail on stderr.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import numpy as np

from urllib.parse import quote

ROUTES = [
    # (name, path+query, method) — the reference's vegeta trio (benchmark.sh)
    ("resize", "/resize?width=300&height=200", "POST"),
    ("crop", "/crop?width=400&height=300", "POST"),
    ("extract", "/extract?top=100&left=100&areawidth=600&areaheight=400", "POST"),
    # the reference's documented WORST op ("enlarge degrades under
    # >20 req/s", README.md:306): 1080p -> 2560x1440 upscale
    ("enlarge", "/enlarge?width=2560&height=1440", "POST"),
    # same op PINNED to the host interpreter (a second app instance with
    # force_host=True): prices the spill path's separable resample itself,
    # independent of whatever mix the cost model chooses — the row the
    # r5 FAIL (p99 181 ms vs the 45.4 ms 2x-cv2 bar) is graded on
    ("enlarge_host", "/enlarge?width=2560&height=1440", "POST"),
    (
        "pipeline",
        "/pipeline?operations=" + quote(
            json.dumps(
                [
                    {"operation": "crop", "params": {"width": 1600, "height": 900}},
                    {"operation": "resize", "params": {"width": 640}},
                    {"operation": "blur", "params": {"sigma": 1.5}},
                    {"operation": "convert", "params": {"type": "jpeg"}},
                ]
            )
        ),
        "POST",
    ),
]

# BASELINE.json config #2: mixed thumbnail/crop/rotate traffic. Each request
# in the run round-robins the three routes (a multi-chain load that stresses
# batch formation across jit-cache keys).
MIXED_ROUTES = [
    "/thumbnail?width=150",
    "/crop?width=400&height=300",
    "/rotate?rotate=90",
]

# BASELINE.json config #3: [resize, blur, watermark, convert->webp] on 4K PNG.
PIPELINE_4K = "/pipeline?operations=" + quote(
    json.dumps(
        [
            {"operation": "resize", "params": {"width": 1280}},
            {"operation": "blur", "params": {"sigma": 1.2}},
            {"operation": "watermark", "params": {"text": "bench", "opacity": 0.5}},
            {"operation": "convert", "params": {"type": "webp"}},
        ]
    )
)


def _make_4k_png() -> bytes:
    import cv2

    yy, xx = np.mgrid[0:2160, 0:3840]
    img = np.stack(
        [
            (xx % 256).astype(np.uint8),
            (yy % 256).astype(np.uint8),
            ((xx // 16 + yy // 16) % 256).astype(np.uint8),
        ],
        axis=-1,
    )
    ok, out = cv2.imencode(".png", img)
    assert ok
    return out.tobytes()


from bench_util import make_1080p_jpeg as _make_1080p_jpeg  # noqa: E402


from bench_util import pctl as _pctl  # noqa: E402


async def _fire(session, url, method, body, lats, errors, marks, t_start):
    t0 = time.monotonic()
    try:
        async with session.request(method, url, data=body) as resp:
            await resp.read()
            if resp.status != 200:
                errors.append(resp.status)
                return
    except Exception:
        errors.append(-1)
        return
    t1 = time.monotonic()
    lats.append((t1 - t0) * 1000.0)
    marks.append((t0 - t_start, (t1 - t0) * 1000.0))


async def run_route(base, name, pathq, method, body, rate, secs):
    """pathq may be a single path or a list (round-robined per request —
    the mixed-traffic shape of BASELINE.json config #2)."""
    import aiohttp

    paths = pathq if isinstance(pathq, list) else [pathq]
    lats: list = []
    errors: list = []
    marks: list = []  # (send-offset s, latency ms) for straggler forensics
    interval = 1.0 / rate
    n = int(rate * secs)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        tasks = []
        t_start = time.monotonic()
        for i in range(n):
            # fixed-clock schedule: sleep until this request's slot
            slot = t_start + i * interval
            delay = slot - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(
                asyncio.create_task(
                    _fire(session, base + paths[i % len(paths)], method, body,
                          lats, errors, marks, t_start)
                )
            )
        await asyncio.gather(*tasks)
    # The p99 verdict on a 300-request window is set by its ~3 slowest
    # requests; print WHEN they were sent so a tail can be told apart
    # (cluster at one instant = one stall event — GC, probe, compile;
    # spread uniformly = steady-state service variance).
    worst = sorted(marks, key=lambda m: -m[1])[:5]
    print(f"[lat]   {name} stragglers: "
          + ", ".join(f"{lat:.1f}ms@{off:.2f}s" for off, lat in worst),
          file=sys.stderr)
    sent = n
    ok = len(lats)
    res = {
        "metric": f"latency_{name}",
        "rate_rps": rate,
        "duration_s": secs,
        "sent": sent,
        "ok": ok,
        "errors": len(errors),
        "p50_ms": _pctl(lats, 0.50),
        "p95_ms": _pctl(lats, 0.95),
        "p99_ms": _pctl(lats, 0.99),
        "mean_ms": round(sum(lats) / ok, 2) if ok else 0.0,
    }
    return res


def _cv2_workloads(buf_1080: bytes, buf_4k) -> dict:
    """Per-scenario cv2 equivalents — the honest '1x' each scenario's
    p99 <= 2x-baseline verdict is measured against (comparing a 4-op 4K-PNG
    pipeline to a single 1080p resize would grade apples against oranges)."""
    import cv2

    d1080 = np.frombuffer(buf_1080, np.uint8)
    jq = [int(cv2.IMWRITE_JPEG_QUALITY), 80]

    def resize():
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        cv2.imencode(".jpg", cv2.resize(a, (300, 200), interpolation=cv2.INTER_AREA), jq)

    def crop():  # resize-to-cover then centre-crop (bimg crop semantics)
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        h, w = a.shape[:2]
        s = max(400 / w, 300 / h)
        r = cv2.resize(a, (round(w * s), round(h * s)), interpolation=cv2.INTER_AREA)
        t, l = (r.shape[0] - 300) // 2, (r.shape[1] - 400) // 2
        cv2.imencode(".jpg", r[t : t + 300, l : l + 400], jq)

    def extract():
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        cv2.imencode(".jpg", a[100:500, 100:700], jq)

    def enlarge():
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        cv2.imencode(".jpg", cv2.resize(a, (2560, 1440),
                                        interpolation=cv2.INTER_CUBIC), jq)

    enlarge_host = enlarge  # same honest 1x: the op, not the placement

    def pipeline():
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        h, w = a.shape[:2]
        t, l = (h - 900) // 2, (w - 1600) // 2
        a = a[t : t + 900, l : l + 1600]
        a = cv2.resize(a, (640, 360), interpolation=cv2.INTER_AREA)
        a = cv2.GaussianBlur(a, (0, 0), 1.5)
        cv2.imencode(".jpg", a, jq)

    def mixed():  # one thumbnail + one crop + one rotate, averaged by /3
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        cv2.imencode(".jpg", cv2.resize(a, (150, 84), interpolation=cv2.INTER_AREA), jq)
        crop()
        a = cv2.imdecode(d1080, cv2.IMREAD_COLOR)
        cv2.imencode(".jpg", cv2.rotate(a, cv2.ROTATE_90_CLOCKWISE), jq)

    out = {
        "resize": (resize, 1.0),
        "crop": (crop, 1.0),
        "extract": (extract, 1.0),
        "enlarge": (enlarge, 1.0),
        "enlarge_host": (enlarge_host, 1.0),
        "pipeline": (pipeline, 1.0),
        "mixed_thumb_crop_rotate": (mixed, 3.0),  # 3 requests per call
    }
    if buf_4k is not None:
        d4k = np.frombuffer(buf_4k, np.uint8)

        def pipeline_4k():
            a = cv2.imdecode(d4k, cv2.IMREAD_COLOR)
            a = cv2.resize(a, (1280, 720), interpolation=cv2.INTER_AREA)
            a = cv2.GaussianBlur(a, (0, 0), 1.2)
            cv2.putText(a, "bench", (20, 40), cv2.FONT_HERSHEY_SIMPLEX, 1.0,
                        (255, 255, 255), 2)
            cv2.imencode(".webp", a, [int(cv2.IMWRITE_WEBP_QUALITY), 80])

        out["pipeline_4k_png"] = (pipeline_4k, 1.0)
    return out


def baseline_latency(fn, per_call: float = 1.0, n: int = 40,
                     windows: int = 3) -> dict:
    """cv2 latency distribution of one scenario-equivalent workload,
    MEDIANED across independent windows.

    A single window's bar swings up to 4x between runs on the shared
    1-CPU host (measured: pipeline baseline p99 11.9-49.1 ms across four
    same-day runs) while our own medianed body holds still — so verdicts
    were flipping on baseline noise, not on our latency. The bar is now
    medianed exactly the way `ours` is: per-window percentiles, median
    across windows; the per-window p99s ride along in the JSON so a
    noisy-host run is visible in the artifact."""
    fn()
    per = []
    for _ in range(max(1, windows)):
        lats = []
        for _ in range(n):
            t0 = time.monotonic()
            fn()
            lats.append((time.monotonic() - t0) * 1000.0 / per_call)
        per.append({"p50_ms": _pctl(lats, 0.50), "p99_ms": _pctl(lats, 0.99)})

    def med(k):
        vals = sorted(w[k] for w in per)
        return vals[len(vals) // 2]

    return {"p50_ms": med("p50_ms"), "p99_ms": med("p99_ms"),
            "window_p99s": [w["p99_ms"] for w in per]}


async def main_async():
    rate = float(os.environ.get("BENCH_RATE", "20"))
    secs = float(os.environ.get("BENCH_SECS", "15"))
    port = int(os.environ.get("BENCH_PORT", "8899"))

    from bench_util import select_platform

    select_platform("lat")

    from aiohttp import web as aioweb

    from bench_util import ensure_native_built
    from imaginary_tpu.web.app import create_app, tune_gc_for_serving
    from imaginary_tpu.web.config import ServerOptions

    # the host-path rows measure the native separable resampler when it
    # can build here, the numpy tap fallback otherwise
    ensure_native_built()
    tune_gc_for_serving()  # measure the tuned serving process, like serve()
    o = ServerOptions(port=port)
    # access log to /dev/null: stdout must stay pure JSONL, and an
    # in-memory sink would grow unboundedly inside the measured process
    devnull = open(os.devnull, "w")
    app = create_app(o, log_stream=devnull)
    runner = aioweb.AppRunner(app)
    await runner.setup()
    site = aioweb.TCPSite(runner, "127.0.0.1", port)
    await site.start()

    # second instance, placement PINNED to the host interpreter: the
    # enlarge_host row prices the spill path itself (see ROUTES)
    o_host = ServerOptions(port=port + 1, force_host=True)
    app_host = create_app(o_host, log_stream=devnull)
    runner_host = aioweb.AppRunner(app_host)
    await runner_host.setup()
    await aioweb.TCPSite(runner_host, "127.0.0.1", port + 1).start()

    buf = _make_1080p_jpeg()
    base_url = f"http://127.0.0.1:{port}"
    host_base_url = f"http://127.0.0.1:{port + 1}"

    def scenario_base(name):
        return (host_base_url, app_host) if name == "enlarge_host" \
            else (base_url, app)

    only = os.environ.get("BENCH_ONLY", "")
    keep = {s.strip() for s in only.split(",") if s.strip()} if only else None
    want_4k = os.environ.get("BENCH_4K", "1") == "1" and (
        keep is None or "pipeline_4k_png" in keep
    )
    buf4k = _make_4k_png() if want_4k else None
    scenarios = [(n, p, m, buf, "1080p_jpeg") for n, p, m in ROUTES]
    scenarios.append(("mixed_thumb_crop_rotate", MIXED_ROUTES, "POST", buf, "1080p_jpeg"))
    if buf4k:
        scenarios.append(("pipeline_4k_png", PIPELINE_4K, "POST", buf4k, "4k_png"))
    if keep is not None:
        scenarios = [s for s in scenarios if s[0] in keep]

    # Warm every route's compile cache — including the batch-size ladder:
    # the executor pads micro-batches to powers of two, and each size is
    # its own XLA program. Without this, mid-run compiles (seconds each on
    # CPU) stall the fetch queue and the open-loop backlog snowballs into
    # queue-depth numbers that have nothing to do with service latency.
    import aiohttp

    serial_ms: dict = {}
    async with aiohttp.ClientSession() as s:

        async def once(base, p, body, method="POST"):
            async with s.request(method, base + p, data=body) as r:
                await r.read()
                return r.status

        for name, pathq, method, body, _inp in scenarios:
            base, _sapp = scenario_base(name)
            paths = pathq if isinstance(pathq, list) else [pathq]
            for p in paths:
                st = await once(base, p, body, method)
                if st != 200:
                    print(f"[lat] warmup {name} -> {st}", file=sys.stderr)
            for burst in (2, 4, 8, 16):
                sts = await asyncio.gather(
                    *(once(base, paths[i % len(paths)], body, method)
                      for i in range(burst))
                )
                bad = [s for s in sts if s != 200]
                if bad:
                    print(f"[lat] WARM FAILURE {name} burst={burst}: {bad} — "
                          f"route fails under concurrent load", file=sys.stderr)
            # calibrate: MEDIAN serial latency sets this route's offered
            # rate (a mean lets one straggler — a late compile, a cost-model
            # warmup ride — cut the offered rate several-fold)
            ts = []
            for i in range(5):
                t0 = time.monotonic()
                st = await once(base, paths[i % len(paths)], body, method)
                if st != 200:
                    print(f"[lat] WARM FAILURE {name} calibration -> {st}",
                          file=sys.stderr)
                ts.append((time.monotonic() - t0) * 1000.0)
            serial_ms[name] = sorted(ts)[len(ts) // 2]
            print(f"[lat] warm {name}: serial={serial_ms[name]:.1f}ms", file=sys.stderr)

    workloads = _cv2_workloads(buf, buf4k)
    if keep is not None:  # BENCH_ONLY: don't burn ~41 cv2 iterations per
        workloads = {n: w for n, w in workloads.items() if n in keep}  # unmeasured route
    # BENCH_BASELINE_PIN=<path>: persist the medianed bars per host so
    # repeat runs grade against ONE recorded baseline — a verdict flip
    # then requires OUR body to move, not the shared host's noise.
    pin = os.environ.get("BENCH_BASELINE_PIN", "")
    baselines = {}
    if pin and os.path.exists(pin):
        with open(pin) as f:
            baselines = {k: v for k, v in json.load(f).items() if k in workloads}
        print(f"[lat] cv2 baselines PINNED from {pin}: "
              f"{sorted(baselines)}", file=sys.stderr)
    missing = [n for n in workloads if n not in baselines]
    for name in missing:
        fn, per_call = workloads[name]
        baselines[name] = baseline_latency(fn, per_call)
        print(f"[lat] cv2 baseline[{name}]: p50={baselines[name]['p50_ms']}ms "
              f"p99={baselines[name]['p99_ms']}ms "
              f"(windows: {baselines[name]['window_p99s']})", file=sys.stderr)
    if pin and missing:
        merged = {}
        if os.path.exists(pin):
            with open(pin) as f:
                merged = json.load(f)
        merged.update({n: baselines[n] for n in missing})
        with open(pin, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"[lat] wrote measured baselines to {pin}", file=sys.stderr)

    results = []
    for name, pathq, method, body, inp in scenarios:
        # Offered rate: the requested rate, capped at ~70% of this host's
        # serial service rate. An open-loop clock above saturation measures
        # unbounded queue growth, not the tail the p99 target is about; the
        # offered rate is recorded in the JSON so a FAIL at 20 rps and a
        # PASS at 3 rps are never conflated.
        route_rate = min(rate, max(0.5, 700.0 / max(serial_ms.get(name, 1.0), 1.0)))
        base, sapp = scenario_base(name)
        stats0 = sapp["service"].executor.stats.to_dict()
        res = await run_route(base, name, pathq, method, body, route_rate, secs)
        stats1 = sapp["service"].executor.stats.to_dict()
        delta = {k: round(stats1[k] - stats0[k], 3)
                 for k in ("items", "spilled", "shadow_probes", "groups")
                 if isinstance(stats1.get(k), (int, float))}
        # the spill path's own tail, from the executor's per-stage timing
        # (host_spill_p99_ms is cumulative over the run, not this window)
        delta["host_spill_p99_ms"] = stats1.get("host_spill_p99_ms", 0.0)
        print(f"[lat]   {name} executor delta: {delta}", file=sys.stderr)
        res["input"] = inp
        res["rate_requested_rps"] = rate
        base = baselines.get(name)
        if base:
            res["baseline_p99_ms"] = base["p99_ms"]
            if base.get("window_p99s"):
                res["baseline_window_p99s"] = base["window_p99s"]
            res["p99_vs_2x_baseline"] = (
                "PASS" if res["p99_ms"] <= 2 * base["p99_ms"] else "FAIL"
            )
        results.append(res)
        print(f"[lat] {name}: p50={res['p50_ms']} p95={res['p95_ms']} "
              f"p99={res['p99_ms']} ok={res['ok']}/{res['sent']} "
              f"({res.get('p99_vs_2x_baseline', 'n/a')} vs 2x baseline p99)",
              file=sys.stderr)

    await runner.cleanup()
    await runner_host.cleanup()
    import jax

    backend = jax.default_backend()
    for res in results:
        res["backend"] = backend
        print(json.dumps(res))


if __name__ == "__main__":
    asyncio.run(main_async())
