#!/usr/bin/env python
"""Headline benchmark: /resize of a 1080p JPEG, end-to-end.

Measures the full request work — JPEG decode -> resize to 300x200 ->
JPEG encode — through (a) this framework's path (host codecs + micro-batched
jit-compiled TPU chain) and (b) the CPU baseline: OpenCV's native C++
decode/INTER_AREA-resize/encode loop, the same libjpeg-turbo-class stack
libvips uses (BASELINE.md: the reference's published numbers are 2015-era
and unusable; the baseline is re-measured on identical hardware).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Supplementary detail goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


from bench_util import make_1080p_jpeg as _make_1080p_jpeg  # noqa: E402


def _run_threaded(fn, n_threads: int, duration: float):
    """Run fn() in a loop across threads for `duration`s.

    Returns (ops/sec, latencies_ms list) — per-request latency is recorded so
    the bench reports p50/p99 alongside throughput (BASELINE.json's metric)."""
    stop = time.monotonic() + duration
    counts = [0] * n_threads
    lats: list = [[] for _ in range(n_threads)]

    def worker(i):
        while time.monotonic() < stop:
            t0 = time.monotonic()
            fn()
            lats[i].append((time.monotonic() - t0) * 1000.0)
            counts[i] += 1

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    all_lats = [x for sub in lats for x in sub]
    return sum(counts) / elapsed, all_lats


from bench_util import pctl as _pctl  # noqa: E402


def bench_ours(buf: bytes, n_threads: int, duration: float, reps: int = 1):
    from imaginary_tpu import codecs
    from imaginary_tpu.codecs import EncodeOptions
    from imaginary_tpu.engine import Executor, ExecutorConfig
    from imaginary_tpu.engine.timing import TIMES
    from imaginary_tpu.imgtype import ImageType
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.ops.plan import choose_decode_shrink, plan_operation

    # BENCH_HOST_SPILL=off forces device-primary serving (the VERDICT's
    # forced-device capture: every item must ride the chip, pricing the
    # link honestly instead of routing around it); on/auto as the CLI
    spill = {"auto": None, "on": True, "off": False}[
        os.environ.get("BENCH_HOST_SPILL", "auto")]
    executor = Executor(ExecutorConfig(max_form_ms=3.0, max_batch=16,
                                       host_spill=spill))
    opts = ImageOptions(width=300, height=200)

    def one():
        # same per-request work the service does: header probe -> provably
        # output-preserving shrink-on-load -> plan -> micro-batched device
        # chain -> encode
        meta = codecs.probe_fast(buf)
        shrink = choose_decode_shrink("resize", opts, meta.height, meta.width,
                                      meta.orientation, 3)
        d = codecs.decode(buf, shrink)
        plan = plan_operation("resize", opts, d.array.shape[0], d.array.shape[1],
                              d.orientation, d.array.shape[2])
        out = executor.process(d.array, plan)
        codecs.encode(out, EncodeOptions(type=ImageType.JPEG))

    # warmup: compile every batch size the power-of-two padding can produce,
    # so no XLA compile lands inside the timed window
    meta0 = codecs.probe_fast(buf)
    d0 = codecs.decode(buf, choose_decode_shrink("resize", opts, meta0.height,
                                                 meta0.width, meta0.orientation, 3))
    plan0 = plan_operation("resize", opts, d0.array.shape[0], d0.array.shape[1],
                           d0.orientation, d0.array.shape[2])
    for bs in (1, 2, 4, 8, 16):
        futs = [executor.submit(d0.array, plan0) for _ in range(bs)]
        for f in futs:
            f.result(timeout=300)
    print(f"[bench] warmup done, backend={codecs.backend_name()}", file=sys.stderr)
    from imaginary_tpu.engine.timing import maybe_start_profiler, stop_profiler

    profiling = maybe_start_profiler()  # IMAGINARY_TPU_PROFILE_DIR=<dir>
    # stats must cover ONLY the timed window (warmup items would inflate
    # the device-vs-spill split the JSON reports). Multiple windows guard
    # the headline number against one-off GC pauses / link hiccups on the
    # shared 1-CPU host (VERDICT r3 weak #7): the MEDIAN window is reported.
    from imaginary_tpu.engine.executor import ExecutorStats

    windows = []
    try:
        for _ in range(max(1, reps)):
            TIMES.reset()
            executor.stats = ExecutorStats()
            rate, lats = _run_threaded(one, n_threads, duration)
            windows.append((rate, lats, executor.stats.to_dict(), TIMES.snapshot()))
    finally:
        if profiling:
            stop_profiler()  # flush the trace even when the run errors
    executor.shutdown()
    windows.sort(key=lambda t: t[0])
    median = windows[len(windows) // 2]
    return median + ([round(w[0], 2) for w in windows],)


def bench_baseline(buf: bytes, n_threads: int, duration: float,
                   reps: int = 1) -> tuple:
    import cv2

    data = np.frombuffer(buf, np.uint8)

    def one():
        a = cv2.imdecode(data, cv2.IMREAD_COLOR)
        r = cv2.resize(a, (300, 200), interpolation=cv2.INTER_AREA)
        cv2.imencode(".jpg", r, [int(cv2.IMWRITE_JPEG_QUALITY), 80])

    one()
    rates = sorted(_run_threaded(one, n_threads, duration)[0]
                   for _ in range(max(1, reps)))
    return rates[len(rates) // 2], [round(r, 2) for r in rates]


def main():
    duration = float(os.environ.get("BENCH_DURATION", "10"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    cpus = os.cpu_count() or 1
    # closed-loop clients: enough in flight to fill micro-batches (the TPU
    # path's throughput comes from batch-amortizing the device link's fixed
    # readback cost; 4 clients can never form more than a batch of 4)
    n_threads = int(os.environ.get("BENCH_THREADS", str(max(32, 4 * cpus))))

    # build the native extension if missing/stale (gitignored artifact);
    # falls back to the resample-only module on codec-header-less hosts
    from bench_util import ensure_native_built, select_platform

    ensure_native_built()
    backend = select_platform("bench")

    buf = _make_1080p_jpeg()
    print(f"[bench] 1080p jpeg = {len(buf)} bytes, threads={n_threads}, "
          f"duration={duration}s x {reps} windows (median), cpus={cpus}",
          file=sys.stderr)

    ours, lats, exec_stats, stages, our_reps = bench_ours(
        buf, n_threads, duration, reps)

    print(f"[bench] imaginary-tpu: {ours:.2f} req/s (windows: {our_reps}) on "
          f"backend={backend} | p50={_pctl(lats, 0.50)}ms "
          f"p95={_pctl(lats, 0.95)}ms p99={_pctl(lats, 0.99)}ms",
          file=sys.stderr)
    print(f"[bench] executor: {exec_stats}", file=sys.stderr)
    print(f"[bench] device-path items={exec_stats['items']} "
          f"spilled-to-host={exec_stats['spilled']}", file=sys.stderr)
    for name, s in stages.items():
        # host_spill's p99/p50 ratio is the spill path's TAIL HEALTH: a
        # ratio in the hundreds means placement is convoying items onto a
        # saturated host pool (the r5 signature: p50 1.16 ms, p99 344.85 ms)
        tail = (f" p99/p50={s['p99_ms'] / max(s['p50_ms'], 1e-3):.1f}x"
                if name == "host_spill" else "")
        print(f"[bench]   stage {name:<12} n={s['count']:<6} "
              f"mean={s['mean_ms']:.2f}ms p50={s['p50_ms']:.2f}ms "
              f"p99={s['p99_ms']:.2f}ms{tail}", file=sys.stderr)

    base, base_reps = bench_baseline(buf, n_threads, duration, reps)
    print(f"[bench] cpu baseline (cv2): {base:.2f} req/s "
          f"(windows: {base_reps})", file=sys.stderr)

    print(json.dumps({
        "metric": "resize_1080p_jpeg_e2e_throughput",
        "value": round(ours, 2),
        "unit": "req/sec",
        "vs_baseline": round(ours / base, 3) if base > 0 else 0.0,
        "backend": backend,
        "device_items": exec_stats["items"],
        "spilled_items": exec_stats["spilled"],
        "p50_ms": _pctl(lats, 0.50),
        "p99_ms": _pctl(lats, 0.99),
        "windows": {"ours": our_reps, "baseline": base_reps},
    }))


if __name__ == "__main__":
    main()
