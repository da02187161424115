#!/usr/bin/env python
"""BASELINE.json configs #4 and #5 — the last two benchmark configs.

#4  smartcrop saliency stream: varied photo-like images through the
    /smartcrop path (saliency conv + integral-image argmax on device;
    smartcrop NEVER spills to host — the window choice must not depend
    on link load). Reports imgs/sec and p50/p99.

#5  mesh firehose: mixed JPEG/PNG/WEBP at jittered sizes through the
    micro-batching executor with use_mesh over the device mesh —
    dynamic-shape bucketing + batch-axis sharding under concurrent load.
    On hosts without a real multi-chip mesh this runs on the virtual
    8-device CPU mesh (the same topology the driver dryrun validates)
    and is labeled so; the measured mechanics (bucketing, jit-cache
    bound, batch formation) are identical either way.

PLUS the 5-format codec firehose (VERDICT r4 next #8): full
decode->transform->encode round trips across JPEG/PNG/WEBP/GIF/TIFF
under thread concurrency, with a per-format latency split. The r4 risk
this measures was PIL-backed GIF/TIFF holding the GIL mid-decode and
degrading JPEG throughput on the shared pool; r5 moved every format
into the GIL-released C extension, and the split is the evidence.

One JSON line per config on stdout; detail on stderr.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _gen_stream(n: int, seed: int = 7):
    """Photo-like varied inputs: gradients + texture + a salient blob, at
    jittered dims (the dynamic-shape reality a CDN stream has)."""
    import cv2
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        h = int(rng.integers(420, 780))
        w = int(rng.integers(560, 1100))
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        base = np.stack([
            128 + 90 * np.sin(xx / (23 + (i % 7))),
            128 + 90 * np.cos(yy / (29 + (i % 5))),
            (xx + yy) % 255,
        ], axis=-1)
        # one high-contrast salient blob off-centre
        cy, cx = int(h * (0.3 + 0.4 * rng.random())), int(w * (0.3 + 0.4 * rng.random()))
        r = int(min(h, w) * 0.12)
        cv2.circle(base, (cx, cy), r, (255, 255, 255), -1)
        cv2.circle(base, (cx, cy), r // 2, (0, 0, 0), -1)
        noise = rng.normal(0, 6, (h, w, 3))
        img = np.clip(base + noise, 0, 255).astype(np.uint8)
        fmt = (".jpg", ".png", ".webp")[i % 3]
        ok, buf = cv2.imencode(fmt, img)
        assert ok
        out.append((buf.tobytes(), fmt))
    return out


def bench_smartcrop(duration: float, n_threads: int) -> dict:
    from bench_util import pctl
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.pipeline import process_operation

    stream = _gen_stream(24, seed=11)
    o = ImageOptions(width=300, height=300)
    # warm the FULL (chain, bucket) matrix this stream exercises — the
    # jittered dims land in many buckets and every bucket is its own XLA
    # program; measuring compiles would benchmark the compiler, and a
    # production server prewarms exactly this matrix at startup
    for buf, _ in stream:
        process_operation("smartcrop", buf, o)

    from bench_util import run_workers

    rate, flat = run_workers(
        lambda k, i: process_operation("smartcrop", stream[i % len(stream)][0], o),
        duration, n_threads,
    )
    return {
        "metric": "smartcrop_saliency_stream",
        "value": round(rate, 2),
        "unit": "imgs/sec",
        "p50_ms": pctl(flat, 0.5),
        "p99_ms": pctl(flat, 0.99),
        "images": len(flat),
    }


def bench_firehose(duration: float, n_threads: int) -> dict:
    from bench_util import pctl
    from imaginary_tpu import codecs
    from imaginary_tpu.engine.executor import Executor, ExecutorConfig
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.ops.plan import plan_operation

    import jax

    n_dev = len(jax.devices())
    ex = Executor(ExecutorConfig(use_mesh=n_dev > 1, host_spill=False,
                                 max_form_ms=2.0))
    stream = _gen_stream(32, seed=23)
    decoded = []
    for buf, _ in stream:
        d = codecs.decode(buf, 1)
        plan = plan_operation("resize", ImageOptions(width=300), d.array.shape[0],
                              d.array.shape[1], 0, 3)
        decoded.append((d.array, plan))
    # Warm pass: cycle the whole stream under the SAME concurrency as the
    # measured window, so every (bucket, padded-batch) program the window
    # can form is compiled before measurement (the ladder compiles by
    # formed batch size, which depends on concurrency, not item count).
    from bench_util import run_workers

    def one(k, i):
        arr, plan = decoded[i % len(decoded)]
        ex.process(arr, plan)

    run_workers(one, max(6.0, duration / 2), n_threads)
    from imaginary_tpu.engine.executor import ExecutorStats

    ex.stats = ExecutorStats()  # measure the warm window only
    rate, flat = run_workers(one, duration, n_threads)
    stats = ex.stats.to_dict()
    ex.shutdown()
    return {
        "metric": "mesh_firehose_mixed_formats",
        "value": round(rate, 2),
        "unit": "imgs/sec",
        "devices": n_dev,
        "mesh": n_dev > 1,
        "p50_ms": pctl(flat, 0.5),
        "p99_ms": pctl(flat, 0.99),
        "avg_batch": stats["avg_batch"],
        "compile_cache_size": stats["compile_cache_size"],
    }


def bench_format_firehose(duration: float, n_threads: int) -> dict:
    """Full e2e round trips (decode -> plan -> execute -> encode SAME
    format) over a 5-format mixed stream; per-format latency split."""
    import numpy as np

    from bench_util import pctl, run_workers
    from imaginary_tpu import codecs
    from imaginary_tpu.codecs import EncodeOptions
    from imaginary_tpu.engine.executor import Executor, ExecutorConfig
    from imaginary_tpu.imgtype import ImageType
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.ops.plan import plan_operation

    fmts = [ImageType.JPEG, ImageType.PNG, ImageType.WEBP,
            ImageType.GIF, ImageType.TIFF]
    raw = _gen_stream(20, seed=31)
    stream = []
    for i, (buf, _) in enumerate(raw):
        import cv2

        arr = cv2.imdecode(np.frombuffer(buf, np.uint8), cv2.IMREAD_COLOR)[..., ::-1]
        t = fmts[i % len(fmts)]
        stream.append((codecs.encode(np.ascontiguousarray(arr), EncodeOptions(type=t)), t))

    ex = Executor(ExecutorConfig(max_form_ms=2.0, host_spill=None))
    o = ImageOptions(width=300)
    lats_by_fmt: dict = {t.value: [] for t in fmts}
    lock = threading.Lock()

    def one_rt(buf, t):
        d = codecs.decode(buf, 1)
        plan = plan_operation("resize", o, d.array.shape[0], d.array.shape[1],
                              0, d.array.shape[2])
        out = ex.process(d.array, plan)
        codecs.encode(out, EncodeOptions(type=t))

    for buf, t in stream:  # warm every bucket/chain
        one_rt(buf, t)

    def one(k, i):
        buf, t = stream[i % len(stream)]
        t0 = time.monotonic()
        one_rt(buf, t)
        dt = (time.monotonic() - t0) * 1000.0
        with lock:
            lats_by_fmt[t.value].append(dt)

    rate, flat = run_workers(one, duration, n_threads)
    ex.shutdown()
    split = {
        f: {"n": len(ls), "p50_ms": pctl(ls, 0.5), "p99_ms": pctl(ls, 0.99)}
        for f, ls in lats_by_fmt.items() if ls
    }
    return {
        "metric": "codec_firehose_5_formats_e2e",
        "value": round(rate, 2),
        "unit": "imgs/sec",
        "p50_ms": pctl(flat, 0.5),
        "p99_ms": pctl(flat, 0.99),
        "per_format": split,
        "codec_backend": codecs.backend_name(),
    }


def main():
    duration = float(os.environ.get("BENCH_DURATION", "20"))
    n_threads = int(os.environ.get("BENCH_THREADS", "16"))

    from bench_util import select_platform

    if os.environ.get("BENCH_PLATFORM") == "cpu":
        # the explicit CPU run drives the mechanics on a virtual 8-device
        # mesh (the driver-dryrun topology); its backend reads "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    backend = select_platform("firehose")
    for fn in (bench_smartcrop, bench_firehose, bench_format_firehose):
        res = fn(duration, n_threads)
        res["backend"] = backend
        print(f"[firehose] {res['metric']}: {res['value']} {res['unit']} "
              f"p50={res['p50_ms']}ms p99={res['p99_ms']}ms", file=sys.stderr)
        print(json.dumps(res))


if __name__ == "__main__":
    main()
