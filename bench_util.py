"""Shared helpers for the benchmark harnesses (bench.py, bench_latency.py).

One definition of the synthetic 1080p workload image and of percentile math,
so throughput and latency benches measure the same thing.
"""

from __future__ import annotations

import numpy as np


def make_1080p_jpeg(quality: int = 88) -> bytes:
    """Deterministic 1920x1080 JPEG with gradient structure + blocky detail
    (compresses like a photo, not like noise)."""
    import cv2

    rng = np.random.default_rng(7)
    yy, xx = np.mgrid[0:1080, 0:1920]
    img = np.stack(
        [
            (xx * 255 / 1919).astype(np.uint8),
            (yy * 255 / 1079).astype(np.uint8),
            ((xx + yy) % 256).astype(np.uint8),
        ],
        axis=-1,
    )
    for _ in range(12):
        x0, y0 = int(rng.integers(0, 1800)), int(rng.integers(0, 1000))
        img[y0 : y0 + 80, x0 : x0 + 120] = rng.integers(0, 256, 3)
    ok, out = cv2.imencode(".jpg", img, [int(cv2.IMWRITE_JPEG_QUALITY), quality])
    assert ok
    return out.tobytes()


def pctl(lats, q: float) -> float:
    """Nearest-rank percentile of a latency list, rounded to 0.01 ms."""
    if not lats:
        return 0.0
    s = sorted(lats)
    return round(s[min(len(s) - 1, int(q * (len(s) - 1)))], 2)


def select_platform(tag: str = "bench") -> str:
    """The bench's JAX backend, initialized in this process. BENCH_PLATFORM
    pins one (BENCH_PLATFORM=cpu is the only way to run on the CPU);
    unpinned, JAX's default backend must be an accelerator or the bench
    exits nonzero — a CPU number is never reported as a chip one."""
    import os
    import sys

    import jax

    platform = os.environ.get("BENCH_PLATFORM", "")
    if platform:
        jax.config.update("jax_platforms", platform)
    devs = jax.devices()
    if not platform and devs[0].platform == "cpu":
        sys.exit(f"[{tag}] no accelerator: JAX's backend is the CPU; set "
                 "BENCH_PLATFORM=cpu for an explicit CPU run")
    print(f"[{tag}] backend {devs[0].platform} ({devs[0].device_kind}), "
          f"{len(devs)} device(s)", file=sys.stderr)
    return devs[0].platform


def run_workers(call, duration: float, n_threads: int):
    """Closed-loop thread harness shared by the bench scripts: run
    call(worker_index, iteration) for `duration` seconds across
    `n_threads`, returning (ops_per_sec, flat_latency_ms_list)."""
    import threading
    import time

    stop = time.monotonic() + duration
    lats: list = [[] for _ in range(n_threads)]
    counts = [0] * n_threads

    def worker(k):
        i = k
        while time.monotonic() < stop:
            t0 = time.monotonic()
            call(k, i)
            lats[k].append((time.monotonic() - t0) * 1000.0)
            counts[k] += 1
            i += n_threads

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(n_threads)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    return sum(counts) / elapsed, [x for sub in lats for x in sub]


def ensure_native_built(timeout: float = 180.0) -> None:
    """Build the best available native module (full codecs, else the
    dependency-free resample-only build) when missing or stale, so a bench
    run measures the native spill-path resize rather than the numpy
    fallback. Failures are non-fatal: the python paths serve, just slower,
    and the run's own stderr makes the difference visible."""
    import os
    import subprocess
    import sys
    import sysconfig

    root = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(root, "imaginary_tpu", "native", "codecs.cpp")
    if not os.path.exists(src):  # deployed artifact: keep whatever .so exists
        return
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    native_dir = os.path.join(root, "imaginary_tpu", "native")
    sos = [os.path.join(native_dir, name + suffix)
           for name in ("_imaginary_codecs", "_imaginary_resample")]
    src_mtime = os.path.getmtime(src)
    fresh = [so for so in sos
             if os.path.exists(so) and os.path.getmtime(so) >= src_mtime]
    if fresh:
        return
    try:
        r = subprocess.run([sys.executable, "-m", "imaginary_tpu.native.build"],
                           timeout=timeout, capture_output=True, cwd=root)
        if r.returncode != 0:
            print(f"[bench] native build failed ({r.returncode}); "
                  "python fallbacks serve", file=sys.stderr)
    except Exception as e:
        print(f"[bench] native build error: {e}; python fallbacks serve",
              file=sys.stderr)


def free_port() -> int:
    """Ephemeral TCP port (shared by bench harnesses and tests)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
