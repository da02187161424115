#!/usr/bin/env python3
"""Chip smoke: the served HTTP path, end to end, on a TPU.

    python chip_smoke.py             one chip: the headline ops at 1080p and 4K
    python chip_smoke.py --chips 4   four chips: --mesh-policy off vs lanes
                                     (byte-identical outputs, every lane
                                     served), then auto's spatial 4K route

The parent process never imports JAX: the server child it starts through
the normal entry point (`python -m imaginary_tpu --require-device
--host-spill off`) is the only process that opens the chip. Inputs are
smooth seeded JPEGs made here; every response is checked for status,
dimensions (measured with PIL), `X-Imaginary-Backend: device` and PSNR
against an oracle computed with PIL/numpy from the same decoded input.
`/health` afterwards must show the TPU backend, every request on the
device and nothing served by the host. Any failed phase exits nonzero and
prints no result; the last stdout line is the result JSON.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import io
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
from PIL import Image

from bench_util import free_port

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 20261015
PSNR_FLOOR = 30.0  # tests/test_quality.py's resample, crop and blur floors
REPS = 3
BOOT_TIMEOUT_S = 420.0
REQUEST_TIMEOUT_S = 300.0


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# -- inputs and oracles -------------------------------------------------------

def make_jpeg(w: int, h: int, seed: int) -> bytes:
    """Smooth structured content: a coarse seeded grid upsampled bicubic
    over a diagonal gradient (resampling PSNR means nothing on noise)."""
    rng = np.random.default_rng(seed)
    grid = rng.integers(0, 256, (h // 48 + 2, w // 48 + 2, 3), dtype=np.uint8)
    smooth = np.asarray(Image.fromarray(grid).resize((w, h), Image.BICUBIC),
                        np.float32)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    ramp = (xx / w + yy / h)[..., None] * 64.0
    img = np.clip(smooth * 0.75 + ramp, 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "JPEG", quality=90)
    return buf.getvalue()


def decode(data: bytes) -> np.ndarray:
    return np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))


def psnr(a: np.ndarray, b: np.ndarray) -> float:
    mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
    return 99.0 if mse == 0 else float(10.0 * np.log10(255.0 ** 2 / mse))


def lanczos(src: np.ndarray, w: int, h: int) -> np.ndarray:
    return np.asarray(Image.fromarray(src).resize((w, h), Image.LANCZOS))


def oracle_resize(src, out):
    """bimg's /resize: one dim scales; both dims fit and embed (centred)."""
    h, w = out.shape[:2]
    sh, sw = src.shape[:2]
    scale = min(w / sw, h / sh)
    rw, rh = max(1, round(sw * scale)), max(1, round(sh * scale))
    top, left = (h - rh) // 2, (w - rw) // 2
    return psnr(out[top:top + rh, left:left + rw], lanczos(src, rw, rh))


def oracle_crop(src, out):
    """bimg's /crop: resize to cover, centre window."""
    h, w = out.shape[:2]
    sh, sw = src.shape[:2]
    scale = max(w / sw, h / sh)
    rw, rh = round(sw * scale), round(sh * scale)
    top, left = (rh - h) // 2, (rw - w) // 2
    return psnr(out, lanczos(src, rw, rh)[top:top + h, left:left + w])


def oracle_smartcrop(src, out):
    """Resize to cover; the window is the service's saliency choice, so
    grade the best window along the free axis."""
    h, w = out.shape[:2]
    sh, sw = src.shape[:2]
    scale = max(w / sw, h / sh)
    cover = lanczos(src, round(sw * scale), round(sh * scale))
    ch, cw = cover.shape[:2]
    return max(psnr(out, cover[t:t + h, l:l + w])
               for t in range(ch - h + 1) for l in range(cw - w + 1))


def oracle_blur(src, out, sigma=2.0):
    """Dense float64 separable gaussian, edge clamp, on the service's
    radius (plan._blur_radius: the libvips gaussmat width at min_ampl 0.2)."""
    radius = max(1, int(np.ceil(sigma * np.sqrt(-2.0 * np.log(0.2)))))
    radius = next(r for r in (2, 4, 8, 16, 32, 64) if radius <= r)
    xs = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (xs / sigma) ** 2)
    k /= k.sum()
    x = src.astype(np.float64)
    n = 2 * radius + 1
    pad = np.pad(x, ((radius, radius), (0, 0), (0, 0)), mode="edge")
    x = sum(k[i] * pad[i:i + src.shape[0]] for i in range(n))
    pad = np.pad(x, ((0, 0), (radius, radius), (0, 0)), mode="edge")
    x = sum(k[i] * pad[:, i:i + src.shape[1]] for i in range(n))
    return psnr(out, np.clip(np.round(x), 0, 255).astype(np.uint8))


def _dims_resize(width=0, height=0):
    def dims(sw, sh):
        if width and height:
            return width, height
        scale = width / sw if width else height / sh
        return round(sw * scale), round(sh * scale)
    return dims


# (name, input key, path, expected (w, h) from source (w, h), oracle, mime)
ONE_CHIP_CASES = [
    ("resize_300x200", "1080p", "/resize?width=300&height=200",
     _dims_resize(300, 200), oracle_resize, "image/jpeg"),
    ("blur_sigma2", "1080p", "/blur?sigma=2",
     lambda sw, sh: (sw, sh), oracle_blur, "image/jpeg"),
    ("crop_400x400", "1080p", "/crop?width=400&height=400",
     lambda sw, sh: (400, 400), oracle_crop, "image/jpeg"),
    ("smartcrop_300x300", "1080p", "/smartcrop?width=300&height=300",
     lambda sw, sh: (300, 300), oracle_smartcrop, "image/jpeg"),
    ("resize_640_png", "1080p", "/resize?width=640&type=png",
     _dims_resize(640), oracle_resize, "image/png"),
    ("resize_4k_1280", "4k", "/resize?width=1280",
     _dims_resize(1280), oracle_resize, "image/jpeg"),
]


def make_inputs(sizes: dict, seed: int = SEED) -> dict:
    """{key: (jpeg bytes, decoded RGB)} for {key: (w, h)}."""
    out = {}
    for i, (key, (w, h)) in enumerate(sorted(sizes.items())):
        data = make_jpeg(w, h, seed + i)
        out[key] = (data, decode(data))
    return out


# -- the server ---------------------------------------------------------------

# what every smoke server runs with: no CPU backend, no host-served items
SERVER_ARGS = ["--require-device", "--host-spill", "off"]


class Server:
    """`python -m imaginary_tpu` as a child; its output goes to a log file."""

    def __init__(self, name: str, args: list, env: dict | None = None):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(OUT_DIR, f"server_{name}.log")
        self._log = open(self.log_path, "wb")
        cmd = [sys.executable, "-m", "imaginary_tpu", "--port", str(self.port),
               *args]
        self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env or dict(os.environ),
                                     stdout=self._log, stderr=subprocess.STDOUT,
                                     start_new_session=True)

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")[-n:]

    def wait_healthy(self, timeout_s: float = BOOT_TIMEOUT_S) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise SmokeFailure(f"server exited {self.proc.returncode} before "
                                   f"serving:\n{self.log_tail()}")
            try:
                health(self.base, timeout=2.0)
                return time.monotonic() - t0
            except (OSError, ValueError):
                time.sleep(0.5)
        raise SmokeFailure(f"server not healthy after {timeout_s:.0f}s:\n"
                           f"{self.log_tail()}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
        self._log.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


def health(base: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(f"{base}/health", timeout=timeout) as r:
        return json.loads(r.read())


def post(base: str, path: str, data: bytes) -> tuple:
    """(status, headers, body, seconds) of one multipart-free POST."""
    req = urllib.request.Request(f"{base}{path}", data=data, method="POST",
                                 headers={"Content-Type": "image/jpeg"})
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
            return r.status, dict(r.headers), r.read(), time.monotonic() - t0
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read(), time.monotonic() - t0


# -- phases -------------------------------------------------------------------

def check_response(case, inputs, status, headers, body) -> float:
    """Status, backend header, MIME, dims and PSNR of one response."""
    name, key, _path, dims_fn, oracle, mime = case
    src = inputs[key][1]
    if status != 200:
        raise SmokeFailure(f"{name}: HTTP {status}: {body[:300]!r}")
    backend = headers.get("X-Imaginary-Backend")
    if backend != "device":
        raise SmokeFailure(f"{name}: X-Imaginary-Backend {backend!r}, not device")
    if headers.get("Content-Type") != mime:
        raise SmokeFailure(f"{name}: Content-Type {headers.get('Content-Type')!r}")
    out = decode(body)
    want = dims_fn(src.shape[1], src.shape[0])
    got = (out.shape[1], out.shape[0])
    if got != tuple(want):
        raise SmokeFailure(f"{name}: output {got}, expected {tuple(want)}")
    p = oracle(src, out)
    if p < PSNR_FLOOR:
        raise SmokeFailure(f"{name}: PSNR {p:.2f} dB < {PSNR_FLOOR}")
    return p


def request_phase(base: str, inputs: dict, cases=ONE_CHIP_CASES,
                  reps: int = REPS) -> dict:
    """Every case `reps` times, in order; returns per-case results. The
    first request of a case pays its compile: it is reported apart."""
    results = {}
    for case in cases:
        name, key, path = case[0], case[1], case[2]
        lat, p = [], None
        for _ in range(reps):
            status, headers, body, secs = post(base, path, inputs[key][0])
            p = check_response(case, inputs, status, headers, body)
            lat.append(secs)
        results[name] = {"path": path, "input": key, "first_s": lat[0],
                         "warm_s": lat[1:], "psnr_db": p, "bytes": len(body)}
        log(f"[smoke] {name:<18} {key:>5} first {lat[0]:.3f}s warm "
            f"{' '.join(f'{x:.3f}' for x in lat[1:])}s PSNR {p:.2f} dB")
    return results


def health_phase(base: str, n_sent: int, platform: str = "tpu") -> dict:
    """/health after the requests: the platform, every item on the device,
    nothing served by the host."""
    h = health(base)
    ex = h.get("executor", {})
    bad = []
    if h.get("backend") != platform:
        bad.append(f"backend {h.get('backend')!r} != {platform!r}")
    if ex.get("items", 0) < n_sent:
        bad.append(f"executor.items {ex.get('items')} < {n_sent} requests sent")
    for k in ("spilled", "breaker_host_served", "oom_host_routed"):
        if ex.get(k) != 0:
            bad.append(f"{k} = {ex.get(k)}")
    if bad:
        raise SmokeFailure("health: " + "; ".join(bad))
    log(f"[smoke] health: backend {h['backend']} kind {h.get('device_kind')} "
        f"devices {h.get('devices')} items {ex.get('items')} "
        f"compile_misses {ex.get('compile_misses')} "
        f"compile_cache_size {ex.get('compile_cache_size')} "
        f"peak_bytes_in_use {h.get('peak_bytes_in_use', 'not reported')}")
    return h


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")


def build_native() -> float:
    """Build the native codec extension from imaginary_tpu/native/*.cpp,
    whatever .so files are on disk."""
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "-m", "imaginary_tpu.native.build"],
                       cwd=ROOT, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise SmokeFailure(f"native build failed ({r.returncode}): "
                           f"{(r.stderr or r.stdout)[-2000:]}")
    return time.monotonic() - t0


def one_chip(inputs: dict) -> dict:
    with Server("one_chip", SERVER_ARGS) as srv:
        boot = srv.wait_healthy()
        log(f"[smoke] server up in {boot:.1f}s (set-up: jax import, backend "
            f"init, app); compile cache {cache_dir()}")
        results = request_phase(srv.base, inputs)
        h = health_phase(srv.base, REPS * len(ONE_CHIP_CASES))
    return {"boot_s": boot, "cases": results, "health": h}


# -- four chips ---------------------------------------------------------------

LANE_CASE = ONE_CHIP_CASES[0]  # the headline thumbnail
# the oversize single: a full-frame op, so the device input stays 4K (a
# thumbnail shrinks on load and never crosses the spatial bar)
AUTO_CASE = ("blur_4k_sigma2", "4k", "/blur?sigma=2",
             lambda sw, sh: (sw, sh), oracle_blur, "image/jpeg")
LANE_SOURCES = 8               # distinct images: no frame-cache affinity
LANE_ROUNDS = 4


def burst(base: str, inputs: dict, keys: list, path: str) -> dict:
    """Every key LANE_ROUNDS times, 32 in flight at once: {(key, round): body}."""
    jobs = [(k, r) for r in range(LANE_ROUNDS) for k in keys]
    out = {}
    with concurrent.futures.ThreadPoolExecutor(32) as pool:
        futs = {pool.submit(post, base, path, inputs[k][0]): (k, r)
                for k, r in jobs}
        for f in concurrent.futures.as_completed(futs):
            k, r = futs[f]
            status, headers, body, _ = f.result()
            case = (LANE_CASE[0], k) + LANE_CASE[2:]
            check_response(case, inputs, status, headers, body)
            out[(k, r)] = body
    return out


def four_chips(inputs: dict) -> dict:
    keys = [k for k in inputs if k.startswith("lane")]
    path = LANE_CASE[2]
    res = {}
    with Server("mesh_off", SERVER_ARGS + ["--mesh-policy", "off"]) as srv:
        res["off_boot_s"] = srv.wait_healthy()
        t0 = time.monotonic()
        base_out = burst(srv.base, inputs, keys, path)
        res["off_burst_s"] = time.monotonic() - t0
        h = health_phase(srv.base, len(base_out))
        if h.get("devices") != 4:
            raise SmokeFailure(f"mesh off: {h.get('devices')} devices, not 4")
    with Server("mesh_lanes", SERVER_ARGS + ["--mesh-policy", "lanes"]) as srv:
        res["lanes_boot_s"] = srv.wait_healthy()
        t0 = time.monotonic()
        lane_out = burst(srv.base, inputs, keys, path)
        res["lanes_burst_s"] = time.monotonic() - t0
        h = health_phase(srv.base, len(lane_out))
        lanes = h["executor"].get("lanes") or []
        served = [ln.get("served_items", 0) for ln in lanes]
        log(f"[smoke] lanes served_items {served}")
        if len(served) != 4 or min(served) <= 0:
            raise SmokeFailure(f"lanes: served_items {served}: not every one "
                               "of 4 lanes served")
        res["lane_served_items"] = served
    diff = [k for k in base_out if base_out[k] != lane_out[k]]
    if diff:
        raise SmokeFailure(f"lanes: {len(diff)} of {len(base_out)} outputs "
                           "differ from the mesh-off run")
    log(f"[smoke] lanes: {len(base_out)} outputs byte-identical to mesh off")
    with Server("mesh_auto", SERVER_ARGS + ["--mesh-policy", "auto", "--spatial", "2",
                                            "--spatial-mpix", "8"]) as srv:
        res["auto_boot_s"] = srv.wait_healthy()
        results = request_phase(srv.base, inputs, cases=[AUTO_CASE], reps=2)
        h = health_phase(srv.base, 2)
        sb = h["executor"].get("spatial_batches", 0)
        log(f"[smoke] auto: spatial_batches {sb}")
        if sb <= 0:
            raise SmokeFailure("auto: the 4K single never took the spatial route")
        res["auto"] = results
        res["spatial_batches"] = sb
    res["health"] = h
    return res


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    try:
        build_s = build_native()
        log(f"[smoke] native build {build_s:.1f}s")
        t0 = time.monotonic()
        sizes = {"1080p": (1920, 1080), "4k": (3840, 2160)}
        if args.chips == 4:
            sizes = {f"lane{i}": (1920, 1080) for i in range(LANE_SOURCES)}
            sizes["4k"] = (3840, 2160)
        inputs = make_inputs(sizes)
        log(f"[smoke] inputs {', '.join(f'{k} {len(v[0])} B' for k, v in sorted(inputs.items()))}"
            f" in {time.monotonic() - t0:.1f}s")
        res = one_chip(inputs) if args.chips == 1 else four_chips(inputs)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    h = res["health"]
    res["total_s"] = time.monotonic() - t_start
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"result_{args.chips}chip.json"), "w") as f:
        json.dump(res, f, indent=1, default=str)
    log(f"[smoke] total {res['total_s']:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": h["backend"], "kind": h["device_kind"],
        "count": h["devices"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
