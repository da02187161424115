#!/usr/bin/env python3
"""One run of one benchmark cell against the served HTTP path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as setup_s): build the native codecs if missing or stale,
make the cell's inputs from the seed, start `python -m imaginary_tpu` with
the configuration's arguments as the only process that opens the chip,
and warm the cell's own shapes. Then measure for --seconds, stop the
server, compare a seeded sample of the window's replies with the plain
reference, and print one JSON line last on stdout. With --trace 1 a 2 s
profiler capture in the window's middle gives the device numbers; a traced
run whose capture gives none (refused, no file, no device op in its
window) prints no line and fails with `trace: <reason>`, kept in run.json.

With no accelerator the server refuses to start (--require-device) and
this exits nonzero with no result; BENCH_PLATFORM=cpu asks for a CPU
rehearsal, which names `cpu` in `device`.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import checks, health, loadgen, registry, xtrace  # noqa: E402
from harness.server import BenchFailure, Server, ensure_native  # noqa: E402

OUT = os.path.join(registry.BENCH, "out")
PROFILE_S = 2.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def server_env(chips: int, cpu: bool) -> dict:
    env = dict(os.environ)
    # the compile cache at a fixed place inside this checkout, whatever
    # the machine sets: only this checkout's first run compiles
    cache = os.path.join(registry.ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)  # JAX writes no entry into a directory that is missing
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    # no eviction: with it on, JAX keeps an access-time file beside every
    # entry and fails every write once one entry lacks it (an entry written
    # with eviction off), so nothing more is cached
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={chips}").strip()
    return env


def server_args(cfg: dict, cpu: bool) -> list:
    args = list(cfg["server_args"])
    if cpu:  # a CPU rehearsal cannot require a device
        args = [a for a in args if a != "--require-device"]
    return args


class Context:
    """What a metric reader reads (benchmark/metrics/<name>.py)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


async def get_json(base: str, path: str, timeout: float = 60.0) -> dict:
    import aiohttp

    async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=timeout)) as s:
        async with s.get(base + path) as r:
            return await r.json(content_type=None)


async def warm(plan: loadgen.Plan, routes: list, rungs: list, repeats: int,
               traffic: dict | None = None) -> int:
    """A burst at every batch rung, `repeats` times, for each route; then,
    where the traffic file asks for it, `warm_s` of the cell's own loop (so
    every lane meets every rung it will meet in the window)."""
    n = 0
    for route in routes:
        for _ in range(repeats):
            for rung in rungs:
                recs = await loadgen.burst(plan, route, rung, first=-(n + rung))
                n += rung
                bad = [(r["status"], r["backend"], r.get("error")) for r in recs if not r["ok"]]
                if bad:
                    raise BenchFailure(f"warm-up {route['name']} x{rung}: {bad[:3]}")
    warm_s = float((traffic or {}).get("warm_s", 0.0))
    if warm_s > 0:
        t0 = time.monotonic()
        if traffic["loop"] == "open":
            recs = await loadgen.open_loop(plan, float(traffic["rate_rps"]), warm_s, t0, first=-10**9)
        else:
            recs = await loadgen.closed_loop(plan, int(traffic["clients"]), 0.0, warm_s, t0)
        bad = [(r["status"], r["backend"], r.get("error")) for r in recs if not r["ok"]]
        if bad:
            raise BenchFailure(f"warm-up traffic: {len(bad)} of {len(recs)} failed: {bad[:3]}")
        n += len(recs)
    return n


async def window(srv: Server, plan: loadgen.Plan, traffic: dict, seconds: float,
                 trace_dir: str | None) -> dict:
    """Drive the window; health at its start and end; a profile in its middle."""
    loop = traffic["loop"]
    ramp = float(traffic.get("ramp_s", 0.0)) if loop == "closed" else 0.0
    t0 = time.monotonic() + 0.05
    out: dict = {"t0": t0, "ramp": ramp}

    async def at(offset: float, coro):
        delay = t0 + offset - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        return await coro

    async def profile():
        import aiohttp

        mid = ramp + seconds / 2.0 - PROFILE_S / 2.0
        ha = await at(mid, get_json(srv.base, "/health"))
        s = time.monotonic() - t0
        try:
            r = await get_json(srv.base, f"/debugz/profile?seconds={PROFILE_S}&dir={trace_dir}",
                               timeout=PROFILE_S + 120)
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as err:
            r = {"error": f"the profile call failed: {err!r}"}
        e = time.monotonic() - t0
        hb = await get_json(srv.base, "/health")
        return {"health_a": ha, "health_b": hb, "interval": (s, e), "reply": r}

    h_start = asyncio.create_task(at(ramp, get_json(srv.base, "/health")))
    prof = asyncio.create_task(profile()) if trace_dir else None
    if loop == "open":
        records = await loadgen.open_loop(plan, float(traffic["rate_rps"]), seconds, t0)
        out["health_end"] = await get_json(srv.base, "/health")
    else:
        h_end = asyncio.create_task(at(ramp + seconds, get_json(srv.base, "/health")))
        records = await loadgen.closed_loop(plan, int(traffic["clients"]), ramp, seconds, t0)
        out["health_end"] = await h_end
    out["health_start"] = await h_start
    out["records"] = records
    out["profile"] = await prof if prof else None
    return out


def window_records(records: list, loop: str, ramp: float, seconds: float) -> list:
    """Open loop: every request due in the window. Closed loop: every
    reply completed in it."""
    if loop == "open":
        return [r for r in records if r["due"] < seconds]
    return [r for r in records if r["done"] is not None and ramp <= r["done"] < ramp + seconds]


def lateness(records: list) -> dict:
    late = sorted((r["sent"] - r["due"]) * 1000.0 for r in records if r["sent"] is not None)
    if not late:
        return {}
    return {"p50_ms": late[len(late) // 2], "p99_ms": late[int(0.99 * (len(late) - 1))],
            "max_ms": late[-1]}


def input_order(seed: int, n: int) -> list:
    """The seeded order in which requests take the inputs."""
    return [int(k) for k in np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 1]).permutation(n)]


def read_capture(reply: dict, trace_dir: str, seconds: float) -> dict:
    """What the window's capture gave: the profile call's reply, the file
    read, a digest of its planes and lines, and either `trace`, its device
    numbers, or `error`, why there are none (a refused or failed call, no
    file, no device op in the window)."""
    out: dict = {"reply": reply, "file": None, "lines": None}
    try:
        if not isinstance(reply, dict) or reply.get("error"):
            raise xtrace.NoDeviceTime(f"the capture was refused: {reply}")
        path = xtrace.find_capture(trace_dir)
        if not path:
            raise xtrace.NoDeviceTime(f"the capture wrote no .xplane.pb under {trace_dir}")
        out["file"] = os.path.relpath(path, trace_dir)
        # the server has exited: this process may load JAX, on the CPU only
        os.environ["JAX_PLATFORMS"] = "cpu"
        flat = xtrace.flatten(path)
        out["lines"] = xtrace.digest(flat)
        out["trace"] = xtrace.reduce(flat, seconds)
    except xtrace.NoDeviceTime as e:
        out["error"] = f"trace: {e}"
    return out


def run(args) -> dict:
    cpu = os.environ.get("BENCH_PLATFORM", "") == "cpu"
    if os.environ.get("BENCH_PLATFORM", "") not in ("", "cpu"):
        raise BenchFailure("BENCH_PLATFORM may only be 'cpu' (an explicit CPU rehearsal)")
    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    cfg = registry.config(spec, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    chips = int(cell["chips"])
    run_dir = os.path.join(OUT, args.workload, f"{args.seed}-t{args.trace}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    t_setup = time.monotonic()
    native_s = ensure_native(registry.ROOT)
    t = time.monotonic()
    gen = cfg["inputs"]
    inputs = registry.generator(gen["generator"])(gen, args.seed)
    gen_s = time.monotonic() - t
    sizes = [len(x["data"]) for x in inputs]
    log(f"[bench] inputs: {len(inputs)} x {gen['generator']} in {gen_s:.2f}s, "
        f"bytes min {min(sizes)} median {sorted(sizes)[len(sizes) // 2]} max {max(sizes)}")
    order = input_order(args.seed, len(inputs))

    srv = Server(registry.ROOT, server_args(cfg, cpu), server_env(chips, cpu),
                 os.path.join(run_dir, "server.log"))
    try:
        t = time.monotonic()
        h = srv.wait_healthy()
        boot_s = time.monotonic() - t
        platform = h.get("backend")
        if not cpu and platform in (None, "cpu"):
            raise BenchFailure(f"no accelerator: the server's backend is {platform!r}")
        if int(h.get("devices", 0)) < chips:
            raise BenchFailure(f"the cell asks for {chips} chips; the server sees {h.get('devices')}")
        plan = loadgen.Plan(srv.base, traffic["routes"], inputs, order)
        t = time.monotonic()
        warmed = asyncio.run(warm(plan, traffic["routes"], cfg["warm_rungs"],
                                  int(cfg.get("warm_repeats", 1)), traffic))
        warm_s = time.monotonic() - t
        setup_s = time.monotonic() - t_setup
        log(f"[bench] setup {setup_s:.2f}s: native {native_s:.2f}s, inputs {gen_s:.2f}s, "
            f"boot {boot_s:.2f}s, warm {warm_s:.2f}s ({warmed} requests)")
        trace_dir = os.path.join(run_dir, "trace") if args.trace else None
        gc.collect()
        gc.disable()  # no collector pause in the load generator inside the window
        try:
            w = asyncio.run(window(srv, plan, traffic, float(args.seconds), trace_dir))
        finally:
            gc.enable()
        final = health.fetch(srv.base)
    finally:
        srv.stop()

    loop = traffic["loop"]
    recs = window_records(w["records"], loop, w["ramp"], float(args.seconds))
    h0, h1 = w["health_start"], w["health_end"]
    compiles = health.executor_delta(h0, final, "compile_misses")
    late = lateness(w["records"]) if loop == "open" else {}
    items = health.executor_delta(h0, h1, "items")
    host_served = sum(health.executor_delta(h0, final, k)
                      for k in ("spilled", "breaker_host_served", "oom_host_routed"))
    # the spans the per-layer metrics read, against the program's own
    # cumulative stage timers over the same window
    span_vs_health = {}
    for stage in ("decode", "batch_form", "dispatch_wait", "encode"):
        n, ms = health.stage_delta(h0, h1, stage)
        spans = [r["spans"][stage] for r in recs if r["ok"] and stage in r["spans"]]
        span_vs_health[stage] = {"health_mean_ms": ms / n if n else None,
                                 "span_mean_ms": sum(spans) / len(spans) if spans else None}
    log(f"[bench] window: {len(recs)} requests, {sum(r['ok'] for r in recs)} ok, "
        f"items {items}, host-served {host_served}, compiles in window {compiles}, "
        f"generator late {json.dumps(late)}")

    prof = w["profile"]
    capture = read_capture(prof["reply"], trace_dir, PROFILE_S) if prof else {}
    trace = capture.get("trace")
    if prof and not os.environ.get("BENCH_KEEP_TRACE"):
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.monotonic()
    cks, detail = checks.judge(recs, traffic["routes"], inputs, args.seed, diagnose=True)
    check_s = time.monotonic() - t
    for route, i, body in detail.pop("over_limit"):
        with open(os.path.join(run_dir, f"over_limit.{route}.{i}"), "wb") as f:
            f.write(body)

    # span metrics read the replies completed before the capture began: the
    # profiler slows the server while it records and the backlog it leaves
    # drains for seconds after
    start = prof["interval"][0] if prof else math.inf
    untraced = [r for r in recs if r["done"] is not None and r["done"] < start]
    ctx = Context(records=recs, untraced=untraced, all_records=w["records"], loop=loop,
                  seconds=float(args.seconds), setup_s=setup_s, health_start=h0,
                  health_end=h1, trace=trace, profile=prof, chips=chips)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in registry.cell_metrics(spec, args.workload, kind):
        v = registry.metric(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    peak = final.get("peak_bytes_in_use")
    device = {"platform": platform, "kind": h.get("device_kind"), "count": int(h.get("devices", 0)),
              "memory_peak_bytes": max(peak) if isinstance(peak, list) and peak else peak}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    result = {"correct": checks.passed(cks), "attempted": len(recs),
              "failed": sum(not r["ok"] for r in recs), "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["checks"] = cks
    with open(os.path.join(run_dir, "run.json"), "w") as f:
        json.dump({"cell": cell, "seed": args.seed, "setup_s": setup_s, "boot_s": boot_s,
                   "native_s": native_s, "inputs_s": gen_s, "warm_s": warm_s, "input_bytes": sizes,
                   "lateness_ms": late, "compiles_in_window": compiles, "items": items,
                   "span_vs_health": span_vs_health,
                   "host_served": host_served, "check_s": check_s, "checks_detail": detail,
                   "health_start": h0, "health_end": h1, "trace": trace,
                   "profile_interval": prof["interval"] if prof else None,
                   "capture": {k: v for k, v in capture.items() if k != "trace"},
                   "timeline": [[r["route"], r["due"], r["sent"], r["done"], r["status"],
                                 r["spans"].get("total")] for r in w["records"]],
                   "result": result}, f, indent=1, default=str)
    for n in detail["nearest"]:
        log(f"[bench] over its limit: {json.dumps(n)}")
    for name, c in cks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    if capture.get("error"):  # a traced run gives device numbers or no result
        raise BenchFailure(capture["error"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except (BenchFailure, KeyError, FileNotFoundError) as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
