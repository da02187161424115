#!/usr/bin/env python3
"""Find a paced cell's knee: one server boot, then the cell's traffic at a
series of constant rates, each for --step-seconds with a pause between.

    python benchmark/sweep.py --workload <paced cell> --seed <n> --rates 50,100,200 [--step-seconds 10]

Not the driver's command. For each rate it prints one JSON line: offered
and completed, p50 and p95 from the due time, the p50 of the first and of
the second half of the step (by due time), and how late the generator
ran. The knee is the highest rate at which every request completes and
the second half is no slower than the first beyond the run-to-run spread;
the paced cell runs at 0.8 x the knee (PERF.md records the sweep).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import loadgen, registry, stats  # noqa: E402
from harness.server import BenchFailure, Server  # noqa: E402


def step(plan, rate: float, seconds: float, first: int) -> dict:
    t0 = time.monotonic() + 0.05
    recs = asyncio.run(loadgen.open_loop(plan, rate, seconds, t0, first=first))
    lat = stats.latencies_from_due(recs, seconds)
    half = [r for r in recs if r["due"] < seconds / 2], [r for r in recs if r["due"] >= seconds / 2]
    p50h = [stats.pctl(stats.latencies_from_due(h, seconds), 0.5) for h in half]
    return {"rate_rps": rate, "offered": len(recs), "ok": sum(r["ok"] for r in recs),
            "p50_ms": stats.pctl(lat, 0.5), "p95_ms": stats.pctl(lat, 0.95),
            "p50_first_half_ms": p50h[0], "p50_second_half_ms": p50h[1],
            "late_ms": run.lateness(recs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s, in order")
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--pause", type=float, default=3.0)
    args = ap.parse_args(argv)
    cpu = os.environ.get("BENCH_PLATFORM", "") == "cpu"
    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    cfg = registry.config(spec, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    out_dir = os.path.join(run.OUT, "sweep")
    os.makedirs(out_dir, exist_ok=True)
    gen = cfg["inputs"]
    inputs = registry.generator(gen["generator"])(gen, args.seed)
    order = run.input_order(args.seed, len(inputs))
    srv = Server(registry.ROOT, run.server_args(cfg, cpu), run.server_env(int(cell["chips"]), cpu),
                 os.path.join(out_dir, f"{args.workload}.server.log"))
    rows = []
    try:
        srv.wait_healthy()
        plan = loadgen.Plan(srv.base, traffic["routes"], inputs, order)
        asyncio.run(run.warm(plan, traffic["routes"], cfg["warm_rungs"], int(cfg.get("warm_repeats", 1)),
                             traffic))
        first = 0
        for rate in (float(x) for x in args.rates.split(",")):
            row = step(plan, rate, args.step_seconds, first)
            first += row["offered"]
            rows.append(row)
            print(json.dumps(row), flush=True)
            if row["p50_second_half_ms"] > 2.0 * row["p50_first_half_ms"] + 50.0:
                break  # a backlog that grows all through the step: past the knee
            time.sleep(args.pause)
    except BenchFailure as e:
        print(f"[sweep] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        srv.stop()
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
