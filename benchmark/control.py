#!/usr/bin/env python3
"""Readings for the limits of `correct`: the program's numbers and the
control's, on many seeds, with one server boot.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 4]

Not the driver's command. For each seed it makes that seed's inputs, runs
a short window of the cell's own traffic at the cell's own load, and judges
the seeded sample of the replies twice: as the program served them (the
lower reading), and with each reply replaced by the control, the reference
computed at 4 bits per sample and coded as the route codes its replies
(the upper reading). One JSON line per seed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import checks, loadgen, registry  # noqa: E402
from harness.server import BenchFailure, Server  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rate", type=float, default=0.0, help="override an open loop's rate")
    ap.add_argument("--save-over-limit", default="", help="directory for replies over their limit")
    args = ap.parse_args(argv)
    cpu = os.environ.get("BENCH_PLATFORM", "") == "cpu"
    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    cfg = registry.config(spec, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if args.rate:
        traffic = dict(traffic, rate_rps=args.rate)
    routes = traffic["routes"]
    out_dir = os.path.join(run.OUT, "control")
    os.makedirs(out_dir, exist_ok=True)
    gen = cfg["inputs"]
    seeds = [int(s) for s in args.seeds.split(",")]
    srv = Server(registry.ROOT, run.server_args(cfg, cpu), run.server_env(int(cell["chips"]), cpu),
                 os.path.join(out_dir, f"{args.workload}.server.log"))
    rows = []
    try:
        srv.wait_healthy()
        for n, seed in enumerate(seeds):
            inputs = registry.generator(gen["generator"])(gen, seed)
            plan = loadgen.Plan(srv.base, routes, inputs, run.input_order(seed, len(inputs)))
            if n == 0:
                asyncio.run(run.warm(plan, routes, cfg["warm_rungs"], int(cfg.get("warm_repeats", 1)), traffic))
            w = asyncio.run(run.window(srv, plan, traffic, args.seconds, None))
            recs = run.window_records(w["records"], traffic["loop"], w["ramp"], args.seconds)
            picked = checks.sample(recs, routes, seed, checks.PIXEL_SAMPLE_PER_ROUTE)
            prog, detail = checks.judge(recs, routes, inputs, seed, picked=picked)
            if args.save_over_limit:
                os.makedirs(args.save_over_limit, exist_ok=True)
                for route, i, body in detail["over_limit"]:
                    rec = next(r for r in recs if r["i"] == i)
                    with open(os.path.join(args.save_over_limit, f"{seed}.{route}.{i}.in{rec['input']}"), "wb") as f:
                        f.write(body)
            ctl_recs = checks.control_bodies([r for p in picked.values() for r in p], routes, inputs)
            ctl_picked = {name: [r for r in ctl_recs if r["route"] == name] for name in picked}
            ctl, _ = checks.judge(ctl_recs, routes, inputs, seed, picked=ctl_picked)
            ex = w["health_end"].get("executor", {})
            row = {"seed": seed, "attempted": len(recs), "failed": sum(not r["ok"] for r in recs),
                   "avg_batch": ex.get("avg_batch"), "max_group": ex.get("max_group"),
                   "excess": detail["excess"],
                   "program": {k: v["value"] for k, v in prog.items()},
                   "control": {k: v["value"] for k, v in ctl.items()}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    except BenchFailure as e:
        print(f"[control] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        srv.stop()
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
