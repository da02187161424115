"""Load generators: an open loop at a constant rate (vegeta's schedule) and a
closed loop of callers that each wait for their reply. One asyncio loop in
the benchmark's own process, aiohttp as bench_latency.py uses it.

Each request is a dict: `i` (its place in the plan), `route`, `input`,
`due` (open loop: when it was due; closed loop: when it was sent), `sent`,
`done`, `status`, `backend`, `ctype`, `spans` (Server-Timing, parsed),
`body`, `ok` (200 and served by the device). Times are seconds on
time.monotonic() from the run's clock zero.
"""

from __future__ import annotations

import asyncio
import time

import aiohttp

from harness.spans import parse_server_timing

# every request sent in the window is awaited this long past its close
LATE_S = 60.0


class Plan:
    """Which route and which input request `i` carries: the routes round
    robin; the inputs cycle through a seeded permutation, so every seed
    sends the same sizes and the same mix, in another order."""

    def __init__(self, base: str, routes: list, inputs: list, order: list):
        self.base, self.routes, self.inputs, self.order = base, routes, inputs, order

    def request(self, i: int) -> tuple:
        route = self.routes[i % len(self.routes)]
        k = self.order[(i // len(self.routes)) % len(self.order)]
        return route, k


async def fire(session, plan: Plan, i: int, due: float, t0: float) -> dict:
    route, k = plan.request(i)
    inp = plan.inputs[k]
    rec = {"i": i, "route": route["name"], "input": k, "due": due, "sent": None,
           "done": None, "status": -1, "backend": None, "ctype": None, "spans": {},
           "body": b"", "ok": False}
    rec["sent"] = time.monotonic() - t0
    try:
        async with session.post(plan.base + route["path"], data=inp["data"],
                                headers={"Content-Type": inp["mime"]}) as resp:
            body = await resp.read()
            rec["status"] = resp.status
            rec["backend"] = resp.headers.get("X-Imaginary-Backend")
            rec["ctype"] = resp.headers.get("Content-Type")
            rec["spans"] = parse_server_timing(resp.headers.get("Server-Timing"))
            rec["body"] = body
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as e:
        rec["error"] = repr(e)
        return rec
    rec["done"] = time.monotonic() - t0
    rec["ok"] = rec["status"] == 200 and rec["backend"] == "device"
    return rec


def session() -> aiohttp.ClientSession:
    return aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0),
                                 timeout=aiohttp.ClientTimeout(total=None))


async def open_loop(plan: Plan, rate: float, seconds: float, t0: float,
                    first: int = 0) -> list:
    """Request n is due at n / rate; sending stops at `seconds`. Every
    request sent is awaited (up to LATE_S past the close); one that never
    completes is recorded as failed."""
    n = int(rate * seconds)
    tasks = []
    async with session() as s:
        for n_i in range(n):
            due = n_i / rate
            delay = t0 + due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(fire(s, plan, first + n_i, due, t0)))
        done, pending = await asyncio.wait(tasks, timeout=max(0.0, t0 + seconds + LATE_S - time.monotonic()))
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    out = []
    for n_i, t in enumerate(tasks):
        if t in done and not t.cancelled():
            out.append(t.result())
        else:
            route, k = plan.request(first + n_i)
            out.append({"i": first + n_i, "route": route["name"], "input": k, "due": n_i / rate,
                        "sent": None, "done": None, "status": -1, "backend": None,
                        "ctype": None, "spans": {}, "body": b"", "ok": False,
                        "error": "no reply within the wait after the close"})
    return out


async def closed_loop(plan: Plan, clients: int, ramp_s: float, seconds: float,
                      t0: float) -> list:
    """`clients` callers, each sending its next request when its reply has
    come. They start at clock zero; the window is [ramp_s, ramp_s + seconds].
    No request is sent after the window; those in flight are awaited."""
    stop = ramp_s + seconds
    counter = iter(range(1 << 62))
    records: list = []

    async def caller(s):
        while time.monotonic() - t0 < stop:
            i = next(counter)
            records.append(await fire(s, plan, i, time.monotonic() - t0, t0))

    async with session() as s:
        await asyncio.gather(*(caller(s) for _ in range(clients)))
    return records


async def burst(plan: Plan, route: dict, n: int, first: int) -> list:
    """n concurrent requests of one route (warm-up)."""
    one = Plan(plan.base, [route], plan.inputs, plan.order)
    async with session() as s:
        return await asyncio.gather(*(fire(s, one, first + j, 0.0, time.monotonic())
                                      for j in range(n)))
