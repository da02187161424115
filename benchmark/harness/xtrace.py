"""Reduction of a jax.profiler capture (`.xplane.pb`) to device numbers.

The capture is read with `jax.profiler.ProfileData` after the program has
exited, in a process pinned to the CPU. It is first flattened to plain
tuples, {plane: {line: [(name, start_ns, dur_ns), ...]}}, and everything
below works on that, so a test can feed it a recorded trace.

- the window: it starts at the first event of any device plane and lasts
  the seconds the capture was asked for. The two tracers need not begin
  together: under 4K /pipeline load on one v5e chip the device plane's
  events began 1.5-2.0 s after the host's first, about where the host's
  ended, so a window anchored at the host's first event held no device op;
- busy: the union of the op intervals on a device plane's "XLA Ops" line;
- modules: device time of each executed program ("XLA Modules" line),
  keyed by its name without the trailing "(id)";
- idle gaps: the holes between busy intervals, each labelled with the
  host event of the same capture that overlaps it most, or `unattributed`.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# host lines whose events say nothing about what the host was doing
_QUIET_HOST_EVENTS = re.compile(r"^ThreadpoolListener::")


class NoDeviceTime(Exception):
    """The capture gives no device numbers; the message says why."""


def find_capture(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def flatten(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: dict = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
        out[plane.name] = lines
    return out


def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_label(text: str) -> str:
    """`%fusion.11 fusion f32[200,304]` from an op's HLO text (name,
    opcode, result type without its layout); the text itself otherwise."""
    m = re.match(r"(%\S+) = (.*?)\s([a-z][\w\-]*)\(", text)
    if not m:
        return text[:120]
    name, typ, opcode = m.groups()
    return f"{name} {opcode} {re.sub(r'[{][^}]*[}]', '', typ)}"[:120]


def device_planes(flat: dict) -> list:
    return sorted(p for p in flat if p.startswith(DEVICE_PREFIX))


def reduce(flat: dict, window_s: float | None = None, top: int = 10) -> dict:
    """The device numbers of a capture over its window, which starts at the
    first event of any device plane and lasts `window_s` (the seconds the
    capture was asked for: stopping it blocks the program for a while,
    which is the profiler's cost, not the program's), or to the capture's
    last event. Raises NoDeviceTime where the capture holds no device plane
    with ops, or no op in the window."""
    found = device_planes(flat)
    planes = [p for p in found if flat[p].get(OPS_LINE)]
    if not planes:
        raise NoDeviceTime(f"no device plane with ops in the capture (planes: {sorted(flat)})"
                           if found else f"no device plane in the capture (planes: {sorted(flat)})")
    t0 = min(s for p in found for evs in flat[p].values() for _, s, _ in evs)
    t1 = (t0 + window_s * 1e9 if window_s else
          max(s + d for lines in flat.values() for evs in lines.values() for _, s, d in evs))
    per_device = []
    ops_total: dict = {}
    modules: dict = {}
    for p in planes:
        ops = [(n, s, d) for n, s, d in flat[p][OPS_LINE] if s < t1]
        busy = [[s, min(e, t1)] for s, e in union((s, s + d) for _, s, d in ops)]
        per_device.append({"plane": p, "busy_ns": sum(e - s for s, e in busy),
                           "busy": busy})
        for name, _, d in ops:
            ops_total[name] = ops_total.get(name, 0.0) + d
        for name, s, d in flat[p].get(MODULES_LINE, []):
            if s >= t1:
                continue
            m = modules.setdefault(module_name(name), [0, 0.0])
            m[0] += 1
            m[1] += d
    window_ns = t1 - t0
    busy_ns = sum(d["busy_ns"] for d in per_device) / len(per_device)
    if busy_ns <= 0:
        raise NoDeviceTime(f"no device op in the {window_ns / 1e9:.3f} s window")
    gaps = idle_gaps(per_device[0]["busy"], t0, t1, host_events(flat), top)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(per_device),
        "busy_s_per_device": [d["busy_ns"] / 1e9 for d in per_device],
        # where the window starts: after the capture's first event, on any plane
        "anchor_s": (t0 - first_event(flat)) / 1e9,
        "modules": {k: {"count": c, "seconds": t / 1e9} for k, (c, t) in modules.items()},
        "device_ops": [[op_label(n), t / 1e9] for n, t in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": gaps,
    }


def first_event(flat: dict) -> float:
    return min((s for lines in flat.values() for evs in lines.values() for _, s, _ in evs),
               default=0.0)


def digest(flat: dict) -> list:
    """[plane, line, events, first start, last end] for every line with
    events, in seconds after the capture's first event: what a capture that
    gave no device numbers held."""
    t0 = first_event(flat)
    return [[p, name, len(evs), (min(s for _, s, _ in evs) - t0) / 1e9,
             (max(s + d for _, s, d in evs) - t0) / 1e9]
            for p, lines in sorted(flat.items()) for name, evs in sorted(lines.items()) if evs]


def host_events(flat: dict) -> tuple:
    """(names, starts, ends) of the host plane's events that have a length."""
    import numpy as np

    host = flat.get(HOST_PLANE, {})
    evs = [(name, s, s + d) for evs in host.values() for name, s, d in evs
           if d > 0 and not _QUIET_HOST_EVENTS.match(name)]
    return ([e[0] for e in evs], np.array([e[1] for e in evs], np.float64),
            np.array([e[2] for e in evs], np.float64))


def idle_gaps(busy: list, t0: float, t1: float, host: tuple, top: int) -> list:
    """The `top` longest holes in `busy` within [t0, t1], as
    [label, seconds], where the label is the host event overlapping the
    hole the most (of equal overlaps, the shortest event: the innermost)."""
    import numpy as np

    names, hs, he = host
    holes, cur = [], t0
    for s, e in busy:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        holes.append((cur, t1))
    holes.sort(key=lambda h: h[0] - h[1])
    out = []
    for gs, ge in holes[:top]:
        label = "unattributed"
        if names:
            ov = np.minimum(he, ge) - np.maximum(hs, gs)
            best = ov.max()
            if best > 0:
                cand = np.flatnonzero(ov == best)
                label = names[int(cand[np.argmin((he - hs)[cand])])]
        out.append([label, (ge - gs) / 1e9])
    return out
