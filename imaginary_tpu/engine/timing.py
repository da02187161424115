"""Per-stage timing aggregation (SURVEY.md section 5.1).

The reference logs only whole-request latency (log.go:80-85). For a
device-backed service the actionable split is per stage of the request's
journey: probe/decode on host, queue wait, drain (H2D, compute and D2H
of a launched batch), encode. Each stage records into a bounded ring so
/health can report count/mean/p50/p99 without unbounded memory, and the
bench can print an honest breakdown of where time goes. `stage` is how
the host code times one; inside a `jax.profiler` capture it is also an
annotation on the device trace's clock.

A `jax.profiler` trace can be captured around the whole serving loop by
setting IMAGINARY_TPU_PROFILE_DIR; see `maybe_start_profiler`.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from imaginary_tpu.obs import cost as _obs_cost
from imaginary_tpu.obs import histogram as _obs_hist
from imaginary_tpu.obs import trace as _obs_trace

_RING = 2048  # samples kept per stage for percentile estimates

STAGES = (
    "probe",        # header-only metadata parse
    "decode",       # host codec decode (incl. shrink-on-load)
    "queue_wait",   # submit -> device-call launch (batch_form + dispatch_wait)
    "batch_form",   # submit -> chunk close (bounded by the formation cap)
    "dispatch_wait",  # chunk close -> launch issued (behind in-flight chunks)
    "drain",        # fetch start -> host bytes landed (one sync, amortized/item)
    "host_gate",    # wait for a host-pool slot (bounded spill concurrency)
    "host_spill",   # host SIMD interpreter execution (spilled items)
    "encode",       # host codec encode
    "total",        # whole processing call
)

# Per-stage histogram children resolved once: record() is the hot path
# (several calls per request) and the stage set is fixed, so the labels()
# lookup should not be paid per sample.
_STAGE_HISTS = {s: _obs_hist.STAGE_SECONDS.labels(s) for s in STAGES}


class StageTimes:
    """Thread-safe per-stage latency aggregator."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sum = {s: 0.0 for s in STAGES}
        self._count = {s: 0 for s in STAGES}
        self._ring = {s: np.zeros(_RING, dtype=np.float32) for s in STAGES}
        self._pos = {s: 0 for s in STAGES}

    def record(self, stage: str, ms: float) -> None:
        with self._lock:
            self._sum[stage] += ms
            c = self._count[stage]
            self._count[stage] = c + 1
            ring = self._ring[stage]
            ring[self._pos[stage]] = ms
            self._pos[stage] = (self._pos[stage] + 1) % _RING
        # Observability fan-out, outside the lock. The histogram is the
        # aggregatable /metrics surface; the trace attribution turns the
        # same sample into a per-request span whenever the recording
        # thread carries a request context (handler tasks and host-pool
        # workers do; the executor's collector/fetcher threads do not —
        # their stages are batch-scoped, not request-scoped).
        hist = _STAGE_HISTS.get(stage)
        if hist is not None:
            hist.observe(ms / 1000.0)
        else:
            _obs_hist.STAGE_SECONDS.observe((stage,), ms / 1000.0)
        tr = _obs_trace.current()
        if tr is not None:
            tr.add_span(stage, ms)

    def snapshot(self) -> dict:
        out = {}
        with self._lock:
            for s in STAGES:
                c = self._count[s]
                if not c:
                    continue
                n = min(c, _RING)
                window = np.sort(self._ring[s][:n])
                out[s] = {
                    "count": c,
                    "mean_ms": round(self._sum[s] / c, 3),
                    "p50_ms": round(float(window[int(0.50 * (n - 1))]), 3),
                    "p99_ms": round(float(window[int(0.99 * (n - 1))]), 3),
                }
        return out

    def totals(self) -> dict:
        """{stage: (count, cumulative_ms)} — the monotonic view the
        capacity plane's utilization sampler diffs between snapshots
        (busy fractions need sums, not the ring percentiles)."""
        with self._lock:
            return {s: (self._count[s], self._sum[s])
                    for s in STAGES if self._count[s]}

    def reset(self) -> None:
        with self._lock:
            for s in STAGES:
                self._sum[s] = 0.0
                self._count[s] = 0
                self._pos[s] = 0


# Process-wide registry: the pipeline, executor, and /health all share it.
TIMES = StageTimes()


class stage:
    """Time a block as one stage: on a clean exit it records into TIMES
    (and so into the current request's spans); while a profiler capture
    is active it is also a `jax.profiler.TraceAnnotation` of the same
    name. A stage that only encloses other stages passes
    `annotate=False`, so a device idle gap is labelled by the work inside."""

    __slots__ = ("name", "annotate", "_t0", "_ann")

    def __init__(self, name: str, annotate: bool = True):
        self.name = name
        self.annotate = annotate
        self._ann = None

    def __enter__(self):
        if self.annotate and _obs_trace.capture_active:
            self._ann = _obs_trace.annotation(self.name).__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        ms = (time.monotonic() - self._t0) * 1000.0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if exc_type is None:
            TIMES.record(self.name, ms)
        return False


class WireLedger:
    """Measured host<->device link bytes, booked where staging actually
    happens (ops/chain.py: the batch-operand device_put for H2D, the
    device_get readbacks for D2H).

    This is the ground truth the link projection was missing: the static
    estimate in bench_device.py recomputed raw-pixel sizes, but what the
    link really carries depends on transport (rgb vs packed yuv420 vs dct
    coefficients) and on the device frame cache suppressing repeat H2D.
    Totals are monotonic counters (exported as
    imaginary_tpu_wire_bytes_total{direction=}); transfer counts ride along
    so per-transfer sizes stay derivable. Process-wide like TIMES — the
    link is a per-host resource, not a per-executor one.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes = {"h2d": 0, "d2h": 0}
        self._transfers = {"h2d": 0, "d2h": 0}
        # per-device attribution (multi-chip lanes): direction -> device
        # label -> bytes. Only populated when a caller names a device —
        # the single-lane path never does, so its snapshot (and /health)
        # stays byte-identical to the pre-lanes build.
        self._by_device: dict = {"h2d": {}, "d2h": {}}

    def add(self, direction: str, nbytes: int, device=None) -> None:
        with self._lock:
            self._bytes[direction] += int(nbytes)
            self._transfers[direction] += 1
            if device is not None:
                dd = self._by_device[direction]
                dd[str(device)] = dd.get(str(device), 0) + int(nbytes)

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "h2d": self._bytes["h2d"],
                "d2h": self._bytes["d2h"],
                "h2d_transfers": self._transfers["h2d"],
                "d2h_transfers": self._transfers["d2h"],
            }
            if self._by_device["h2d"] or self._by_device["d2h"]:
                out["by_device"] = {
                    "h2d": dict(self._by_device["h2d"]),
                    "d2h": dict(self._by_device["d2h"]),
                }
            return out

    def reset(self) -> None:
        with self._lock:
            self._bytes = {"h2d": 0, "d2h": 0}
            self._transfers = {"h2d": 0, "d2h": 0}
            self._by_device = {"h2d": {}, "d2h": {}}


WIRE = WireLedger()

# Canonical byte-touch stages, in request order. The ledger accepts any
# label (future stages must not require a ledger edit), but these are the
# ones the host path books today; /metrics emits whatever shows up.
COPY_STAGES = (
    "ingress",    # request body landed in host memory (streamed read)
    "decode",     # codec output pixels materialized
    "transform",  # intermediate frame copies (host spill / device staging)
    "encode",     # encoded body materialized
    "response",   # extra body copies on the serving edge (target: zero)
    "cache_hit",  # bytes touched serving a cached body (target: 1x body)
)


class CopyLedger:
    """Per-stage ledger of host bytes actually COPIED per request's journey
    (ingress -> decode -> transform -> encode -> response), the
    generalization of the shm tier's `bytes_copied` counter to the whole
    host path.

    "Bytes touched per byte served" is the metric the reference's libvips
    core wins on (one C pipeline, no per-hop body materialization); this
    ledger makes it first-class and gateable: every site that materializes
    a body or frame books here, so a future "convenience" bytes() slice
    shows up as a counter regression in bench_stages.py rather than a
    profiler session. Monotonic totals (exported as
    imaginary_tpu_bytes_copied_total{stage=}); copy-event counts ride
    along so copies-per-request stays derivable. Process-wide like WIRE —
    host memory bandwidth is a per-host resource.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes: dict = {}
        self._copies: dict = {}

    def add(self, stage: str, nbytes: int, copies: int = 1) -> None:
        with self._lock:
            self._bytes[stage] = self._bytes.get(stage, 0) + int(nbytes)
            self._copies[stage] = self._copies.get(stage, 0) + int(copies)
        # Cost-attribution stamp (obs/cost.py): when the plane is armed
        # AND the booking thread carries a request context (handler
        # tasks + host-pool workers do), the same bytes attribute to the
        # request's cost vector. Off by default: no plane, no stamp.
        if _obs_cost.active() is not None:
            tr = _obs_trace.current()
            if tr is not None:
                tr.accumulate("cost_copied_bytes", int(nbytes))
                if stage == "cache_hit":
                    tr.accumulate("cost_cache_bytes", int(nbytes))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bytes": dict(self._bytes),
                "copies": dict(self._copies),
            }

    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._bytes.values())

    def reset(self) -> None:
        with self._lock:
            self._bytes = {}
            self._copies = {}


COPIES = CopyLedger()


class LaneStageTimes:
    """Per-lane split of the executor stages (multi-chip lanes).

    TIMES aggregates batch_form/dispatch_wait/drain fleet-wide; with one
    lane per chip the actionable view is per LANE — a limping chip's
    drain EWMA must not hide inside its healthy peers' average (the same
    reasoning that moved the fail-slow latency booking per-chunk). Tiny
    count+EWMA cells rather than full rings: /debugz wants a trend per
    (lane, stage), not percentiles — the fleet percentiles stay in TIMES.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # (lane, stage) -> [count, ewma_ms, total_ms]; the cumulative
        # total feeds the capacity plane's per-lane busy fractions
        self._cells: dict = {}

    def record(self, lane: int, stage: str, ms: float) -> None:
        with self._lock:
            cell = self._cells.get((lane, stage))
            if cell is None:
                self._cells[(lane, stage)] = [1, ms, ms]
            else:
                cell[0] += 1
                cell[1] = 0.8 * cell[1] + 0.2 * ms
                cell[2] += ms

    def snapshot(self) -> dict:
        """{lane: {stage: {count, ewma_ms, total_ms}}} — empty when no
        lane ever recorded (the single-lane parity path)."""
        with self._lock:
            out: dict = {}
            for (lane, stage), (count, ewma, total) in self._cells.items():
                out.setdefault(lane, {})[stage] = {
                    "count": count, "ewma_ms": round(ewma, 3),
                    "total_ms": round(total, 3)}
            return out

    def totals(self) -> dict:
        """{(lane, stage): cumulative_ms} for utilization delta math."""
        with self._lock:
            return {k: cell[2] for k, cell in self._cells.items()}

    def reset(self) -> None:
        with self._lock:
            self._cells = {}


LANE_TIMES = LaneStageTimes()

_profiler_started = False
_profiler_lock = threading.Lock()


def start_profiler(trace_dir: str) -> bool:
    """Start a jax.profiler trace into an explicit directory. Returns
    False when a capture is already active (one at a time: jax keeps one
    global trace session). /debugz/profile uses this for one-shot
    captures from a live process — no restart needed.

    The capture runs without JAX's Python tracer: it records every Python
    call on every thread, which slows the server it measures and buries
    the program's own annotations (obs/trace.annotation) under thousands
    of interpreter frames. The host tracer stays at its default, so the
    annotations and the runtime's own events are kept."""
    global _profiler_started
    with _profiler_lock:
        if _profiler_started:
            return False
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        _profiler_started = True
        _obs_trace.capture_active = True
        return True


def profiler_active() -> bool:
    with _profiler_lock:
        return _profiler_started


def maybe_start_profiler() -> bool:
    """Start a jax.profiler trace if IMAGINARY_TPU_PROFILE_DIR is set.

    The trace covers everything until stop_profiler() (or process exit);
    inspect with TensorBoard or xprof. Returns True if a trace started.
    """
    trace_dir = os.environ.get("IMAGINARY_TPU_PROFILE_DIR")
    if not trace_dir:
        return False
    return start_profiler(trace_dir)


def stop_profiler() -> None:
    global _profiler_started
    with _profiler_lock:
        if _profiler_started:
            import jax

            _obs_trace.capture_active = False
            jax.profiler.stop_trace()
            _profiler_started = False
