"""Per-request distributed-trace identity and span accumulation.

Dapper-shaped, not OpenTelemetry-shaped: one RequestTrace per HTTP
request, carried by a contextvar so every layer the request touches —
handler, cache lookup, coalesce wait, pipeline stages, executor
queue/device vs host-spill, encode — can attach spans and annotations
without plumbing an argument through a dozen signatures. The web
middleware creates/activates the trace; `contextvars.copy_context()`
carries it into the host worker pool, so spans recorded on the worker
thread (decode/encode/host_spill via engine/timing.py's stage hook)
attribute to the right request. Stages recorded on the executor's own
collector/fetcher threads (queue_wait and its batch_form/dispatch_wait
split, drain) aggregate in /metrics but are not per-request
attributable — by design, they are batch-scoped.
The one exception is the PLACEMENT LADDER: each queued executor item
carries a reference to its request's trace, so the collector stamps the
per-chip dispatch attempts (`placement_attempts`, engine/executor.py)
onto the right request even though it runs on its own thread —
annotate() takes the trace lock, so cross-thread stamps are safe.

While a jax.profiler capture runs, the same boundaries also open
`jax.profiler.TraceAnnotation`s (`annotation`, `span`, and
engine/timing.stage), so the capture carries the program's own host
spans on the device trace's clock.

Identity follows W3C Trace Context: an inbound `traceparent` header is
honored (same trace-id continues, our span becomes a child); outbound
fetches (web/sources.py) forward a fresh child `traceparent` plus the
`X-Request-ID`. Both headers are also echoed on every response.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import os
import re
import secrets
import threading
import time
from typing import Optional

# 00-<trace-id 32hex>-<parent-id 16hex>-<flags 2hex> (W3C Trace Context)
_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)
# Echoed into response headers and log lines: restrict to a safe charset
# so a hostile inbound id cannot inject headers or forge log fields.
_REQID_RE = re.compile(r"^[A-Za-z0-9._@=+/-]{1,128}$")
# Server-Timing metric names must be RFC 9110 tokens.
_TOKEN_SUB = re.compile(r"[^A-Za-z0-9_.-]").sub

_MAX_SPANS = 256  # hard cap; a runaway loop must not grow a trace unbounded


def new_request_id() -> str:
    return secrets.token_hex(16)


def sanitize_request_id(raw: str) -> str:
    """An inbound X-Request-ID is reused verbatim when it is a sane token;
    anything else (empty, oversized, hostile chars) is discarded and the
    middleware generates a fresh id."""
    return raw if raw and _REQID_RE.match(raw) else ""


class Span:
    __slots__ = ("name", "start_ms", "dur_ms")

    def __init__(self, name: str, start_ms: float, dur_ms: float):
        self.name = name
        self.start_ms = start_ms
        self.dur_ms = dur_ms

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "start_ms": round(self.start_ms, 3),
            "dur_ms": round(self.dur_ms, 3),
        }


class RequestTrace:
    """One request's identity + span timeline + wide-event fields."""

    __slots__ = ("request_id", "trace_id", "parent_span_id", "span_id",
                 "flags", "enabled", "t0", "spans", "fields", "deadline",
                 "tenant", "_lock")

    def __init__(self, request_id: str, traceparent: str = "",
                 enabled: bool = True):
        self.request_id = request_id
        m = _TRACEPARENT_RE.match(traceparent.strip().lower()) if traceparent else None
        if m:
            self.trace_id = m.group(1)
            self.parent_span_id = m.group(2)
            self.flags = m.group(3)
            self.span_id = os.urandom(8).hex()
        else:
            # one urandom call covers both ids (hot path: every request)
            rand = os.urandom(24).hex()
            self.trace_id = rand[:32]
            self.span_id = rand[32:]
            self.parent_span_id = ""
            self.flags = "01"
        self.enabled = enabled
        self.t0 = time.monotonic()
        self.spans: list = []
        self.fields: dict = {}
        # Per-request deadline (imaginary_tpu/deadline.py), set by the web
        # middleware when --request-timeout is on. It rides the trace so
        # copy_context() carries exactly ONE vehicle into pool threads —
        # deadline enforcement works even with tracing disabled (enabled
        # gates span accumulation, not identity or lifecycle state).
        self.deadline = None
        # Resolved TenantSpec (imaginary_tpu/qos/tenancy.py), stamped by
        # the web middleware when a qos policy is configured. Rides the
        # trace for the same reason the deadline does: copy_context()
        # carries ONE vehicle into pool threads, and the executor's fair
        # scheduler reads tenant+class from it at submit time. None when
        # qos is off (the default) — every consumer takes a fast path.
        self.tenant = None
        self._lock = threading.Lock()

    # -- accumulation (called from handler tasks AND pool threads) ---------

    def add_span(self, name: str, dur_ms: float,
                 end: Optional[float] = None) -> None:
        if not self.enabled:
            return
        end = time.monotonic() if end is None else end
        start_ms = (end - self.t0) * 1000.0 - dur_ms
        with self._lock:
            if len(self.spans) < _MAX_SPANS:
                self.spans.append(Span(name, start_ms, dur_ms))

    def annotate(self, **fields) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.fields.update(fields)

    def accumulate(self, key: str, delta: float) -> None:
        """Thread-safe additive field — the cost-attribution stamps
        (cost_device_ms, cost_wire_bytes, ...) sum contributions from
        executor/ledger threads here. Unlike annotate/add_span this is
        NOT gated on `enabled`: cost booking must work with tracing
        off, and the fields only reach a wide event via to_event, which
        tracing-off requests never build."""
        with self._lock:
            self.fields[key] = self.fields.get(key, 0.0) + delta

    def field(self, key: str, default=None):
        with self._lock:
            return self.fields.get(key, default)

    def span_sum(self, names) -> float:
        """Summed duration of every span whose name is in `names` —
        how the middleware derives a request's host-pool-ms from its
        probe/decode/encode/host_spill spans at booking time."""
        with self._lock:
            return sum(s.dur_ms for s in self.spans if s.name in names)

    def duration_ms(self) -> float:
        return (time.monotonic() - self.t0) * 1000.0

    # -- identity ----------------------------------------------------------

    def traceparent(self) -> str:
        """This request's own span context."""
        return f"00-{self.trace_id}-{self.span_id}-{self.flags}"

    def outbound_traceparent(self) -> str:
        """A fresh child span id per outbound hop (each ?url= / watermark
        fetch is its own child of this request's span)."""
        return f"00-{self.trace_id}-{secrets.token_hex(8)}-{self.flags}"

    def exemplar(self) -> tuple:
        """(request_id, trace_id) — the identity pair the latency
        histograms attach to their buckets (obs/histogram.py), so a
        spike in the merged fleet exposition links to this request's
        wide event."""
        return self.request_id, self.trace_id

    # -- surfaces ----------------------------------------------------------

    def server_timing(self, limit: int = 16) -> str:
        """RFC draft Server-Timing: one `name;dur=` entry per distinct span
        name (durations of repeated spans sum), first-seen order."""
        agg: dict = {}
        with self._lock:
            for s in self.spans:
                agg[s.name] = agg.get(s.name, 0.0) + s.dur_ms
        parts = [
            f"{_TOKEN_SUB('_', name)};dur={dur:.2f}"
            for name, dur in list(agg.items())[:limit]
        ]
        return ", ".join(parts)

    def to_event(self, **extra) -> dict:
        """The wide-event dict: identity, annotations, and the full span
        timeline. Extra keys (route/method/status/...) ride alongside."""
        with self._lock:
            fields = dict(self.fields)
            spans = [s.to_dict() for s in self.spans]
        event = {
            "request_id": self.request_id,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
        }
        event.update(extra)
        event.update(fields)
        event["spans"] = spans
        return event


_current: contextvars.ContextVar = contextvars.ContextVar(
    "imaginary_tpu_trace", default=None
)


def activate(tr: RequestTrace):
    """Install `tr` as the current context's trace; returns a reset token."""
    return _current.set(tr)


def deactivate(token) -> None:
    _current.reset(token)


def current() -> Optional[RequestTrace]:
    return _current.get()


# True while a jax.profiler capture runs; engine/timing.start_profiler and
# stop_profiler set it. Read without a lock: with no capture, a stage pays
# this one global read for its profiler annotation.
capture_active = False
_NO_ANNOTATION = contextlib.nullcontext()


class annotation:
    """A `jax.profiler.TraceAnnotation` around a block while a capture is
    active, so the capture shows what this thread was doing on the device
    trace's clock; nothing is constructed otherwise."""

    __slots__ = ("name", "_ann")

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def __enter__(self):
        if capture_active:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False


def _on_event_loop() -> bool:
    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return False
    return True


@contextlib.contextmanager
def span(name: str, annotate: bool = True):
    """Time a block into the current trace; no-op when no trace is active
    (the pipeline and cache layers work unchanged outside a request).

    While a capture is active the block is also a profiler annotation,
    except on the event loop: there a request's await interleaves with
    every other request's, and their events would overlap on one thread.
    A span that only encloses waits on other threads passes
    `annotate=False`, so a device idle gap is labelled by the work inside."""
    with annotation(name) if (annotate and capture_active
                              and not _on_event_loop()) else _NO_ANNOTATION:
        tr = _current.get()
        if tr is None or not tr.enabled:
            yield
            return
        t0 = time.monotonic()
        try:
            yield
        finally:
            end = time.monotonic()
            tr.add_span(name, (end - t0) * 1000.0, end=end)
