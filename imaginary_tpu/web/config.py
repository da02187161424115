"""Server configuration (ref: ServerOptions, server.go:20-51).

Immutable after startup, threaded through every constructor — no globals
(matching the reference's config discipline, SURVEY.md section 5.6) — plus
the TPU-engine knobs that have no reference counterpart.
"""

from __future__ import annotations

import dataclasses
from typing import Optional
from urllib.parse import urlparse


@dataclasses.dataclass
class ServerOptions:
    port: int = 9000
    address: str = ""
    path_prefix: str = "/"
    burst: int = 100
    concurrency: int = 0
    http_cache_ttl: int = -1
    http_read_timeout: int = 60
    http_write_timeout: int = 60
    max_allowed_size: int = 0
    max_allowed_pixels: float = 18.0  # megapixels (ref: imaginary.go:36)
    cors: bool = False
    gzip: bool = False  # accepted for CLI parity; deprecated upstream
    auth_forwarding: bool = False
    enable_url_source: bool = False
    enable_placeholder: bool = False
    enable_url_signature: bool = False
    url_signature_key: str = ""
    api_key: str = ""
    mount: str = ""
    cert_file: str = ""
    key_file: str = ""
    # HTTP/2 over TLS (ALPN h2), matching Go net/http's default; served by
    # the nghttp2-backed terminator in web/http2.py. Auto-degrades to
    # http/1.1-only when libnghttp2 is absent.
    http2: bool = True
    authorization: str = ""
    placeholder: str = ""
    placeholder_status: int = 0
    forward_headers: tuple = ()
    placeholder_image: bytes = b""
    endpoints: tuple = ()  # disabled endpoint names (ref: Endpoints)
    allowed_origins: tuple = ()  # parsed urlparse results
    log_level: str = "info"
    return_size: bool = False
    cpus: int = 0  # host worker-thread cap, 0 = auto (role of -cpus/GOMAXPROCS)
    # serving processes sharing the port via SO_REUSEPORT (web/workers.py);
    # >1 makes every listener bind with reuse_port
    workers: int = 1
    # depth-based admission control: 503 new arrivals when the estimated
    # queueing delay (host backlog + device owed-work ledger) exceeds this
    # many ms; 0 disables (GCRA still bounds the RATE either way)
    max_queue_ms: float = 0.0
    # --- request lifecycle robustness (imaginary_tpu/deadline.py) ------------
    # End-to-end per-request deadline in seconds; ALSO the clamp ceiling
    # for the per-request X-Request-Timeout header. 0 = off (parity: the
    # serving path is byte-identical with deadlines disabled).
    request_timeout_s: float = 0.0
    # Resilient ?url=/watermark origin fetches (web/sources.py): bounded
    # retries with exponential backoff + full jitter on connect errors,
    # timeouts, 5xx and 429 (honoring Retry-After; other 4xx never retry).
    source_retries: int = 2
    # Per-ATTEMPT connect/read timeouts, split out of the 60 s total so a
    # black-holed origin fails the attempt in seconds and the retry (or
    # the request deadline) decides what happens next.
    source_connect_timeout_s: float = 5.0
    source_read_timeout_s: float = 30.0
    # --- memory-pressure resilience (imaginary_tpu/engine/pressure.py) -------
    # RSS ceiling in MB for the pressure governor. 0 = the whole
    # subsystem OFF (parity: no governor is built, no pressure check ever
    # runs, responses are byte-identical to the pre-pressure build).
    pressure_rss_mb: float = 0.0
    # Estimated device-HBM budget in MB fed by the executor's per-batch
    # wire-byte ledger; 0 skips the device signal.
    pressure_hbm_mb: float = 0.0
    # Rung thresholds as fractions of a limit: elevated at 75%, critical
    # at 90% (5-point hysteresis on the way down; see PressureConfig).
    pressure_elevated_frac: float = 0.75
    pressure_critical_frac: float = 0.90
    # Elevated/critical rung knobs: admitted device-batch wire-MB cap
    # (halved at critical), the megapixel size at which batch-class work
    # is forced to the host, and the fraction of --max-allowed-resolution
    # the critical pixel-admission clamp allows.
    pressure_batch_mb: float = 32.0
    pressure_oversize_mpix: float = 4.0
    pressure_pixel_frac: float = 0.25
    # --- output-integrity defense (imaginary_tpu/engine/integrity.py) --------
    # Master switch for SDC defense: golden-probe canaries (devhealth
    # re-admission probes run a real op-chain and compare against a
    # boot-time host reference), sampled cross-verification of production
    # device chunks (mismatch = corruption strike + transparent re-serve
    # from the verified copy), and poison-batch isolation (deterministic
    # non-OOM chunk failures bisect to convict the input into a
    # digest-keyed quarantine list). False = the whole subsystem OFF
    # (parity: no state object exists, no digest/sample/golden run ever
    # happens, responses byte-identical to the pre-integrity build).
    integrity: bool = False
    # Fraction of production device chunks recomputed + compared before
    # release (1/256 default; 1.0 verifies everything).
    integrity_sample: float = 1.0 / 256.0
    # Consecutive clean golden probes a corruption-struck device needs
    # before re-admission (crash strikes need one).
    integrity_clean_probes: int = 3
    # Poison quarantine list: entry TTL in seconds and size cap.
    integrity_poison_ttl: float = 300.0
    integrity_poison_cap: int = 256
    # --- fail-slow demotion (imaginary_tpu/engine/devhealth.py) --------------
    # Demote a device whose per-chunk latency EWMA exceeds this ratio x
    # the median of its PEERS' EWMAs to a `degraded` state that sheds its
    # dispatch share to healthy chips (readmission through the golden
    # probe; quarantine if it keeps slipping). 0 = off (parity: the EWMA
    # is recorded but never consulted — the pre-failslow behavior).
    failslow_ratio: float = 0.0
    # Latency samples a device (and each peer) needs before the
    # comparison may demote it — the cold-fleet hysteresis.
    failslow_min_samples: int = 8
    # Fraction of its dispatch rotation a degraded device keeps (0 =
    # full shed; recovery then rides the golden probe's timed runs).
    failslow_share: float = 0.0
    # --- fleet tier (imaginary_tpu/fleet/ + web/workers.py) ------------------
    # Byte budget in MB for the crash-safe shared result cache mapped by
    # every local worker (fleet/shmcache.py). 0 = the whole fleet data
    # plane OFF (parity: no file is created, no shm branch ever runs,
    # single-process responses are byte-identical to the pre-fleet
    # build). Under a supervisor the file is created once and workers
    # attach via IMAGINARY_TPU_FLEET_PATH.
    fleet_cache_mb: float = 0.0
    # Rolling-restart drain grace in seconds: after a SIGHUP roll's
    # replacement reports ready, the old worker stops accepting
    # (SIGUSR1) and gets this long to finish in-flight work before
    # SIGTERM starts its normal shutdown drain.
    fleet_roll_grace_s: float = 5.0
    # Fleet coherence (fleet/ownership.py + fleet/ipc.py): rendezvous
    # digest ownership with a local IPC forward hop, fleet-wide
    # singleflight via the shm claim table, and device-owner gating of
    # the chip group. Requires --fleet-cache-mb > 0 (the coordination
    # tables ride the shm file). False = OFF (parity: no ring, no
    # sockets, no claim traffic — responses byte-identical to the
    # incoherent build). Every owner-path fault fails OPEN to local
    # execution.
    fleet_coherence: bool = False
    # Forward-hop budget in ms: a non-owner gives the owner at most
    # this long (further clamped by the request deadline's remaining
    # budget) before failing open to local execution.
    fleet_hop_ms: float = 250.0
    # Fleet-wide QoS enforcement: per-tenant GCRA tat + in-flight share
    # columns in the shm qos table, so qos/limiter.py rates and
    # sched.py share caps hold across every worker a tenant sprays
    # connections over. Requires --fleet-cache-mb > 0. False = OFF
    # (parity: per-process enforcement exactly as before).
    fleet_qos: bool = False
    # Ingress slow-client hardening: close a connection whose request
    # read (headers or body) goes this many seconds without a byte —
    # the slowloris shape that would otherwise pin a worker slot
    # through a rolling drain. 0 = off (parity; aiohttp defaults).
    read_timeout_s: float = 0.0
    # Supervisor admin plane (obs/aggregate.py): a 127.0.0.1-only HTTP
    # port serving the fleet-merged /metrics (reset-corrected counter
    # sums across workers) and /fleetz (supervisor process table +
    # per-worker /health side by side). 0 = off (parity: no socket is
    # opened, no scrape loop exists). Only meaningful with --workers>1.
    fleet_admin_port: int = 0
    # --- multi-tenant QoS (imaginary_tpu/qos/) -------------------------------
    # Tenant table + scheduler/shed knobs: inline JSON (starts with '{')
    # or a file path; parsed once at assembly (qos/tenancy.load_policy).
    # "" = qos OFF (parity): single default tenant, the executor keeps
    # its FIFO queue, responses byte-identical to the pre-qos build.
    qos_config: str = ""
    # --- TPU engine knobs (no reference counterpart) -------------------------
    # default mirrors engine.executor.MAX_BATCH (kept literal here so this
    # config module stays import-light; test_engine pins the two equal)
    max_batch: int = 16
    # Continuous-batching formation cap in ms (engine/executor.py module
    # docstring), for the global collector and every lane
    batch_form_ms: float = 5.0
    # launched-but-unfetched device groups (the double-buffer depth: H2D of
    # N+1 overlaps compute of N and D2H of N-1; mirrors ExecutorConfig)
    max_inflight: int = 4
    # donate the batch operand to XLA so input HBM is reused for outputs
    # (ops/chain.py); rejection latches it off with a counted fallback
    donation: bool = True
    use_mesh: bool = False
    n_devices: Optional[int] = None
    spatial: int = 1  # spatial mesh axis (W-sharding for >=4K inputs)
    # pixel count at which a bucket's W axis shards across the spatial
    # mesh axis (default: 4K-class); mirrors ExecutorConfig — test_engine
    # pins the three definitions (here, CLI, executor) equal
    spatial_threshold_px: int = 3840 * 2160
    # Multi-chip sharded serving (engine/lanes.py; mirrors ExecutorConfig):
    # "off" is the single-lane parity path; "lanes" runs one continuous-
    # batching collector lane per healthy chip; "sharded"/"auto" also
    # stage big chunks batch-sharded over the healthy mesh.
    mesh_policy: str = "off"
    # Megapixel bar for the lane tier's oversize-single spatial route
    # (maps onto spatial_threshold_px; 0 keeps the pixel knob authoritative).
    spatial_mpix: float = 0.0
    lane_inflight: int = 2  # per-lane launched-but-undrained window
    # host SIMD spill under link saturation: None = auto (spill only when the
    # host has spare cores), True/False force it. Spilled pixels come from the
    # host interpreter (same dims, PSNR-equivalent but not bit-identical);
    # processed-image responses carry X-Imaginary-Backend: device|host so
    # operators can detect mixed-backend traffic (/info and error responses
    # never touch the executor and carry no such header).
    host_spill: Optional[bool] = None
    # Pin every host-executable plan to the host interpreter (measurement
    # override for bench_latency's host-path rows; see ExecutorConfig).
    force_host: bool = False
    # Per-thread native codec scratch-arena byte budget in MB
    # (native/codecs.cpp CodecArena): worker threads reuse decode/resize/
    # encode scratch at its high-water size; an over-budget thread drops
    # its arena after the call (counted as an eviction). 0 = unlimited.
    arena_mb: float = 0.0
    # Host-side DCT-domain shrink-on-load for SPILLED baseline-JPEG work
    # (engine/host_exec.py _run_dct): eligible dct-transport plans that
    # land on the host fold + IDCT at the shrunk size instead of full
    # decode + resample. Only reachable under --transport-dct; default on
    # (off restores the full-decode spill path byte-for-byte).
    host_dct_spill: bool = True
    # Hedged failover dispatch (ExecutorConfig.hedge_threshold_ms): after
    # this many ms stuck on the device path, launch a host-path twin and
    # take the first success. 0 = OFF (the parity default — the submit
    # path is byte-identical to the unhedged build). The budget caps
    # concurrent hedges as a fraction of in-flight device items so
    # hedging can never amplify an overload.
    hedge_threshold_ms: float = 0.0
    hedge_budget: float = 0.05
    prewarm: bool = False
    # compressed-domain ingest (codecs/jpeg_dct.py): host entropy decode
    # ships dequantized DCT coefficients; the device runs IDCT + color
    # convert, with shrink-on-load folded in the DCT domain. OFF by
    # default (parity: responses stay byte-identical when off).
    transport_dct: bool = False
    # compressed-domain egress: JPEG-bound dct-transport responses drain
    # quantized int16 coefficients (device forward DCT + quantization,
    # host entropy encode only). Rides on transport_dct; OFF by default
    # for the same byte-parity reason.
    transport_dct_egress: bool = False
    # entropy-decoder arm for the dct transport: "auto" picks the native
    # C kernel when built, the numpy lockstep decoder for restart-
    # segmented scans, else the pure-python oracle. "native"/"numpy"/
    # "python" pin an arm (native falls back to python when not built).
    dct_native: str = "auto"
    # --- content-addressed caching (imaginary_tpu/cache.py) ------------------
    # All tiers default OFF: with every knob at 0/False the serving path is
    # byte-identical to the uncached build (PARITY.md "Cache semantics").
    # encoded-result LRU byte budget in MB (serves repeat requests without
    # touching the executor; also enables strong ETag + If-None-Match 304)
    cache_result_mb: float = 0.0
    # decoded-frame LRU byte budget in MB (digest -> ndarray; different ops
    # on the same hot source skip decode)
    cache_frame_mb: float = 0.0
    # device-resident packed-frame cache byte budget in MB (HBM): staged
    # transport inputs pin on-device keyed by (digest, shrink, transport),
    # so a hot source pays ZERO H2D wire bytes on repeat requests. Shrinks
    # to half under elevated memory pressure, disables under critical
    # (cache.py apply_pressure).
    cache_device_mb: float = 0.0
    # singleflight: N concurrent identical (digest, plan) requests run the
    # pipeline once and fan the result out
    cache_coalesce: bool = False
    # TTL'd remote-source cache for ?url= fetches: seconds (0 = off) and
    # its own byte budget
    cache_source_ttl: float = 0.0
    cache_source_mb: float = 32.0
    # --- observability (imaginary_tpu/obs/) ---------------------------------
    # Per-request span tracing (X-Request-ID is ALWAYS assigned/echoed;
    # this gates span accumulation, Server-Timing, wide events, and the
    # slow-request exemplar ring). On by default; the off switch exists
    # for A-B overhead measurement (bench_obs.py) and emergencies.
    trace_enabled: bool = True
    # One structured JSON line per request (obs/events.py schema), written
    # to the access-log stream. Off by default.
    wide_events: bool = False
    # Tail-based sampling for the boring wide events: the interesting
    # tail (errors/sheds/504s/hedges/placement trouble/fenced/slow) is
    # ALWAYS emitted; boring successes roll this probability. 1.0 (the
    # default) keeps everything — byte-identical event volume to the
    # pre-sampling build (parity).
    wide_events_sample: float = 1.0
    # Per-route SLO objectives (obs/slo.py): inline JSON or a file
    # path, same convention as --qos-config. "" = OFF (parity: no
    # engine is built, /health //metrics //debugz carry no slo block).
    slo_config: str = ""
    # /debugz runtime introspection (task dump, executor/cache snapshots,
    # slow-request exemplars, one-shot profiler). Off by default: it is an
    # information surface an internet-facing deployment must opt into.
    enable_debug: bool = False
    # Per-tenant cost attribution + capacity plane (obs/cost.py). Off by
    # default (parity): no cost ring, no /topz, no capacity block, no
    # imaginary_tpu_cost_*/imaginary_tpu_utilization_* families.
    cost_attribution: bool = False
    # Top-K sketch width: at most this many tenant (and op) label values
    # stay distinct; everything past K folds into `other`.
    cost_topk: int = 20
    # Rollup windows over the 1s cost ring, ascending `<n>s|<n>m` CSV.
    cost_windows: str = "10s,1m,5m"
    # multi-host (DCN) fleet join: jax.distributed.initialize before meshing
    distributed: bool = False
    coordinator_address: str = ""
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # --- multi-host serving plane (fleet/multihost.py, fleet/router.py) ------
    # Peer supervisor admin bases: CSV/whitespace list or @file. "" = the
    # entire cross-host tier OFF (parity: no gossip thread, no peer table,
    # no route/spill surfaces, responses byte-identical to single-host).
    peers: str = ""
    # Route non-owned digests one HTTP hop to the rendezvous owner host.
    # Off = route only requests carrying an X-Imaginary-Route: route hint.
    router: bool = False
    # Stable host identity for rendezvous + fencing; "" = hostname.
    host_id: str = ""
    # Gossip poll cadence against each peer's /fleetz, seconds.
    peer_probe_interval: float = 2.0
    # Serving-boot jax.distributed mesh: join an N-host device mesh before
    # backend init so oversize spatial work shards across hosts. <=1 = off.
    mesh_hosts: int = 0

    def is_endpoint_enabled(self, path: str) -> bool:
        """Endpoint disabling by last path segment (ref: server.go:57-66)."""
        segment = path.rstrip("/").split("/")[-1]
        return segment not in self.endpoints


def parse_origins(value: str) -> tuple:
    """CSV of allowed origin URLs (ref: imaginary.go:303-326).

    The reference moves a wildcard prefix from the path into the host when
    the URL parser left `*.example.com` in the path portion (origins given
    without a scheme); accepting both spellings matters for parity with its
    documented examples.
    """
    origins = []
    for raw in value.split(","):
        raw = raw.strip()
        if not raw:
            continue
        u = urlparse(raw if "//" in raw else "//" + raw)
        host, path = u.netloc, u.path or ""
        if host == "" and path.startswith("*."):
            # "*.example.com/foo" parses host-less; recover host from path
            parts = path.split("/", 1)
            host = parts[0]
            path = "/" + parts[1] if len(parts) > 1 else ""
        if path:
            # ref: imaginary.go:314-321 — a trailing "*" turns the path
            # into a raw prefix ("/bucket*" matches "/bucket-a/.."), and
            # anything else gets a trailing "/" so "/assets" can never
            # leak "/assetsevil/.." through the prefix check
            if path.endswith("*"):
                path = path[:-1]
            elif not path.endswith("/"):
                path += "/"
        origins.append((host, path))
    return tuple(origins)


def parse_endpoints(value: str) -> tuple:
    """CSV of endpoint names to disable (ref: imaginary.go:328-337)."""
    return tuple(e.strip().lower() for e in value.split(",") if e.strip())


def parse_forward_headers(value: str) -> tuple:
    """CSV of header names to forward to origins (ref: imaginary.go:289-301)."""
    return tuple(h.strip() for h in value.split(",") if h.strip())
