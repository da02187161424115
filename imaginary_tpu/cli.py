"""CLI and bootstrap (ref: imaginary.go:20-229).

All 35 reference flags are accepted (spelled identically where argparse
allows), plus TPU-engine flags. Env overrides: PORT, URL_SIGNATURE_KEY, and
LOG_LEVEL (role of GOLANG_LOG; ref: imaginary.go:231-254).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from imaginary_tpu.version import Version
from imaginary_tpu.web.config import (
    ServerOptions,
    parse_endpoints,
    parse_forward_headers,
    parse_origins,
)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_bool(name: str) -> bool:
    return os.environ.get(name, "").lower() in ("1", "true", "on", "yes")


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, "") or default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="imaginary-tpu",
        description="TPU-native HTTP image processing microservice",
    )
    # ref flags (imaginary.go:20-55). Every flag reads its canonical
    # IMAGINARY_TPU_<FLAG> env override in its default (ITPU005 pins the
    # spelling; container deployments script knobs without a wrapper).
    # Historical env names (PORT, URL_SIGNATURE_KEY, LOG_LEVEL) still win
    # in options_from_args for back-compat.
    p.add_argument("-p", "--port", type=int,
                   default=_env_int("IMAGINARY_TPU_PORT", 9000), help="TCP port")
    p.add_argument("-a", "--addr", default=_env_str("IMAGINARY_TPU_ADDR", ""),
                   help="bind address")
    p.add_argument("--path-prefix",
                   default=_env_str("IMAGINARY_TPU_PATH_PREFIX", "/"),
                   help="URL path prefix")
    p.add_argument("--cors", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_CORS"), help="enable CORS")
    p.add_argument("--gzip", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_GZIP"),
                   help="deprecated no-op (parity)")
    p.add_argument("--key", default=_env_str("IMAGINARY_TPU_KEY", ""),
                   help="API key for authorization")
    p.add_argument("--mount", default=_env_str("IMAGINARY_TPU_MOUNT", ""),
                   help="local directory to serve images from")
    p.add_argument("--http-cache-ttl", type=int,
                   default=_env_int("IMAGINARY_TPU_HTTP_CACHE_TTL", -1),
                   help="cache TTL seconds (0=no-cache)")
    p.add_argument("--http-read-timeout", type=int,
                   default=_env_int("IMAGINARY_TPU_HTTP_READ_TIMEOUT", 60))
    p.add_argument("--http-write-timeout", type=int,
                   default=_env_int("IMAGINARY_TPU_HTTP_WRITE_TIMEOUT", 60))
    p.add_argument("--enable-url-source", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ENABLE_URL_SOURCE"),
                   help="allow GET ?url= fetches")
    p.add_argument("--enable-placeholder", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ENABLE_PLACEHOLDER"),
                   help="placeholder on errors")
    p.add_argument("--enable-auth-forwarding", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ENABLE_AUTH_FORWARDING"))
    p.add_argument("--enable-url-signature", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ENABLE_URL_SIGNATURE"))
    p.add_argument("--url-signature-key",
                   default=_env_str("IMAGINARY_TPU_URL_SIGNATURE_KEY", ""))
    p.add_argument("--allowed-origins",
                   default=_env_str("IMAGINARY_TPU_ALLOWED_ORIGINS", ""),
                   help="CSV of allowed origin URLs")
    p.add_argument("--max-allowed-size", type=int,
                   default=_env_int("IMAGINARY_TPU_MAX_ALLOWED_SIZE", 0),
                   help="max source bytes")
    p.add_argument("--max-allowed-resolution", type=float,
                   default=_env_float("IMAGINARY_TPU_MAX_ALLOWED_RESOLUTION", 18.0),
                   help="max megapixels")
    p.add_argument("--certfile", default=_env_str("IMAGINARY_TPU_CERTFILE", ""))
    p.add_argument("--keyfile", default=_env_str("IMAGINARY_TPU_KEYFILE", ""))
    p.add_argument("--require-device", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_REQUIRE_DEVICE"),
                   help="exit 2 at boot unless JAX's first device is an "
                        "accelerator (not the CPU backend)")
    p.add_argument("--disable-http2", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_DISABLE_HTTP2"),
                   help="serve http/1.1 only over TLS (h2 is on by default, like the reference)")
    p.add_argument("--authorization",
                   default=_env_str("IMAGINARY_TPU_AUTHORIZATION", ""),
                   help="fixed Authorization header for origins")
    p.add_argument("--forward-headers",
                   default=_env_str("IMAGINARY_TPU_FORWARD_HEADERS", ""),
                   help="CSV of headers to forward")
    p.add_argument("--placeholder",
                   default=_env_str("IMAGINARY_TPU_PLACEHOLDER", ""),
                   help="placeholder image path")
    p.add_argument("--placeholder-status", type=int,
                   default=_env_int("IMAGINARY_TPU_PLACEHOLDER_STATUS", 0))
    p.add_argument("--concurrency", type=int,
                   default=_env_int("IMAGINARY_TPU_CONCURRENCY", 0),
                   help="rate limit (req/sec)")
    p.add_argument("--burst", type=int,
                   default=_env_int("IMAGINARY_TPU_BURST", 100),
                   help="rate limit burst")
    p.add_argument("--mrelease", type=int,
                   default=_env_int("IMAGINARY_TPU_MRELEASE", 30),
                   help="memory release interval seconds")
    p.add_argument("--cpus", type=int,
                   default=_env_int("IMAGINARY_TPU_CPUS", 0),
                   help="worker thread cap (0=auto)")
    p.add_argument("--log-level",
                   default=_env_str("IMAGINARY_TPU_LOG_LEVEL", "info"),
                   choices=["debug", "info", "warning", "error"])
    p.add_argument("--return-size", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_RETURN_SIZE"),
                   help="Image-Width/Height headers")
    p.add_argument("--disable-endpoints",
                   default=_env_str("IMAGINARY_TPU_DISABLE_ENDPOINTS", ""),
                   help="CSV of endpoints to disable")
    p.add_argument("--version", action="store_true")
    # TPU engine flags (no reference counterpart)
    p.add_argument("--max-queue-ms", type=float,
                   default=_env_float("IMAGINARY_TPU_MAX_QUEUE_MS", 0.0),
                   help="shed load (503) when estimated queueing delay "
                        "exceeds this; 0 disables")
    # request lifecycle robustness (imaginary_tpu/deadline.py +
    # web/sources.py retry policy); --request-timeout defaults OFF so the
    # serving path stays byte-identical to the reference build
    p.add_argument("--request-timeout", type=float,
                   default=_env_float("IMAGINARY_TPU_REQUEST_TIMEOUT", 0.0),
                   help="end-to-end per-request deadline in seconds, "
                        "enforced at every hop (admission, fetch, queue, "
                        "execute, encode); also the clamp ceiling for the "
                        "X-Request-Timeout header; 0 disables")
    p.add_argument("--source-retries", type=int,
                   default=_env_int("IMAGINARY_TPU_SOURCE_RETRIES", 2),
                   help="retry budget for remote ?url=/watermark fetches "
                        "(connect errors, timeouts, 5xx, 429; exponential "
                        "backoff + full jitter, honors Retry-After)")
    p.add_argument("--source-connect-timeout", type=float,
                   default=_env_float("IMAGINARY_TPU_SOURCE_CONNECT_TIMEOUT", 5.0),
                   help="per-attempt origin connect timeout in seconds")
    p.add_argument("--source-read-timeout", type=float,
                   default=_env_float("IMAGINARY_TPU_SOURCE_READ_TIMEOUT", 30.0),
                   help="per-attempt origin total read timeout in seconds")
    # memory-pressure resilience (imaginary_tpu/engine/pressure.py):
    # governor + brownout ladder + OOM bisect-retry; defaults OFF
    # (--pressure-rss-mb 0 builds no governor — byte parity)
    p.add_argument("--pressure-rss-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_RSS_MB", 0.0),
                   help="RSS ceiling in MB for the memory-pressure "
                        "governor: elevated at 75%%, critical at 90%% "
                        "(see --pressure-*-frac); drives the brownout "
                        "ladder (cache shrink, oversize-to-host, batch "
                        "shed, pixel clamp); 0 disables the subsystem")
    p.add_argument("--pressure-hbm-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_HBM_MB", 0.0),
                   help="estimated device-HBM budget in MB (fed by the "
                        "executor's per-batch wire-byte ledger); 0 skips "
                        "the device signal")
    p.add_argument("--pressure-elevated-frac", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_ELEVATED_FRAC",
                                      0.75),
                   help="fraction of a limit at which pressure reads "
                        "'elevated'")
    p.add_argument("--pressure-critical-frac", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_CRITICAL_FRAC",
                                      0.90),
                   help="fraction of a limit at which pressure reads "
                        "'critical'")
    p.add_argument("--pressure-batch-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_BATCH_MB", 32.0),
                   help="admitted device-batch wire-MB cap under pressure "
                        "(halved at critical); 0 never caps")
    p.add_argument("--pressure-oversize-mpix", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_OVERSIZE_MPIX",
                                      4.0),
                   help="source megapixels at which batch-class work is "
                        "forced to the host interpreter under elevated "
                        "pressure")
    p.add_argument("--pressure-pixel-frac", type=float,
                   default=_env_float("IMAGINARY_TPU_PRESSURE_PIXEL_FRAC",
                                      0.25),
                   help="fraction of --max-allowed-resolution the critical "
                        "rung's pixel-admission clamp allows (source and "
                        "requested output dims)")
    # output-integrity defense (imaginary_tpu/engine/integrity.py) + fail-slow
    # demotion (engine/devhealth.py); defaults OFF (--integrity absent and
    # --failslow-ratio 0 build no state — byte parity with the pre-defense
    # serving path)
    p.add_argument("--integrity", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_INTEGRITY"),
                   help="arm the output-integrity defense: golden-probe "
                        "canaries on device re-admission, sampled "
                        "cross-verification of device batches (mismatch = "
                        "corruption strike + transparent re-serve from the "
                        "verified copy), and poison-batch isolation")
    p.add_argument("--integrity-sample", type=float,
                   default=_env_float("IMAGINARY_TPU_INTEGRITY_SAMPLE",
                                      1.0 / 256.0),
                   help="fraction of production device batches recomputed "
                        "on the host (or a peer chip) and compared before "
                        "the response is released (default 1/256; 1.0 "
                        "verifies every batch)")
    p.add_argument("--integrity-clean-probes", type=int,
                   default=_env_int("IMAGINARY_TPU_INTEGRITY_CLEAN_PROBES", 3),
                   help="consecutive clean golden probes a corruption-"
                        "struck device must pass before re-admission")
    p.add_argument("--integrity-poison-ttl", type=float,
                   default=_env_float("IMAGINARY_TPU_INTEGRITY_POISON_TTL",
                                      300.0),
                   help="seconds a convicted poison input stays in the "
                        "digest quarantine list (routed host/422 instead "
                        "of re-poisoning device batches)")
    p.add_argument("--integrity-poison-cap", type=int,
                   default=_env_int("IMAGINARY_TPU_INTEGRITY_POISON_CAP", 256),
                   help="max poison quarantine entries (oldest evicted)")
    p.add_argument("--failslow-ratio", type=float,
                   default=_env_float("IMAGINARY_TPU_FAILSLOW_RATIO", 0.0),
                   help="demote a device to `degraded` when its per-chunk "
                        "latency EWMA exceeds this ratio x the median of "
                        "its peers' EWMAs (sheds its dispatch share to "
                        "healthy chips; quarantines if it keeps slipping; "
                        "golden probe re-admits); 0 disables")
    p.add_argument("--failslow-min-samples", type=int,
                   default=_env_int("IMAGINARY_TPU_FAILSLOW_MIN_SAMPLES", 8),
                   help="latency samples a device and its peers each need "
                        "before fail-slow demotion may trigger")
    p.add_argument("--failslow-share", type=float,
                   default=_env_float("IMAGINARY_TPU_FAILSLOW_SHARE", 0.0),
                   help="fraction of its dispatch rotation a degraded "
                        "device keeps (0 = full shed)")
    # multi-tenant QoS (imaginary_tpu/qos/): tenant table + priority
    # classes + per-tenant rates/shares; defaults OFF (single default
    # tenant, FIFO executor intake, byte-identical responses)
    p.add_argument("--qos-config",
                   default=os.environ.get("IMAGINARY_TPU_QOS_CONFIG", ""),
                   help="multi-tenant QoS policy: inline JSON (starts "
                        "with '{') or a file path; tenants carry a class "
                        "(interactive|standard|batch), rate/burst "
                        "overrides, and a max queue share (see README "
                        "Multi-tenant QoS); empty disables qos")
    p.add_argument("--workers", type=int,
                   default=_env_int("IMAGINARY_TPU_WORKERS", 1),
                   help="serving processes on one port via SO_REUSEPORT "
                        "(0 = one per CPU core); worker 0 owns the device, "
                        "the rest serve on the host backend")
    # fleet tier (imaginary_tpu/fleet/): crash-safe shared result cache
    # + worker fencing + rolling restarts; defaults OFF (no shm file is
    # created, byte parity with the single-process build)
    p.add_argument("--fleet-cache-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_FLEET_CACHE_MB", 0.0),
                   help="byte budget in MB for the crash-safe mmap result "
                        "cache shared by all local workers (sealed "
                        "checksummed entries, torn-write detection, "
                        "worker fencing via generation epochs); 0 "
                        "disables the fleet data plane")
    p.add_argument("--fleet-roll-grace", type=float,
                   default=_env_float("IMAGINARY_TPU_FLEET_ROLL_GRACE", 5.0),
                   help="SIGHUP rolling restart: seconds an old worker "
                        "keeps finishing in-flight work after its "
                        "replacement reports ready and it stops "
                        "accepting, before SIGTERM starts its normal "
                        "shutdown drain")
    p.add_argument("--fleet-coherence", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_FLEET_COHERENCE"),
                   help="arm the fleet data plane's coherence layer: "
                        "rendezvous digest ownership with a local IPC "
                        "forward hop, fleet-wide singleflight via the "
                        "shm claim table, and device-owner gating; "
                        "requires --fleet-cache-mb > 0; every owner-"
                        "path fault fails open to local execution")
    p.add_argument("--fleet-hop-ms", type=float,
                   default=_env_float("IMAGINARY_TPU_FLEET_HOP_MS", 250.0),
                   help="forward-hop budget in ms a non-owner gives the "
                        "digest owner (clamped by the request "
                        "deadline's remaining budget) before failing "
                        "open to local execution")
    p.add_argument("--fleet-qos", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_FLEET_QOS"),
                   help="enforce per-tenant GCRA rates and in-queue "
                        "share caps fleet-wide via the shm qos table "
                        "(closes the spray-across-workers rate-limit "
                        "evasion); requires --fleet-cache-mb > 0; "
                        "shared-table faults degrade to per-worker "
                        "enforcement (fail-open)")
    p.add_argument("--fleet-admin-port", type=int,
                   default=_env_int("IMAGINARY_TPU_FLEET_ADMIN_PORT", 0),
                   help="supervisor admin plane on 127.0.0.1: /metrics "
                        "(fleet-merged strict exposition with monotonic "
                        "counter-reset correction across respawns) and "
                        "/fleetz (per-worker epoch/restarts/liveness + "
                        "health side by side); 0 disables (parity); "
                        "meaningful only with --workers > 1")
    p.add_argument("--read-timeout", type=float,
                   default=_env_float("IMAGINARY_TPU_READ_TIMEOUT", 0.0),
                   help="close a connection whose request read (headers "
                        "or body) goes this many seconds without a byte "
                        "— slow-client/slowloris hardening so a stalled "
                        "read cannot pin a worker slot through a rolling "
                        "drain; 0 disables (parity)")
    p.add_argument("--max-batch", type=int,
                   default=_env_int("IMAGINARY_TPU_MAX_BATCH", 16),
                   help="micro-batch size cap")
    # continuous batching (engine/executor.py): formation capped at
    # single-digit ms, chunks launch immediately and overlap in flight
    p.add_argument("--batch-form-ms", type=float,
                   default=_env_float("IMAGINARY_TPU_BATCH_FORM_MS", 5.0),
                   help="max milliseconds an item may wait for its chunk "
                        "to close (the batch-formation latency cap, for "
                        "the global collector and every lane)")
    p.add_argument("--max-inflight", type=int,
                   default=_env_int("IMAGINARY_TPU_MAX_INFLIGHT", 4),
                   help="device groups launched but not yet fetched (the "
                        "H2D/compute/D2H double-buffer depth; backpressure "
                        "beyond it)")
    p.add_argument("--donation",
                   default=_env_str("IMAGINARY_TPU_DONATION", "on"),
                   choices=["on", "off"],
                   help="donate the batch operand to XLA (donate_argnums) "
                        "so input HBM is reused for outputs; a backend "
                        "that rejects donation falls back undonated and "
                        "latches it off")
    p.add_argument("--use-mesh", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_USE_MESH"),
                   help="shard batches over the device mesh")
    p.add_argument("--devices", type=int,
                   default=_env_int("IMAGINARY_TPU_DEVICES", 0),
                   help="device count (0=all)")
    p.add_argument("--spatial", type=int,
                   default=_env_int("IMAGINARY_TPU_SPATIAL", 1),
                   help="spatial mesh axis size (W-shard huge images across chips)")
    p.add_argument("--spatial-threshold-px", type=int,
                   default=_env_int("IMAGINARY_TPU_SPATIAL_THRESHOLD_PX", 3840 * 2160),
                   help="bucket pixel count at which W-sharding engages")
    p.add_argument("--mesh-policy",
                   default=_env_str("IMAGINARY_TPU_MESH_POLICY", "off"),
                   choices=["off", "lanes", "sharded", "auto"],
                   help="multi-chip serving (engine/lanes.py): 'lanes' "
                        "gives every healthy chip its own continuous-"
                        "batching collector lane; 'sharded'/'auto' "
                        "additionally stage big chunks batch-sharded "
                        "over the healthy mesh; 'off' (default) is the "
                        "single-lane parity path")
    p.add_argument("--spatial-mpix", type=float,
                   default=_env_float("IMAGINARY_TPU_SPATIAL_MPIX", 0.0),
                   help="megapixel bar for the lane tier's oversize-"
                        "single spatial route (maps onto "
                        "--spatial-threshold-px; 0 keeps the pixel knob "
                        "authoritative)")
    p.add_argument("--lane-inflight", type=int,
                   default=_env_int("IMAGINARY_TPU_LANE_INFLIGHT", 2),
                   help="per-lane launched-but-undrained group window "
                        "(the lane's only backpressure)")
    p.add_argument("--host-spill",
                   default=_env_str("IMAGINARY_TPU_HOST_SPILL", "auto"),
                   choices=["auto", "on", "off"],
                   help="spill to host SIMD when the device link saturates "
                        "(auto = enabled, governed by the measured cost "
                        "model; spilled responses carry "
                        "X-Imaginary-Backend: host)")
    p.add_argument("--force-host", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_FORCE_HOST"),
                   help="pin every host-executable plan to the host SIMD "
                        "interpreter (measurement override; device-only "
                        "plans still ride the chip)")
    p.add_argument("--arena-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_ARENA_MB", 0.0),
                   help="per-thread native codec scratch-arena budget in "
                        "MB: worker threads reuse decode/resize/encode "
                        "scratch at its high-water size, an over-budget "
                        "thread drops its arena after the call (0 = "
                        "unlimited)")
    p.add_argument("--host-dct-spill",
                   default=_env_str("IMAGINARY_TPU_HOST_DCT_SPILL", "on"),
                   choices=["on", "off"],
                   help="DCT-domain shrink-on-load for spilled baseline-"
                        "JPEG work: eligible dct-transport plans that land "
                        "on the host fold + IDCT at the shrunk size "
                        "instead of full decode + resample (only reachable "
                        "under --transport-dct; off restores the full-"
                        "decode spill path)")
    # hedged failover dispatch (engine/executor.py): default OFF so the
    # device path stays byte-identical to the unhedged build
    p.add_argument("--hedge-threshold-ms", type=float,
                   default=_env_float("IMAGINARY_TPU_HEDGE_THRESHOLD_MS", 0.0),
                   help="launch a speculative host-path twin when a "
                        "device request has waited this long (floored at "
                        "50 ms and at 4x the item's estimated device "
                        "service time); first success wins, the loser is "
                        "cancelled; 0 disables hedging")
    p.add_argument("--hedge-budget", type=float,
                   default=_env_float("IMAGINARY_TPU_HEDGE_BUDGET", 0.05),
                   help="max concurrent hedges as a fraction of in-flight "
                        "device items (floor 1); bounds how much duplicate "
                        "host work hedging may add under overload")
    p.add_argument("--prewarm", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_PREWARM"),
                   help="pre-compile common op chains")
    p.add_argument("--transport-dct", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_TRANSPORT_DCT"),
                   help="serve baseline JPEG requests (4:2:0/4:2:2/4:4:4/"
                        "grayscale) over the compressed-domain transport: "
                        "host entropy decode ships DCT coefficients, the "
                        "device runs the IDCT, and shrink-on-load folds in "
                        "the DCT domain")
    p.add_argument("--transport-dct-egress", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_TRANSPORT_DCT_EGRESS"),
                   help="drain JPEG-bound dct-transport responses as "
                        "quantized DCT coefficients: the device runs the "
                        "forward DCT + quantization and the host only "
                        "entropy-codes (requires --transport-dct)")
    p.add_argument("--dct-native", choices=("auto", "native", "numpy", "python"),
                   default=os.environ.get("IMAGINARY_TPU_DCT_NATIVE", "auto"),
                   help="entropy-decoder arm for the dct transport: the "
                        "native C kernel, the vectorized numpy bit-plane "
                        "decoder, the pure-python oracle, or auto (native "
                        "if built, numpy for restart-segmented scans, else "
                        "python)")
    # content-addressed caching (imaginary_tpu/cache.py); every knob also
    # honors an IMAGINARY_TPU_CACHE_* env override and defaults OFF so the
    # uncached serving path stays byte-identical to the reference build
    p.add_argument("--cache-result-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_CACHE_RESULT_MB", 0.0),
                   help="encoded-result LRU byte budget in MB (0=off); "
                        "enables strong ETag + If-None-Match 304")
    p.add_argument("--cache-frame-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_CACHE_FRAME_MB", 0.0),
                   help="decoded-frame LRU byte budget in MB (0=off)")
    p.add_argument("--cache-device-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_CACHE_DEVICE_MB", 0.0),
                   help="device-resident packed-frame cache byte budget in "
                        "MB of HBM (0=off); hot sources skip the H2D "
                        "transfer entirely on repeat requests")
    p.add_argument("--cache-coalesce", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_CACHE_COALESCE"),
                   help="coalesce concurrent identical requests onto one "
                        "pipeline run")
    p.add_argument("--cache-source-ttl", type=float,
                   default=_env_float("IMAGINARY_TPU_CACHE_SOURCE_TTL", 0.0),
                   help="TTL seconds for the remote ?url= source cache (0=off)")
    p.add_argument("--cache-source-mb", type=float,
                   default=_env_float("IMAGINARY_TPU_CACHE_SOURCE_MB", 32.0),
                   help="remote-source cache byte budget in MB")
    # observability (imaginary_tpu/obs/): tracing defaults ON (every
    # response carries X-Request-ID + Server-Timing); /debugz and wide
    # events default OFF
    # IMAGINARY_TPU_TRACE=0 and IMAGINARY_TPU_DEBUG=1 predate the canonical
    # flag<->env spelling and stay honored next to it (renaming a deployed
    # env var breaks fleets for tidiness)
    p.add_argument("--disable-tracing", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_DISABLE_TRACING")
                   or os.environ.get("IMAGINARY_TPU_TRACE", "").lower()
                   in ("0", "off", "false"),
                   help="disable per-request span tracing / Server-Timing / "
                        "wide events (X-Request-ID is still assigned)")
    p.add_argument("--wide-events", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_WIDE_EVENTS"),
                   help="emit one structured JSON line per request "
                        "(op, plan digest, cache outcome, placement, spans)")
    p.add_argument("--wide-events-sample", type=float,
                   default=_env_float("IMAGINARY_TPU_WIDE_EVENTS_SAMPLE", 1.0),
                   help="tail-based sampling probability for BORING wide "
                        "events; errors/sheds/504s/hedges/placement "
                        "trouble/fenced publishes/slow requests are always "
                        "emitted regardless; 1.0 (default) keeps everything")
    p.add_argument("--slo-config",
                   default=os.environ.get("IMAGINARY_TPU_SLO_CONFIG", ""),
                   help="per-route SLO objectives: inline JSON (starts "
                        "with '{') or a file path mapping route -> "
                        "{latency_ms, latency_target, availability} with "
                        "'*' as catch-all; burn rates over 5m/1h windows "
                        "surface in /health, /metrics and /debugz; empty "
                        "disables (parity)")
    p.add_argument("--enable-debug", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ENABLE_DEBUG")
                   or _env_bool("IMAGINARY_TPU_DEBUG"),
                   help="serve /debugz runtime introspection (task dump, "
                        "executor/cache snapshots, slow-request exemplars, "
                        "one-shot profiler trigger)")
    p.add_argument("--cost-attribution", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_COST_ATTRIBUTION"),
                   help="per-tenant cost attribution + capacity plane "
                        "(obs/cost.py): cost vectors booked per tenant x "
                        "qos_class x route x op, a capacity block in "
                        "/health, /topz top-K consumers, live bound_by "
                        "advisor, imaginary_tpu_cost_*/_utilization_* "
                        "metrics; off = none of it exists (parity)")
    p.add_argument("--cost-topk", type=int,
                   default=_env_int("IMAGINARY_TPU_COST_TOPK", 20),
                   help="cost-attribution sketch width: at most K distinct "
                        "tenant/op label values; the rest fold into 'other'")
    p.add_argument("--cost-windows",
                   default=_env_str("IMAGINARY_TPU_COST_WINDOWS",
                                    "10s,1m,5m"),
                   help="cost rollup windows over the 1s ring: ascending "
                        "CSV of <n>s/<n>m spans (max 6, each <= 1h)")
    p.add_argument("--distributed", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_DISTRIBUTED"),
                   help="join a multi-host fleet (jax.distributed.initialize before meshing)")
    p.add_argument("--coordinator-address",
                   default=_env_str("IMAGINARY_TPU_COORDINATOR_ADDRESS", ""),
                   help="host:port of process 0 (auto-discovered on TPU pods)")
    p.add_argument("--num-processes", type=int,
                   default=_env_int("IMAGINARY_TPU_NUM_PROCESSES", 0),
                   help="total process count (auto-discovered on TPU pods)")
    p.add_argument("--process-id", type=int,
                   default=_env_int("IMAGINARY_TPU_PROCESS_ID", -1),
                   help="this process's index (auto-discovered on TPU pods)")
    p.add_argument("--peers",
                   default=_env_str("IMAGINARY_TPU_PEERS", ""),
                   help="peer supervisor admin bases (http://host:admin-port)"
                        " as a CSV/whitespace list or @file; arms the "
                        "multi-host plane: host identity, /fleetz gossip, "
                        "digest routing and pressure spillover; empty = "
                        "entirely off (parity)")
    p.add_argument("--router", action="store_true",
                   default=_env_bool("IMAGINARY_TPU_ROUTER"),
                   help="route non-owned digests one HTTP hop to the "
                        "rendezvous owner host (requires --peers); without "
                        "it only requests carrying an X-Imaginary-Route: "
                        "route hint are routed")
    p.add_argument("--host-id",
                   default=_env_str("IMAGINARY_TPU_HOST_ID", ""),
                   help="stable host identity for cross-host rendezvous "
                        "and fencing (default: hostname)")
    p.add_argument("--peer-probe-interval", type=float,
                   default=_env_float("IMAGINARY_TPU_PEER_PROBE_INTERVAL",
                                      2.0),
                   help="gossip poll cadence against each peer's /fleetz, "
                        "seconds")
    p.add_argument("--mesh-hosts", type=int,
                   default=_env_int("IMAGINARY_TPU_MESH_HOSTS", 0),
                   help="join an N-host jax.distributed device mesh at "
                        "serving boot (requires --coordinator-address and "
                        "--process-id, single-worker only) so oversize "
                        "spatial work can shard across hosts; <=1 = off")
    return p


def _resolve_workers(n: int) -> int:
    if n == 0:  # auto: one per core
        return max(1, os.cpu_count() or 1)
    return max(1, n)


def options_from_args(args) -> ServerOptions:
    port = args.port
    if os.environ.get("PORT"):
        try:
            port = int(os.environ["PORT"])
        except ValueError:
            pass
    signature_key = args.url_signature_key or os.environ.get("URL_SIGNATURE_KEY", "")
    log_level = os.environ.get("LOG_LEVEL", args.log_level)

    placeholder_image = b""
    if args.placeholder:
        with open(args.placeholder, "rb") as f:
            placeholder_image = f.read()
        from imaginary_tpu.imgtype import ImageType, determine_image_type

        if determine_image_type(placeholder_image) is ImageType.UNKNOWN:
            raise SystemExit("placeholder image is not a valid image")

    if args.enable_url_signature and len(signature_key) < 32:
        raise SystemExit("URL signature key must be at least 32 characters long")
    if args.mount and not os.path.isdir(args.mount):
        raise SystemExit(f"mount directory does not exist: {args.mount}")
    if args.http_cache_ttl < -1 or args.http_cache_ttl > 31556926:
        raise SystemExit("The -http-cache-ttl flag only accepts a value from 0 to 31556926")
    if (args.fleet_coherence or args.fleet_qos) and args.fleet_cache_mb <= 0:
        # the coordination tables (claims, qos) ride the shm cache file;
        # refusing at boot beats silently serving without coherence
        raise SystemExit(
            "--fleet-coherence/--fleet-qos require --fleet-cache-mb > 0 "
            "(the ownership/claim/qos tables live in the shared cache file)")
    if args.qos_config:
        # validate at boot, like the placeholder/signature checks above:
        # a typo'd tenant table must refuse to start, not silently serve
        # with no isolation (create_app parses it again at assembly)
        from imaginary_tpu.qos.tenancy import load_policy

        try:
            load_policy(args.qos_config)
        except ValueError as e:
            raise SystemExit(str(e)) from None
    if args.slo_config:
        # same boot-time discipline as --qos-config: a typo'd objective
        # table must refuse to start, not silently track nothing
        from imaginary_tpu.obs.slo import load_config as load_slo_config

        try:
            load_slo_config(args.slo_config)
        except ValueError as e:
            raise SystemExit(str(e)) from None
    if args.router and not args.peers:
        # a router with no peer table can never route; refusing at boot
        # beats silently serving single-host behind a lying flag
        raise SystemExit("--router requires --peers (the routing ring is "
                         "built from the gossiped peer table)")
    if args.peers:
        # boot-time discipline as for --qos-config: an unreadable @file
        # or empty list must refuse to start, not gossip into the void
        from imaginary_tpu.fleet import multihost

        try:
            if not multihost.parse_peers(args.peers):
                raise ValueError("--peers resolved to an empty peer list")
        except ValueError as e:
            raise SystemExit(str(e)) from None
    if args.mesh_hosts > 1:
        if not args.coordinator_address:
            raise SystemExit(
                "--mesh-hosts requires --coordinator-address (process 0 of "
                "the mesh)")
        if args.process_id < 0:
            raise SystemExit("--mesh-hosts requires --process-id")
        if _resolve_workers(args.workers) != 1:
            # each mesh process owns its host's chips outright; a local
            # worker fleet would fight the mesh for the same devices
            raise SystemExit("--mesh-hosts requires --workers 1")
    if args.cost_attribution:
        # same boot-time discipline: a typo'd window spec must refuse to
        # start, not silently attribute into malformed windows
        from imaginary_tpu.obs.cost import parse_windows

        try:
            parse_windows(args.cost_windows)
        except ValueError as e:
            raise SystemExit(str(e)) from None

    return ServerOptions(
        port=port,
        address=args.addr,
        path_prefix=args.path_prefix,
        cors=args.cors,
        gzip=args.gzip,
        api_key=args.key,
        mount=args.mount,
        http_cache_ttl=args.http_cache_ttl,
        http_read_timeout=args.http_read_timeout,
        http_write_timeout=args.http_write_timeout,
        enable_url_source=args.enable_url_source,
        enable_placeholder=args.enable_placeholder,
        auth_forwarding=args.enable_auth_forwarding,
        enable_url_signature=args.enable_url_signature,
        url_signature_key=signature_key,
        allowed_origins=parse_origins(args.allowed_origins),
        max_allowed_size=args.max_allowed_size,
        max_allowed_pixels=args.max_allowed_resolution,
        cert_file=args.certfile,
        key_file=args.keyfile,
        http2=not args.disable_http2,
        authorization=args.authorization,
        forward_headers=parse_forward_headers(args.forward_headers),
        placeholder=args.placeholder,
        placeholder_image=placeholder_image,
        placeholder_status=args.placeholder_status,
        concurrency=args.concurrency,
        burst=args.burst,
        log_level=log_level,
        return_size=args.return_size,
        cpus=args.cpus,
        endpoints=parse_endpoints(args.disable_endpoints),
        workers=_resolve_workers(args.workers),
        fleet_cache_mb=max(0.0, args.fleet_cache_mb),
        fleet_roll_grace_s=max(0.0, args.fleet_roll_grace),
        fleet_coherence=args.fleet_coherence,
        fleet_hop_ms=max(1.0, args.fleet_hop_ms),
        fleet_qos=args.fleet_qos,
        fleet_admin_port=max(0, args.fleet_admin_port),
        read_timeout_s=max(0.0, args.read_timeout),
        max_queue_ms=max(0.0, args.max_queue_ms),
        request_timeout_s=max(0.0, args.request_timeout),
        source_retries=max(0, args.source_retries),
        source_connect_timeout_s=max(0.001, args.source_connect_timeout),
        source_read_timeout_s=max(0.001, args.source_read_timeout),
        qos_config=args.qos_config,
        integrity=args.integrity,
        integrity_sample=min(1.0, max(0.0, args.integrity_sample)),
        integrity_clean_probes=max(1, args.integrity_clean_probes),
        integrity_poison_ttl=max(0.0, args.integrity_poison_ttl),
        integrity_poison_cap=max(1, args.integrity_poison_cap),
        failslow_ratio=max(0.0, args.failslow_ratio),
        failslow_min_samples=max(1, args.failslow_min_samples),
        failslow_share=min(1.0, max(0.0, args.failslow_share)),
        pressure_rss_mb=max(0.0, args.pressure_rss_mb),
        pressure_hbm_mb=max(0.0, args.pressure_hbm_mb),
        pressure_elevated_frac=min(1.0, max(0.01, args.pressure_elevated_frac)),
        pressure_critical_frac=min(1.0, max(0.01, args.pressure_critical_frac)),
        pressure_batch_mb=max(0.0, args.pressure_batch_mb),
        pressure_oversize_mpix=max(0.0, args.pressure_oversize_mpix),
        pressure_pixel_frac=min(1.0, max(0.01, args.pressure_pixel_frac)),
        max_batch=args.max_batch,
        batch_form_ms=max(0.0, args.batch_form_ms),
        max_inflight=max(1, args.max_inflight),
        donation=args.donation != "off",
        use_mesh=args.use_mesh,
        n_devices=args.devices or None,
        spatial=max(1, args.spatial),
        spatial_threshold_px=max(1, args.spatial_threshold_px),
        mesh_policy=args.mesh_policy,
        spatial_mpix=max(0.0, args.spatial_mpix),
        lane_inflight=max(1, args.lane_inflight),
        host_spill={"auto": None, "on": True, "off": False}[args.host_spill],
        force_host=args.force_host,
        arena_mb=max(0.0, args.arena_mb),
        host_dct_spill=args.host_dct_spill != "off",
        hedge_threshold_ms=max(0.0, args.hedge_threshold_ms),
        hedge_budget=min(1.0, max(0.0, args.hedge_budget)),
        prewarm=args.prewarm,
        transport_dct=args.transport_dct,
        transport_dct_egress=args.transport_dct_egress,
        dct_native=args.dct_native,
        cache_result_mb=max(0.0, args.cache_result_mb),
        cache_frame_mb=max(0.0, args.cache_frame_mb),
        cache_device_mb=max(0.0, args.cache_device_mb),
        cache_coalesce=args.cache_coalesce,
        cache_source_ttl=max(0.0, args.cache_source_ttl),
        cache_source_mb=max(0.0, args.cache_source_mb),
        trace_enabled=not args.disable_tracing,
        wide_events=args.wide_events,
        wide_events_sample=min(1.0, max(0.0, args.wide_events_sample)),
        slo_config=args.slo_config,
        enable_debug=args.enable_debug,
        cost_attribution=args.cost_attribution,
        cost_topk=max(1, args.cost_topk),
        cost_windows=args.cost_windows,
        distributed=args.distributed,
        coordinator_address=args.coordinator_address,
        num_processes=args.num_processes or None,
        process_id=args.process_id if args.process_id >= 0 else None,
        peers=args.peers,
        router=args.router,
        host_id=args.host_id,
        peer_probe_interval=max(0.05, args.peer_probe_interval),
        mesh_hosts=max(0, args.mesh_hosts),
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.version:
        print(Version)
        return 0
    o = options_from_args(args)

    if args.gzip:  # ref: imaginary.go:168-171
        print("warning: -gzip flag is deprecated and will not have effect")

    # Multi-host identity: stamped into the ENVIRONMENT (not options) so
    # supervisor-spawned workers inherit the same (host_id, host_epoch)
    # incarnation verbatim — a worker must never mint its own host epoch.
    host_info = None
    if o.peers:
        from imaginary_tpu.fleet import multihost

        hid, hepoch = multihost.ensure_host_identity(o.host_id)
        scheme = "https" if o.cert_file and o.key_file else "http"
        host_info = {
            "id": hid,
            "epoch": hepoch,
            "serve_url": (f"{scheme}://{o.address or '127.0.0.1'}:{o.port}"
                          f"{o.path_prefix.rstrip('/')}"),
        }

    # Multi-process serving: the parent becomes the supervisor and the
    # workers re-enter main() marked by WORKER_ENV (web/workers.py holds
    # the design: SO_REUSEPORT fan-in, worker 0 owns the device).
    from imaginary_tpu.web.workers import WORKER_ENV, run_supervisor, worker_index

    if o.workers > 1 and WORKER_ENV not in os.environ:
        # refuse loudly BEFORE any worker pays a jax import: without
        # SO_REUSEPORT the fleet would crash-loop on late bind failures
        from imaginary_tpu.web.workers import check_reuseport

        check_reuseport()
        # liveness probe target: /health is a PUBLIC_PATHS route, so no
        # key rides along; a TLS-only fleet is probed with verification
        # off (the supervisor talks to its own children over loopback)
        scheme = "https" if o.cert_file and o.key_file else "http"
        health_url = (f"{scheme}://127.0.0.1:{o.port}"
                      f"{o.path_prefix.rstrip('/')}/health")
        # fleet shared cache: the supervisor creates the file (one per
        # fleet) and every worker attaches via IMAGINARY_TPU_FLEET_PATH;
        # the supervisor keeps the handle to stamp fencing epochs
        fleet = None
        if o.fleet_cache_mb > 0:
            from imaginary_tpu.fleet import shmcache

            fleet = shmcache.ShmCache.create_for_fleet(o.fleet_cache_mb)
            os.environ[shmcache.PATH_ENV] = fleet.path
        try:
            return run_supervisor(
                list(argv) if argv is not None else sys.argv[1:],
                o.workers, health_url=health_url, fleet=fleet,
                roll_grace_s=o.fleet_roll_grace_s,
                admin_port=o.fleet_admin_port,
                host_info=host_info, peers=o.peers,
                peer_probe_interval=o.peer_probe_interval)
        finally:
            if fleet is not None:
                fleet.close()
    if worker_index() > 0:
        # non-owner workers are CPU-pinned BY DESIGN (the chip accepts one
        # client); --require-device is worker 0's guarantee — enforcing it
        # here would deterministically crash-loop the rest of the fleet
        args.require_device = False

    # Pin the JAX platform when asked: IMAGINARY_TPU_PLATFORM wins over
    # JAX_PLATFORMS, so the supervisor can pin workers 1..N-1 to the CPU
    # (web/workers.py) while an operator's JAX_PLATFORMS still picks
    # worker 0's backend.
    platform = os.environ.get("IMAGINARY_TPU_PLATFORM", "") or os.environ.get("JAX_PLATFORMS", "")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)

    if o.distributed:
        # must run before any jax backend initialization so every process
        # sees the global device set (SURVEY.md section 5.8)
        from imaginary_tpu.parallel.mesh import init_distributed

        init_distributed(
            coordinator_address=o.coordinator_address or None,
            num_processes=o.num_processes,
            process_id=o.process_id,
        )
    elif o.mesh_hosts > 1:
        # --mesh-hosts is --distributed sugar scoped to serving boot: N
        # single-worker hosts join one device mesh BEFORE backend init,
        # so the executor's spatial axis (--spatial-mpix oversize path)
        # can see every host's chips; profitability gating is unchanged
        # (the mesh only wins where the spatial policy already shards)
        from imaginary_tpu.parallel.mesh import init_distributed

        init_distributed(
            coordinator_address=o.coordinator_address or None,
            num_processes=o.mesh_hosts,
            process_id=o.process_id,
        )

    # Backend init happens here, in-process: a failure raises, and no
    # path re-pins the server to another backend behind the operator.
    import jax

    devs = jax.devices()
    print(f"imaginary-tpu: backend {devs[0].platform} "
          f"({devs[0].device_kind}), {len(devs)} device(s)", file=sys.stderr)
    if args.require_device and devs[0].platform == "cpu":
        print("imaginary-tpu: --require-device is set and only the CPU "
              "backend initialized; refusing to start", file=sys.stderr)
        return 2

    from imaginary_tpu.prewarm import enable_persistent_cache

    enable_persistent_cache()

    # IMAGINARY_TPU_PROFILE_DIR=<dir> captures a jax.profiler trace of the
    # serving loop for TensorBoard/xprof (SURVEY.md section 5.1)
    from imaginary_tpu.engine.timing import maybe_start_profiler, stop_profiler

    if maybe_start_profiler():
        import atexit

        atexit.register(stop_profiler)

    from imaginary_tpu.web.app import serve

    if o.prewarm:
        from imaginary_tpu.ops import chain as chain_mod
        from imaginary_tpu.prewarm import prewarm_common_chains

        # the donate flag is part of the compile-cache key: prewarm must
        # agree with the serving executor or every warm would miss
        chain_mod.set_donation(o.donation)
        prewarm_common_chains()
    try:
        asyncio.run(serve(o, mrelease=args.mrelease))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
