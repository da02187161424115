"""Controllers and the live image handler.

The reference's LIVE path skips several documented behaviors that only exist
on its dead controller path (SURVEY.md section 2.13.1); per the survey's
build decision this handler implements the FULL imageHandler semantics
(controllers.go:79-156) live: media-type sniffing, `type=auto` Accept
negotiation with `Vary: Accept`, output-format validation, the
max-allowed-resolution guard, and `-return-size` headers.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Optional

import numpy as np
from aiohttp import web

from imaginary_tpu import cache as cache_mod
from imaginary_tpu import codecs
from imaginary_tpu import deadline as deadline_mod
from imaginary_tpu import failpoints
from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.engine import pressure as pressure_mod
from imaginary_tpu.engine import routes as routes_mod
from imaginary_tpu.engine.timing import COPIES
from imaginary_tpu.errors import (
    ErrEmptyBody,
    ErrNotFound,
    ErrOutputFormat,
    ErrResolutionTooBig,
    ErrUnsupportedMedia,
    ImageError,
    new_error,
)
from imaginary_tpu.obs import trace as obs_trace
from imaginary_tpu.imgtype import (
    determine_image_type,
    get_image_mime_type,
    image_type,
    ImageType,
    is_image_mime_type_supported,
)
from imaginary_tpu.params import ParamError, build_params_from_query
from imaginary_tpu.pipeline import process_operation
from imaginary_tpu.version import current_versions
from imaginary_tpu.web.config import ServerOptions
from imaginary_tpu.web.health import get_health_stats
from imaginary_tpu.web.middleware import (
    check_url_signature,
    error_response,
    validate_image_request,
)
from imaginary_tpu.web.sources import SourceRegistry

_ACCEPT_TO_TYPE = {"image/webp": "webp", "image/png": "png", "image/jpeg": "jpeg"}


def _retry_after_s(est_ms: Optional[float]) -> str:
    """Retry-After seconds for a shed 503, derived from the queue estimate
    (floor 1 s — sub-second retry hints just synchronize the herd)."""
    return str(max(1, int((est_ms or 0.0) / 1000.0 + 0.5)))


def determine_accept_mime_type(accept: str) -> str:
    """Preferred output format from the Accept header
    (ref: controllers.go:63-76)."""
    for part in accept.split(","):
        media = part.split(";", 1)[0].strip().lower()
        if media in _ACCEPT_TO_TYPE:
            return _ACCEPT_TO_TYPE[media]
    return ""


class ImageService:
    """Owns the micro-batch executor, the host thread pool (decode/encode
    parallelism), and the source registry."""

    def __init__(self, o: ServerOptions, qos=None, pressure=None,
                 slo=None, cost=None):
        self.options = o
        # multi-tenant QoS policy (imaginary_tpu/qos/): create_app builds
        # it once and passes it in; direct constructors (tests, benches)
        # get it parsed from the options here. None = qos off.
        if qos is None and o.qos_config:
            from imaginary_tpu.qos.tenancy import load_policy

            qos = load_policy(o.qos_config)
        self.qos = qos
        # memory-pressure governor (engine/pressure.py): same pattern as
        # qos — create_app builds and shares it, direct constructors
        # derive it from the options. None = the subsystem is off and no
        # pressure check ever runs (parity).
        if pressure is None:
            from imaginary_tpu.engine import pressure as pressure_mod

            pressure = pressure_mod.from_options(o)
        self.pressure = pressure
        # SLO burn-rate engine (obs/slo.py): same pattern — create_app
        # builds and shares it (the trace middleware feeds it), direct
        # constructors derive it from the options. None = off (parity:
        # no slo block on /health //metrics //debugz).
        if slo is None and o.slo_config:
            from imaginary_tpu.obs import slo as slo_mod

            slo = slo_mod.from_options(o)
        self.slo = slo
        # cost-attribution plane (obs/cost.py): same pattern — create_app
        # builds and shares it (the trace middleware books into it),
        # direct constructors derive it from the options (which also
        # installs the module plane the engine stamps check). None = off
        # (parity: no capacity block, no /topz, no cost families).
        if cost is None and o.cost_attribution:
            from imaginary_tpu.obs import cost as cost_mod

            cost = cost_mod.from_options(o)
            if cost is not None and self.qos is not None:
                cost.seed_tenants(self.qos.tenant_names())
        self.cost = cost
        # content-addressed cache tiers (imaginary_tpu/cache.py): result
        # LRU + ETag, singleflight coalescing, decoded-frame LRU, and the
        # remote-source TTL cache the registry consumes. All default off.
        self.caches = cache_mod.CacheSet.from_options(o)
        # fleet coherence plane (fleet/ownership.py): None unless BOTH
        # --fleet-cache-mb and --fleet-coherence armed — parity off
        self.coherence = None
        self._forward_server = None
        self._armed_fleet_qos = False
        if o.fleet_cache_mb > 0:
            # fleet shm tier (fleet/shmcache.py): under a supervisor the
            # file was created before this worker spawned and rides in
            # via IMAGINARY_TPU_FLEET_PATH; a single process creates its
            # own. Identity (worker index, fencing epoch) comes from the
            # supervisor's env stamps.
            from imaginary_tpu.fleet.shmcache import ShmCache
            from imaginary_tpu.web.workers import worker_epoch, worker_index

            self.caches.attach_shm(ShmCache.from_options(
                o, worker=worker_index(), epoch=worker_epoch()))
            if o.fleet_coherence and self.caches.shm is not None:
                from imaginary_tpu.fleet.ownership import FleetCoherence

                self.coherence = FleetCoherence(
                    self.caches.shm, worker=worker_index(),
                    hop_s=o.fleet_hop_ms / 1000.0)
            if o.fleet_qos and self.caches.shm is not None:
                # register the shared GCRA/share handle the qos layer
                # consults lazily (fleet/ownership.py registry); cleared
                # in close() so per-test apps never leak it
                from imaginary_tpu.fleet import ownership as ownership_mod

                ownership_mod.set_fleet_qos(
                    ownership_mod.FleetQos(self.caches.shm))
                self._armed_fleet_qos = True
        # cross-host plane (fleet/multihost.py + fleet/router.py): None
        # unless --peers — parity: no peer table, no gossip thread, no
        # route/spill code on the request path, no new headers.
        self.multihost = None
        if o.peers:
            from imaginary_tpu.fleet import multihost as multihost_mod
            from imaginary_tpu.fleet import router as router_mod

            hid, hepoch = multihost_mod.ensure_host_identity(o.host_id)
            self.multihost = router_mod.HostRouter(
                multihost_mod.PeerTable(multihost_mod.parse_peers(o.peers)),
                self_id=hid, self_epoch=hepoch, route_all=o.router,
                hop_s=o.fleet_hop_ms / 1000.0,
                probe_interval_s=o.peer_probe_interval)
        self.frame_cache = cache_mod.FrameCache(self.caches.frames,
                                                self.caches.stats)
        self.registry = SourceRegistry(o, caches=self.caches)
        # compressed-domain transport switch + device-resident frame
        # cache: both ride module-level registries (pipeline and chain
        # respectively), matching how donation is wired — the settings
        # must be in place before the first dispatch compiles anything
        from imaginary_tpu import pipeline as pipeline_mod

        pipeline_mod.set_transport_dct(o.transport_dct)
        pipeline_mod.set_transport_dct_egress(
            o.transport_dct and o.transport_dct_egress)
        # entropy-decoder arm + segment fan-out pool (codecs/jpeg_dct.py):
        # restart-segmented scans split across the handler pool, so the
        # decode parallelism rides the same threads the host codecs use
        from imaginary_tpu.codecs import jpeg_dct as jpeg_dct_mod

        jpeg_dct_mod.set_decoder(o.dct_native)
        # native codec scratch-arena budget + host-side DCT shrink-on-load
        # for spilled work: both module-level switches, same wiring shape
        # as the transport toggles above
        from imaginary_tpu.codecs import native_backend as native_backend_mod
        from imaginary_tpu.engine import host_exec as host_exec_mod

        if o.arena_mb > 0:
            native_backend_mod.set_arena_cap(o.arena_mb)
        host_exec_mod.set_dct_spill(o.host_dct_spill)
        from imaginary_tpu.ops import chain as dev_chain_mod

        # with coherence armed, the device frame cache (device-resident
        # HBM state) lives ONLY on the device-owner worker — siblings run
        # host-path and forward device-shaped digests to the owner, so N
        # workers do not pin N copies of the hot frame set in HBM
        is_dev_owner = (self.coherence is None
                        or self.coherence.is_device_owner())
        if o.cache_device_mb > 0 and is_dev_owner:
            dev_chain_mod.set_device_frame_cache(
                cache_mod.DeviceFrameCache(self.caches.device,
                                           self.caches.stats))
        else:
            dev_chain_mod.set_device_frame_cache(None)
        if pressure is not None:
            # cache tiers shrink/restore their budgets on the governor's
            # transition edge (elevated halves, critical quarters +
            # disables the source cache), not by per-request polling
            pressure.on_transition(
                lambda _old, new: self.caches.apply_pressure(new))
        # output-integrity defense (engine/integrity.py): built here so
        # /health can read its counters next to the executor's; the
        # golden host reference is computed NOW, at boot — a reference
        # computed lazily under suspicion of a sick chip would be
        # computed too late to be trusted as a boot-time ground truth.
        # None when --integrity is off: no state, no checks, parity.
        from imaginary_tpu.engine import integrity as integrity_mod

        self.integrity = integrity_mod.from_options(o)
        if self.integrity is not None or o.failslow_ratio > 0.0:
            integrity_mod.golden()
        # donation rides the chain module (the donate flag is part of the
        # compile-cache key, shared with prewarm): set before the executor
        # exists so its first dispatch compiles what serving will use
        from imaginary_tpu.ops import chain as chain_mod

        chain_mod.set_donation(o.donation)
        self.executor = Executor(
            ExecutorConfig(
                max_batch=o.max_batch,
                max_form_ms=o.batch_form_ms,
                max_inflight=max(1, o.max_inflight),
                use_mesh=o.use_mesh,
                n_devices=o.n_devices,
                spatial=o.spatial,
                spatial_threshold_px=o.spatial_threshold_px,
                mesh_policy=o.mesh_policy,
                spatial_mpix=o.spatial_mpix,
                lane_inflight=o.lane_inflight,
                host_spill=o.host_spill,
                force_host=o.force_host,
                hedge_threshold_ms=o.hedge_threshold_ms,
                hedge_budget=o.hedge_budget,
                qos=qos,
                pressure=pressure,
                integrity=self.integrity,
                failslow_ratio=o.failslow_ratio,
                failslow_min_samples=o.failslow_min_samples,
                failslow_share=o.failslow_share,
                device_owner=is_dev_owner,
            )
        )
        from imaginary_tpu.engine.executor import _available_cpus

        workers = o.cpus if o.cpus > 0 else max(4, _available_cpus())
        self.pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="itpu-host")
        self._pool_workers = workers
        # restart-segmented entropy decodes fan out across this same pool
        # (jpeg_dct._run_scan runs chunk 0 inline and reclaims queued
        # chunks on contention, so sharing the request pool cannot
        # deadlock it)
        jpeg_dct_mod.set_segment_pool(self.pool)
        # admission-control state (--max-queue-ms): in-flight host tasks
        # and an EWMA of per-request host service time feed the queue-delay
        # estimate; GCRA caps the RATE, this caps the queue DEPTH an
        # overload can build (r4 weak: closed-loop p99 reached 450+ ms
        # with nothing bounding per-request queueing)
        self._inflight = 0  # guarded by _inflight_lock (pool threads mutate)
        self._service_ewma_ms = 20.0
        self._inflight_lock = threading.Lock()
        if self.cost is not None:
            # wire the capacity plane's live signal sources: the executor
            # (drain-floor + ms/MB EWMAs for the bound_by advisor) and a
            # host-pool occupancy view
            self.cost.bind(
                executor=self.executor,
                host_view=lambda: (self._pool_workers, self._inflight))

    def estimated_queue_ms(self) -> float:
        """Expected queueing delay for a NEW request: host-pool backlog
        (tasks beyond the worker count, at the measured EWMA service
        time) plus the executor's own device-path estimate."""
        backlog = max(0, self._inflight - self._pool_workers)
        host_wait = backlog * self._service_ewma_ms / max(1, self._pool_workers)
        return host_wait + self.executor.estimated_wait_ms()

    def start_multihost(self) -> None:
        """Start the cross-host gossip thread (no-op with --peers off).
        Called from the app's on_startup hook next to start_coherence so
        unit-test Services never spin a poller."""
        if self.multihost is not None:
            self.multihost.start()

    async def close(self):
        if self.multihost is not None:
            self.multihost.close()
        await self.stop_coherence()
        if self._armed_fleet_qos:
            # unregister OUR handle only (tests boot many apps per
            # process; a stale handle would point at a closed mmap)
            from imaginary_tpu.fleet import ownership as ownership_mod

            ownership_mod.set_fleet_qos(None)
            self._armed_fleet_qos = False
        await self.registry.close()
        self.executor.shutdown()
        self.pool.shutdown(wait=False)
        if self.caches.shm is not None:
            self.caches.shm.close()

    # -- fleet coherence: the forward-hop server lifecycle ---------------------

    async def start_coherence(self) -> None:
        """Bind this worker's forward socket (fleet/ipc.py). Called from
        the app's on_startup hook — the server needs the running loop a
        constructor does not have. No-op with coherence off. A bind
        failure degrades to client-side-only coherence: this worker
        still forwards OUT and claims; siblings forwarding HERE fail
        open to their local execution (the subsystem's one answer)."""
        if self.coherence is None or self._forward_server is not None:
            return
        from imaginary_tpu.fleet import ipc as ipc_mod

        srv = ipc_mod.ForwardServer(
            ipc_mod.socket_path(self.caches.shm.path, self.coherence.worker),
            self._handle_forward)
        try:
            await srv.start()
        except OSError:
            return
        self._forward_server = srv

    async def stop_coherence(self) -> None:
        if self._forward_server is not None:
            await self._forward_server.stop()
            self._forward_server = None

    # -- the image route handler ----------------------------------------------

    async def handle(self, request: web.Request, op_name: str) -> web.StreamResponse:
        o = self.options
        tr = obs_trace.current()
        if tr is not None:
            tr.annotate(op=op_name)
        dl = deadline_mod.current()
        qos = self.qos
        kidx = 1  # CLASSES index; "standard" when qos is off
        if qos is not None:
            ten = getattr(tr, "tenant", None) if tr is not None else None
            kidx = (ten or qos.default).class_index
        try:
            if o.enable_url_signature:
                check_url_signature(request, o)
            validate_image_request(request, o)
            try:
                # chaos site: an injected error IS a shed decision — the
                # same 503 + Retry-After contract as real overload, so
                # `make chaos` can exercise client-visible shedding
                # without building actual backlog
                await failpoints.ahit("qos.admit")
            except failpoints.FailpointError:
                if qos is not None:
                    qos.stats.note_shed(kidx)
                if tr is not None:
                    tr.annotate(placement_attempts=["shed_503"])
                raise new_error(
                    "Request shed by admission control, retry later", 503,
                    headers={"Retry-After": "1"}) from None
            gov = self.pressure
            if gov is not None:
                # the brownout ladder's admission rung: sample the
                # governor once per request, stamp the level into the
                # trace (wide events / slow ring ride along), and at
                # critical shed batch-class work outright — the class
                # whose deferral is already sold, 503 + Retry-After like
                # every other shed in this codebase
                plevel = gov.level()
                if tr is not None and tr.enabled:
                    tr.annotate(pressure=pressure_mod.LEVEL_NAMES[plevel])
                from imaginary_tpu.qos.shed import shed_for_pressure

                if qos is not None and shed_for_pressure(plevel, kidx):
                    # cross-host spillover (fleet/router.py): work this
                    # host is about to shed is first OFFERED to the
                    # least-loaded non-critical peer from gossip; a
                    # failed offer falls through to the 503 the request
                    # was owed anyway — strictly no worse than shedding
                    if self.multihost is not None:
                        spilled = await self._try_spill(request)
                        if spilled is not None:
                            if tr is not None:
                                tr.annotate(
                                    placement_attempts=["spill_peer"])
                            return spilled
                    gov.note_shed()
                    qos.stats.note_shed(kidx)
                    if tr is not None:
                        tr.annotate(placement_attempts=["shed_503"])
                    raise new_error(
                        "Server under memory pressure, batch work shed, "
                        "retry later", 503, headers={"Retry-After": "2"})
            est_ms = None
            if o.max_queue_ms > 0 or dl is not None:
                est_ms = self.estimated_queue_ms()
            limit_ms = o.max_queue_ms
            if qos is not None and o.max_queue_ms > 0:
                # DAGOR-style class grading: the lowest class sheds at
                # half the operator's budget, standard at 3/4, so under
                # building overload capacity is reserved for the classes
                # whose latency is actually sold (qos/shed.py)
                limit_ms = qos.shed_threshold_ms(kidx, o.max_queue_ms)
            if o.max_queue_ms > 0 and est_ms > limit_ms:
                # depth-based admission control: shed load BEFORE fetching
                # the source — at overload an operator wants bounded
                # latency + fast 503s, not an unbounded queue (GCRA bounds
                # the rate; this bounds what a burst can pile up).
                # Retry-After mirrors the rate-limiter's 503 contract so
                # well-behaved clients back off instead of hammering.
                if qos is not None:
                    qos.stats.note_shed(kidx)
                if tr is not None:
                    # the placement ladder's final rung: no capacity
                    # anywhere, the request was shed before any work
                    tr.annotate(placement_attempts=["shed_503"])
                raise new_error(
                    "Server queue is full, retry later", 503,
                    headers={"Retry-After": _retry_after_s(est_ms)})
            if dl is not None:
                # deadline admission ("The Tail at Scale" deadline
                # propagation): when the estimated queue delay already
                # exceeds the remaining budget, a 503 NOW is strictly
                # better than a guaranteed 504 after the client's money
                # was spent — the request is shed before any work
                rem = dl.note("admission")
                if rem <= 0.0:
                    raise dl.error("admission")
                if est_ms > rem * 1000.0:
                    if qos is not None:
                        qos.stats.note_shed(kidx)
                    if tr is not None:
                        tr.annotate(placement_attempts=["shed_503"])
                    raise new_error(
                        "Server queue exceeds request deadline, retry later",
                        503, headers={"Retry-After": _retry_after_s(est_ms)})
            if qos is not None:
                qos.stats.note_admitted(kidx)
            if self.pressure is not None and o.max_allowed_pixels > 0:
                # arm the codec-level bomb cap BEFORE the fetch: the
                # streaming body source runs the same dimension check on
                # the header prefix as soon as it lands (web/sources.py),
                # so an over-cap upload 413s while its body is still on
                # the wire. _process_and_respond re-arms the same value
                # for the pool-thread context — idempotent.
                codecs.set_decode_pixel_cap(o.max_allowed_pixels)
            with obs_trace.span("fetch"):
                buf = await self._get_source_image(request)
            if not buf:
                raise ErrEmptyBody
            if tr is not None:
                tr.annotate(bytes_in=len(buf))
            return await self._process_and_respond(request, op_name, buf)
        except ImageError as e:
            return error_response(request, e, o)
        except ParamError as e:
            return error_response(request, new_error(str(e), 400), o)

    async def _try_spill(self, request) -> Optional[web.Response]:
        """Offer one about-to-shed request to the least-loaded
        non-critical peer (cross-host spillover). The ORIGINAL request
        ships verbatim — method, path+query, body — and the peer runs
        its own fetch/admission. None on any fault or when no eligible
        peer exists: the caller sheds exactly as it would have."""
        mh = self.multihost
        from imaginary_tpu.fleet import router as router_mod

        hint = str(request.headers.get(router_mod.ROUTE_HEADER, ""))
        if hint.startswith("fwd"):
            # arrived over a hop already: two critical hosts must shed,
            # not ping-pong the same request between each other
            return None
        peer = mh.spill_target()
        if peer is None:
            return None
        try:
            body = await request.read()
        except Exception:
            return None
        res = await mh.try_spill(peer, request.method, request.path_qs,
                                 body, dict(request.headers))
        if res is None:
            return None
        status, mime, rbody = res
        return web.Response(body=rbody, status=status,
                            content_type=mime or "application/octet-stream")

    async def _get_source_image(self, request: web.Request) -> bytes:
        try:
            return await self.registry.get_image(request)
        except ImageError:
            raise
        except Exception as e:
            raise new_error("Error getting image: " + str(e), 400) from None

    async def _process_and_respond(self, request, op_name, buf) -> web.Response:
        o = self.options

        # media-type sniff (ref: imageHandler controllers.go:80-84)
        sniffed = determine_image_type(buf)
        if sniffed is ImageType.UNKNOWN or not is_image_mime_type_supported(
            get_image_mime_type(sniffed)
        ):
            raise ErrUnsupportedMedia

        try:
            opts = build_params_from_query(dict(request.query))
        except ParamError as e:
            raise new_error("Error while processing parameters: " + str(e), 400) from None

        # type=auto Accept negotiation (ref: controllers.go:89-99)
        vary = ""
        if opts.type == "auto":
            opts.type = determine_accept_mime_type(request.headers.get("Accept", ""))
            vary = "Accept"
        elif opts.type and image_type(opts.type) is ImageType.UNKNOWN:
            raise ErrOutputFormat

        # resolution guard (ref: controllers.go:101-110). probe_fast is the
        # header-only parser; the metadata is reused downstream so the hot
        # path pays exactly one header parse per request.
        #
        # With the pressure subsystem armed (governor non-None) the same
        # guard grows three teeth, all PARITY-off without it:
        #   * the codec-level pre-decode gate is armed in this request's
        #     context (copy_context carries it into pool threads), so a
        #     bomb whose header this probe couldn't parse still cannot
        #     make any decode — including the watermark fetch — allocate
        #     past the cap;
        #   * over-cap sources answer 413 (the payload demands more
        #     memory than this server will commit) instead of the
        #     reference's 422 — PARITY r11 notes the divergence;
        #   * at critical pressure, admission clamps to pixel_frac of the
        #     cap for BOTH source dims and the requested output dims (an
        #     8K enlarge of a thumbnail is an output-side memory bomb).
        gov = self.pressure
        limit_mpix = o.max_allowed_pixels
        clamp_mpix = 0.0
        if gov is not None and limit_mpix > 0:
            codecs.set_decode_pixel_cap(limit_mpix)
            if gov.level() >= pressure_mod.LEVEL_CRITICAL:
                clamp_mpix = limit_mpix * gov.config.pixel_frac
        if clamp_mpix > 0.0:
            out_w = getattr(opts, "width", 0) or 0
            out_h = getattr(opts, "height", 0) or 0
            if out_w > 0 and out_h > 0 and out_w * out_h / 1e6 > clamp_mpix:
                gov.note_pixel_clamp()
                raise new_error(
                    "Requested output resolution exceeds the memory-"
                    "pressure admission clamp, retry later", 413,
                    headers={"Retry-After": "2"})
        meta = None
        if limit_mpix > 0:
            try:
                meta = codecs.probe_fast(buf)
                src_mpix = meta.width * meta.height / 1_000_000.0
                if clamp_mpix > 0.0 and src_mpix > clamp_mpix:
                    gov.note_pixel_clamp()
                    raise new_error(
                        "Image resolution exceeds the memory-pressure "
                        "admission clamp, retry later", 413,
                        headers={"Retry-After": "2"})
                if src_mpix > limit_mpix:
                    if gov is not None:
                        raise new_error("Image resolution is too big", 413)
                    raise ErrResolutionTooBig
            except ImageError as e:
                if e is ErrResolutionTooBig or e.code == 413:
                    raise
                # probe failure falls through; decode will produce the error

        # --- content-addressed cache tiers (imaginary_tpu/cache.py) -------
        # The key derives from sha256(source bytes) + the canonicalized
        # operation, AFTER Accept negotiation resolved type=auto — so a
        # negotiated webp and jpeg never share an entry or an ETag.
        caches = self.caches
        digest = key = etag = None
        if caches.keyed or caches.frames.enabled:
            digest = cache_mod.source_digest(buf)
        if caches.keyed:
            key = cache_mod.request_key(digest, op_name, opts)

        tr = obs_trace.current()
        if tr is not None and tr.enabled:
            # plan digest: op x negotiated output type x sorted query with
            # source-identifying params excluded — a GROUPING key for wide
            # events ("which transformation shape was slow"), cheap by
            # construction (the full options canonicalization costs ~50us
            # per call, measured; this is the per-request hot path)
            qs = tuple(sorted(
                (k, v) for k, v in request.query.items()
                if k not in ("url", "file", "sign")
            ))
            tr.annotate(plan=hashlib.sha256(
                repr((op_name, opts.type, qs)).encode()).hexdigest()[:16],
                cache="off")
        if (caches.result.enabled or caches.shm is not None) \
                and key is not None:
            with obs_trace.span("cache_lookup"):
                etag = cache_mod.strong_etag(key)
                if request.method == "GET" and cache_mod.etag_matches(
                    request.headers.get("If-None-Match", ""), etag
                ):
                    # conditional GET answered before the pipeline runs
                    caches.stats.etag_304 += 1
                    if tr is not None:
                        tr.annotate(cache="etag_304")
                    headers = {"ETag": etag}
                    if vary:
                        headers["Vary"] = vary
                    return web.Response(status=304, headers=headers)
                hit = None
                if caches.result.enabled:
                    try:
                        hit = caches.result.get(key)
                    except Exception:
                        # a failing cache tier degrades to a miss, never
                        # to a failed request (failpoint cache.get proves)
                        hit = None
            if hit is not None:
                caches.stats.result_hits += 1
                if tr is not None:
                    tr.annotate(cache="result_hit")
                out, placement = hit
                # the ONE read of the stored body a local hit pays (the
                # response writes straight from it — no snapshot at all)
                COPIES.add("cache_hit", len(out.body))
                return self._build_response(out, placement, vary, etag, o)
            if caches.result.enabled:
                caches.stats.result_misses += 1
            # tiered lookup, local LRU -> fleet shm: a sibling worker may
            # already have produced this exact response. Entries are
            # checksum-verified by the tier; a corrupt or torn entry
            # reads as a miss here, never as bytes.
            shm_hit = caches.shm_lookup(key)
            if shm_hit is not None:
                out, placement = shm_hit
                # the shm tier's defensive mmap snapshot IS the one copy
                # a fleet hit pays; mirror it into the unified ledger so
                # both tiers grade on the same copies-per-hit == 1 bar
                COPIES.add("cache_hit", len(out.body))
                if caches.result.enabled:
                    # promote: the next local hit skips the IPC copy
                    caches.result.put(key, (out, placement), len(out.body))
                if tr is not None:
                    tr.annotate(cache="shm_hit")
                return self._build_response(out, placement, vary, etag, o)
            if tr is not None:
                tr.annotate(cache="result_miss")

        # --- cross-host routing: one HTTP hop to the owner HOST ------------
        # Armed only with --peers (+ --router or a per-request route hint):
        # host-level rendezvous elects one owner host per shared key, and a
        # non-owner ships source bytes + resolved params one hop so the
        # owner host's caches and intra-host ownership ring see every
        # occurrence of the digest CLUSTER-wide. Placed after the local
        # cache lookups (a local hit never pays a network hop) and before
        # the intra-host forward (the receiving host runs its own). Any
        # fault — dead host, fenced answer, hop timeout, injected
        # peer.forward — falls through to local execution: no new 5xx.
        mh = self.multihost
        if mh is not None and not mh.note_hop_marker(request.headers):
            rdigest = digest if digest is not None \
                else cache_mod.source_digest(buf)
            rkey = key if key is not None \
                else cache_mod.request_key(rdigest, op_name, opts)
            peer = mh.route_target(request.headers,
                                   cache_mod.shared_key(rkey))
            if peer is not None:
                fwd_query = dict(request.query)
                # the peer re-fetches nothing: source bytes ride the
                # body, so source-identifying params must not
                for p in ("url", "file", "sign"):
                    fwd_query.pop(p, None)
                if fwd_query.get("type") == "auto":
                    # ship the NEGOTIATED type — the owner host has no
                    # Accept header to re-run the negotiation against
                    fwd_query["type"] = opts.type
                fwd = await mh.try_forward(
                    peer, op_name, fwd_query, buf,
                    get_image_mime_type(sniffed))
                if fwd is not None:
                    out, placement = fwd
                    if caches.result.enabled and key is not None:
                        # promote: the next local occurrence skips the hop
                        caches.result.put(key, (out, placement),
                                          len(out.body))
                    if tr is not None:
                        tr.annotate(cache="host_forward",
                                    placement=placement)
                    return self._build_response(out, placement, vary,
                                                etag, o)

        # --- fleet coherence: forward to the digest's owner ----------------
        # Armed only with --fleet-coherence: the rendezvous ring elects one
        # owner per shared key; a non-owner ships source bytes + resolved
        # params one local hop and serves the owner's answer (the owner's
        # caches see every occurrence of the digest fleet-wide). Any hop
        # fault falls through to the uncoordinated local path below.
        flc = self.coherence
        skey = None
        if flc is not None and key is not None:
            skey = cache_mod.shared_key(key)
            fwd_query = dict(request.query)
            if fwd_query.get("type") == "auto":
                # ship the NEGOTIATED type: both sides must derive the
                # same key, and the owner has no Accept header to re-run
                # the negotiation against
                fwd_query["type"] = opts.type
            fwd = await flc.try_forward(op_name, fwd_query, buf, skey)
            if fwd is not None:
                out, placement = fwd
                if caches.result.enabled:
                    # promote: the next local occurrence skips the hop
                    caches.result.put(key, (out, placement), len(out.body))
                if tr is not None:
                    tr.annotate(cache="fleet_forward", placement=placement)
                return self._build_response(out, placement, vary, etag, o)

        async def produce():
            wm_rgba = await self._prefetch_watermark(request, op_name, opts)
            return await self._submit_pool(op_name, buf, opts, wm_rgba,
                                           meta, digest)

        async def run_work():
            body_fn = produce
            if flc is not None and key is not None:
                # fleet singleflight: the local leader claims the shared
                # key so N WORKERS x same digest still run the pipeline
                # once fleet-wide; the claim runner owns the shm deposit
                # (winner stores BEFORE the claim drops) and every
                # failure exit runs locally — fail-open
                async def claimed():
                    return await flc.run_claimed(key, skey, produce, caches)

                body_fn = claimed
            if caches.coalesce and key is not None:
                # singleflight: N concurrent identical (digest, plan)
                # requests run produce() ONCE — one _inflight unit, one
                # pipeline run — and every waiter (shielded, so a client
                # disconnect detaches without cancelling the group) gets
                # the same result or the same error
                return await caches.flight.run(key, body_fn)
            return await body_fn()

        dl = deadline_mod.current()
        try:
            if dl is None:
                out, placement = await run_work()
            else:
                # The deadline's one await-side enforcement point: bounds
                # the coalesce wait, the executor/pool queue wait, and the
                # work itself. wait_for's cancellation does the right thing
                # on both paths: a pool future still QUEUED is cancelled
                # and _release_if_cancelled balances the _inflight ledger
                # (the worker never runs it); a coalesce FOLLOWER detaches
                # from the shielded group task without cancelling the
                # leader's run other waiters depend on.
                rem = dl.note("queue")
                if rem <= 0.0:
                    raise dl.error("queue")
                try:
                    out, placement = await asyncio.wait_for(run_work(), rem)
                except asyncio.TimeoutError:
                    raise dl.error("queue") from None
        except ImageError:
            raise
        except Exception as e:
            raise new_error("Error processing image: " + str(e), 400) from None

        if tr is not None:
            tr.annotate(placement=placement)
        if caches.result.enabled and key is not None:
            # placement rides along so a replayed response carries the
            # same X-Imaginary-Backend facts as the run that produced it
            caches.result.put(key, (out, placement), len(out.body))
        if key is not None and flc is None:
            # fleet deposit (no-op when the shm tier is off): two-phase
            # write-then-publish, refused when this worker is fenced.
            # With coherence armed the claim runner already deposited
            # (winner stores before its claim drops) — a second store
            # here would double-publish every miss.
            caches.shm_store(key, out, placement)
        return self._build_response(out, placement, vary, etag, o)

    async def _submit_pool(self, op_name, buf, opts, wm_rgba, meta, digest):
        """Dispatch one pipeline run onto the host pool. Inflight is
        incremented HERE and normally decremented inside _process_sync's
        own finally, in the pool thread — NOT in an async finally: a
        client disconnect cancels the awaiting coroutine while the
        worker thread keeps running, and decrementing on cancellation
        would collapse the backlog signal to ~0 exactly at overload
        (mass client timeouts), failing the admission gate open when it
        matters most. The one case _process_sync's finally can never
        cover: a task cancelled while still QUEUED in the pool never
        starts, so the done-callback balances the ledger for exactly the
        fut.cancelled() outcome (run_in_executor can't express this —
        its asyncio future abandons the pool task without cancelling it;
        submit + wrap_future propagates the cancellation into the pool
        queue). Without it every cancelled-while-queued request leaked
        one _inflight forever, inflating estimated_queue_ms until
        --max-queue-ms latched shut.

        The request's route token (engine/routes.py) follows the same
        rules: taken here, released by Executor.submit before its item is
        enqueued, else by _process_sync's finally or, for a task cancelled
        while queued, by the same done-callback. Release is idempotent."""
        with self._inflight_lock:
            self._inflight += 1
        token = self.executor.routes.take(op_name)
        # copy_context() carries the contextvar trace into the worker
        # thread: stage timings recorded there (decode/encode/
        # host_spill via engine/timing.py) attribute to THIS request.
        # For a coalesced group the leader's context rides along —
        # the shared run's spans land in the leader's trace.
        ctx = contextvars.copy_context()
        ctx.run(routes_mod.bind, token)
        # [submitted, finished] on the monotonic clock: the pool thread
        # books `pool_wait` (waiting for a pool thread) from the first and
        # stamps the second; this side books `resume` (the event loop's
        # delay in picking the result back up) from it
        clock = [time.monotonic(), 0.0]
        fut = self.pool.submit(ctx.run, self._process_sync, clock, op_name,
                               buf, opts, wm_rgba, meta, digest)
        fut.add_done_callback(
            functools.partial(self._release_if_cancelled, token=token))
        result = await asyncio.wrap_future(fut)
        tr = obs_trace.current()
        if tr is not None:
            tr.add_span("resume", (time.monotonic() - clock[1]) * 1000.0)
        return result

    async def _handle_forward(self, header: dict, body: bytes):
        """Owner side of the forward hop (fleet/ipc.py handler): compute
        — or serve from this worker's caches — a sibling's request for a
        digest this worker owns. The client already ran ingress checks
        (size cap, signature, admission) and Accept negotiation; the
        header carries the RESOLVED params, so keys derive identically
        on both sides. Runs under a non-exported trace holding the
        remaining hop budget as its deadline, so the pool/device waits
        inherit the client's clock."""
        flc = self.coherence
        shm = self.caches.shm
        if flc is None or shm is None or shm.fenced() or shm.host_fenced():
            # a deposed zombie must not compute for the fleet: refuse in
            # an orderly frame; the client falls back to local execution
            if flc is not None:
                flc.stats.serve_refused += 1
            return {"status": "fenced"}, b""
        op_name = str(header.get("op", ""))
        try:
            opts = build_params_from_query(
                {str(k): str(v) for k, v in dict(header.get("query")
                                                 or {}).items()})
        except ParamError:
            return {"status": "error", "error": "params"}, b""
        sniffed = determine_image_type(body)
        if sniffed is ImageType.UNKNOWN:
            return {"status": "error", "error": "media"}, b""
        caches = self.caches
        digest = cache_mod.source_digest(body)
        key = cache_mod.request_key(digest, op_name, opts) \
            if caches.keyed else None
        tr = obs_trace.RequestTrace(request_id="fleet-forward", enabled=False)
        budget_ms = float(header.get("budget_ms") or 0)
        if budget_ms > 0:
            tr.deadline = deadline_mod.Deadline(budget_ms / 1000.0)
        token = obs_trace.activate(tr)
        try:
            if key is not None:
                if caches.result.enabled:
                    try:
                        hit = caches.result.get(key)
                    except Exception:
                        hit = None
                    if hit is not None:
                        caches.stats.result_hits += 1
                        out, placement = hit
                        flc.stats.serve_forwarded += 1
                        return ({"status": "ok", "mime": out.mime,
                                 "placement": placement or ""},
                                bytes(out.body))
                shm_hit = caches.shm_lookup(key)
                if shm_hit is not None:
                    out, placement = shm_hit
                    flc.stats.serve_forwarded += 1
                    return ({"status": "ok", "mime": out.mime,
                             "placement": placement or ""}, bytes(out.body))

            async def produce():
                # request=None: the prefetch only reads op/opts (the
                # watermark URL rides the params, not the request)
                wm_rgba = await self._prefetch_watermark(None, op_name, opts)
                return await self._submit_pool(op_name, body, opts, wm_rgba,
                                               None, digest)

            async def claimed():
                # flight OUTSIDE claim, matching the live handler path:
                # a consistent order means a local leader and a forwarded
                # request for the same key can never wait on each other
                if key is not None:
                    return await flc.run_claimed(
                        key, cache_mod.shared_key(key), produce, caches)
                return await produce()

            if caches.coalesce and key is not None:
                out, placement = await caches.flight.run(key, claimed)
            else:
                out, placement = await claimed()
            if caches.result.enabled and key is not None:
                caches.result.put(key, (out, placement), len(out.body))
            flc.stats.serve_forwarded += 1
            return ({"status": "ok", "mime": out.mime,
                     "placement": placement or ""}, bytes(out.body))
        finally:
            obs_trace.deactivate(token)

    # returnSize probes at most this many header bytes when an entry's
    # meta carries no dims (legacy/shm entries): SOF/IHDR live in the
    # first KBs, so a multi-MB body is never copied to read its header
    _PROBE_PREFIX = 64 * 1024

    def _build_response(self, out, placement, vary, etag, o) -> web.Response:
        headers = {}
        if placement:
            headers["X-Imaginary-Backend"] = placement
        if vary:
            headers["Vary"] = vary
        if etag:
            headers["ETag"] = etag
        if self.multihost is not None:
            # incarnation stamp: a cross-host forwarder refuses answers
            # whose epoch gossip has already deposed (fleet/router.py).
            # Absent with --peers off — response byte parity.
            from imaginary_tpu.fleet import router as router_mod

            headers[router_mod.HOST_EPOCH_HEADER] = \
                self.multihost.identity_header
        if o.return_size and out.mime != "application/json":
            # dims ride the result-cache meta (pipeline stamps plan
            # geometry into ProcessedImage), so the hot path re-probes
            # nothing and copies nothing
            w = getattr(out, "width", 0)
            h = getattr(out, "height", 0)
            if not (w and h):
                try:
                    prefix = bytes(memoryview(out.body)[:self._PROBE_PREFIX])
                    COPIES.add("response", len(prefix))
                    m = codecs.probe(prefix)
                    w, h = m.width, m.height
                except ImageError:
                    w = h = 0
            if w and h:
                headers["Image-Width"] = str(w)
                headers["Image-Height"] = str(h)
        return web.Response(body=out.body, content_type=out.mime, headers=headers)

    async def _prefetch_watermark(self, request, op_name, opts) -> Optional[np.ndarray]:
        """watermarkImage URL fetch happens async, before thread dispatch
        (ref: image.go:343-357; origin-checked unlike the reference)."""
        url = ""
        if op_name == "watermarkImage":
            url = opts.image
        elif op_name == "pipeline":
            for op in opts.operations:
                if op.name == "watermarkImage":
                    url = str(op.params.get("image", ""))
                    break
        if not url:
            return None
        raw = await self.registry.fetch_watermark(url)
        if not raw:
            raise new_error("Unable to read watermark image", 400)
        d = codecs.decode(raw)
        arr = d.array
        if arr.shape[2] == 3:
            alpha = np.full(arr.shape[:2] + (1,), 255, dtype=np.uint8)
            arr = np.concatenate([arr, alpha], axis=2)
        return arr

    def _release_if_cancelled(self, fut, token=None) -> None:
        """Balance the _inflight ledger for pool tasks that never ran: a
        future cancelled while queued skips _process_sync (and its
        finally) entirely. Ran-and-finished futures are NOT cancelled, so
        this never double-decrements."""
        if fut.cancelled():
            with self._inflight_lock:
                self._inflight -= 1
            if token is not None:
                token.release()

    def _process_sync(self, clock, op_name, buf, opts, wm_rgba, meta=None,
                      digest=None):
        # Service-time EWMA measured INSIDE the worker thread: stamping
        # at submission would fold pool queue-wait into "service time"
        # and make estimated_queue_ms count the backlog twice (backlog x
        # inflated-EWMA grows quadratically with queue depth).
        t0 = time.monotonic()
        tr = obs_trace.current()
        if tr is not None:
            tr.add_span("pool_wait", (t0 - clock[0]) * 1000.0, end=t0)
        try:
            # a request that expired while queued must not cost a single
            # decoded byte: bail here so the worker frees immediately (the
            # async side already 504'd via wait_for; this keeps the pool
            # honest when the future started running right at the buzzer)
            deadline_mod.check("host_pool")
            return self._process_sync_inner(op_name, buf, opts, wm_rgba,
                                            meta, digest)
        finally:
            # decode errors, identity plans and every other exit that never
            # reached Executor.submit (a no-op where submit released it)
            token = routes_mod.current()
            if token is not None:
                token.release()
            clock[1] = time.monotonic()
            dt_ms = (clock[1] - t0) * 1000.0
            with self._inflight_lock:
                self._inflight -= 1
                self._service_ewma_ms += 0.1 * (dt_ms - self._service_ewma_ms)

    def _process_sync_inner(self, op_name, buf, opts, wm_rgba, meta=None,
                            digest=None):
        from imaginary_tpu.engine.executor import last_placement, reset_placement

        fetcher = (lambda url: wm_rgba) if wm_rgba is not None else None
        frames = self.frame_cache if self.frame_cache.enabled else None
        reset_placement()
        out = process_operation(
            op_name, buf, opts, watermark_fetcher=fetcher,
            runner=self._execute_within_deadline, meta=meta,
            frame_cache=frames, source_digest=digest,
        )
        # placement was recorded by submit() on THIS worker thread
        return out, last_placement()

    def _execute_within_deadline(self, arr, plan):
        """Executor.process with the device wait bounded by the request's
        remaining budget: a future whose deadline passes while it sits in
        the micro-batch queue (or mid-drain on a slow device) is cancelled
        — releasing its owed-work ledger charge via the done-callback —
        and the request 504s instead of riding out the full 120 s cap."""
        dl = deadline_mod.current()
        if dl is None:
            return self.executor.process(arr, plan)
        rem = dl.note("device_queue")
        if rem <= 0.0:
            raise dl.error("device_queue")
        fut = self.executor.submit(arr, plan)
        try:
            out = fut.result(timeout=rem)
        except FuturesTimeout:
            fut.cancel()  # queued: skipped at dispatch; running: result dropped
            raise dl.error("device_execute") from None
        hp = getattr(fut, "_hedge_placement", None)
        if hp:
            # a hedge twin beat the device path: these pixels came from
            # the host interpreter (X-Imaginary-Backend must say so)
            from imaginary_tpu.engine.executor import note_placement

            note_placement(hp)
        return out


# --- simple controllers -------------------------------------------------------

async def index_controller(request: web.Request, o: ServerOptions) -> web.Response:
    """Version JSON (ref: controllers.go:17-26)."""
    prefix = o.path_prefix.rstrip("/") or ""
    if request.path not in (prefix + "/", prefix or "/"):
        return error_response(request, ErrNotFound, o)
    return web.json_response(current_versions().to_dict())


def collect_health_stats(service: Optional[ImageService]) -> dict:
    """The ONE stats assembly /health and /metrics both serve (they must
    never drift — /metrics promises 'the same numbers as /health')."""
    stats = get_health_stats(service.executor if service else None,
                             qos=service.qos if service else None,
                             pressure=service.pressure if service else None,
                             slo=service.slo if service else None,
                             cost=getattr(service, "cost", None)
                             if service else None)
    if service is not None:
        # the admission-control signal (estimated_queue_ms): operators
        # watching overload want the same number the 503 gate reads
        stats["estimatedQueueMs"] = round(service.estimated_queue_ms(), 2)
        # cache tier counters (hit/miss/eviction/coalesce), same
        # Executor.stats()-style dict /metrics renders as gauges
        stats["cache"] = service.caches.to_dict()
        if service.caches.shm is not None:
            # fleet shared-cache block (fleet/shmcache.py): this
            # worker's epoch/fence state, the shared slot-table scan,
            # and its process-local hit/publish/corrupt/reclaim
            # counters; absent with --fleet-cache-mb off — the block's
            # presence IS the armed/parity signal
            stats["fleet"] = service.caches.shm.snapshot()
            if service.coherence is not None:
                # ownership-plane counters (fleet/ownership.py): the
                # ring view + forward/claim outcomes; the sub-dict's
                # presence IS the --fleet-coherence armed signal
                stats["fleet"]["coherence"] = service.coherence.snapshot()
        if service.multihost is not None:
            # cross-host plane (fleet/router.py): identity, route/spill
            # outcome counters and the gossiped peer table; the block's
            # presence IS the --peers armed signal
            stats["multihost"] = service.multihost.snapshot()
        if service.options.read_timeout_s > 0:
            # ingress read-guard counters (web/ingress.py)
            from imaginary_tpu.web.ingress import STATS as ingress_stats

            stats["ingress"] = ingress_stats.to_dict()
        # native codec scratch-arena counters: absent when the built
        # extension predates the arena ABI (the block's presence IS the
        # armed signal, matching fleet/integrity/slo)
        from imaginary_tpu.codecs import native_backend

        arena = native_backend.arena_stats()
        if arena is not None:
            stats["arena"] = arena
    # event-loop lag probe (obs/looplag.py): absent until the sampler
    # has taken a sample (a bare worker that never ran a loop reports
    # nothing, matching the other presence-is-the-signal blocks)
    from imaginary_tpu.obs import looplag

    loop_lag = looplag.snapshot()
    if loop_lag is not None:
        stats["eventLoop"] = loop_lag
    return stats


async def health_controller(request: web.Request, service: Optional[ImageService]) -> web.Response:
    # chaos site, deliberately SYNCHRONOUS: a delay() armed here blocks
    # the whole event loop — the "process alive, loop wedged" failure the
    # workers.py supervisor's liveness probe exists to catch (an async
    # sleep would only slow this one request and prove nothing)
    # itpu: allow[ITPU001] deliberate sync block: this failpoint SIMULATES the wedged-loop failure
    failpoints.hit("worker.hang")
    return web.json_response(collect_health_stats(service))


async def form_controller(request: web.Request, o: ServerOptions) -> web.Response:
    """HTML playground (ref: controllers.go:159-194)."""
    prefix = o.path_prefix.rstrip("/")
    demos = [
        ("Resize", "resize", "width=300&height=200&type=jpeg"),
        ("Force resize", "resize", "width=300&height=200&force=true"),
        ("Crop", "crop", "width=300&quality=95"),
        ("SmartCrop", "crop", "width=300&height=260&quality=95&gravity=smart"),
        ("Extract", "extract", "top=100&left=100&areawidth=300&areaheight=150"),
        ("Enlarge", "enlarge", "width=1440&height=900&quality=95"),
        ("Rotate", "rotate", "rotate=180"),
        ("AutoRotate", "autorotate", "quality=90"),
        ("Flip", "flip", ""),
        ("Flop", "flop", ""),
        ("Thumbnail", "thumbnail", "width=100"),
        ("Zoom", "zoom", "factor=2&areawidth=300&top=80&left=80"),
        ("Color space (black&white)", "resize", "width=400&height=300&colorspace=bw"),
        ("Add watermark", "watermark", "textwidth=100&text=Hello&font=sans%2012&opacity=0.5&color=255,200,50"),
        ("Convert format", "convert", "type=png"),
        ("Image metadata", "info", ""),
        ("Gaussian blur", "blur", "sigma=15.0&minampl=0.2"),
        ("Pipeline", "pipeline",
         "operations=%5B%7B%22operation%22:%20%22crop%22,%20%22params%22:%20%7B%22width%22:%20300,"
         "%20%22height%22:%20260%7D%7D,%20%7B%22operation%22:%20%22convert%22,%20%22params%22:"
         "%20%7B%22type%22:%20%22webp%22%7D%7D%5D"),
    ]
    parts = ["<html><body>"]
    for title, op, args in demos:
        action = f"{prefix}/{op}" + (f"?{args}" if args else "")
        parts.append(
            f'<h1>{title}</h1>'
            f'<form method="POST" action="{action}" enctype="multipart/form-data">'
            f'<input type="file" name="file" /><input type="submit" value="Upload" />'
            f"</form>"
        )
    parts.append("</body></html>")
    return web.Response(text="".join(parts), content_type="text/html")
