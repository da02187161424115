"""Chain compiler: stage chain -> ONE jit-compiled device program.

The unit of compilation (and of the compile cache) is the *chain signature*:
(tuple of stage specs, input bucket, channels, batch size). Dynamic params
ride as arrays, so every request with the same signature — any actual dims,
scales, offsets, colors — reuses the same XLA executable. A launch stages
its pixels plus ONE packed int32 array of every item's small params (h, w
and each stage's scalars, bit-cast where they are floats); only a value of
more than _PACK_MAX elements an item (a watermark overlay, the DCT
quantizer tables) keeps an operand of its own. A multi-op
/pipeline therefore compiles to a single fused program: decode once, one
device round-trip, encode once (vs the reference's per-op decode/transform/
encode loop, SURVEY.md section 3.3 — the biggest architectural win).

Transfers: images move host->device as uint8 (4x less PCIe/ICI traffic than
f32); conversion to f32 happens on device and output returns as uint8.

Buffer donation: the batch operand is compiled with `donate_argnums` so XLA
may reuse the input's HBM for intermediates/outputs — on a memory-bound chip
that halves the per-batch footprint and drops an allocation from the hot
path. Donation is ALIASING-SAFE by construction here: launch_batch always
stages the batch through a fresh copy (np.stack over the per-item arrays, or
a device_put of that stack), so a frame-cache-resident host array is never
the donated buffer — the donated array dies with the call and the cache's
bytes are untouched (pinned by tests/test_continuous.py). The installed
backends (CPU, TPU) accept donation; JAX only warns where a donated buffer
could not be aliased. So an error from a donated call is a real error and
propagates — a use-after-donate bug is never retried away.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from imaginary_tpu.engine.timing import WIRE
from imaginary_tpu.ops.buckets import bucket_shape
from imaginary_tpu.ops.plan import ImagePlan

_CACHE: dict = {}
_LOCK = threading.Lock()

# Device-resident frame cache (cache.DeviceFrameCache), installed by the
# web layer when --cache-device-mb > 0. Chain-level rather than
# executor-level on purpose: run_single, the bench, and every executor
# launch path stage through launch_batch, so one registry covers them all.
_DEVICE_FRAMES = None


def set_device_frame_cache(cache) -> None:
    global _DEVICE_FRAMES
    _DEVICE_FRAMES = cache


def device_frame_cache():
    return _DEVICE_FRAMES


def device_frame_cache_bytes() -> int:
    dc = _DEVICE_FRAMES
    return dc.bytes_used if dc is not None else 0

# Buffer-donation switch (process-wide, like the link seed): the executor
# and prewarm must agree on it — the donate flag is part of the compile
# cache key, so a prewarm/serve disagreement would recompile every chain
# at first request. Flipped off only by --donation off.
_DONATE = True

# XLA tells us (per compile, as a Python warning) when a donated buffer
# could not actually be aliased — e.g. the output bucket differs from the
# input's so shapes don't line up. That is the expected, harmless case:
# donation is permission, not obligation, and the input buffer still frees
# at dispatch instead of at fetch. Silence it once, narrowly, or every
# resize chain would warn on its first launch.
import warnings as _warnings

_warnings.filterwarnings(
    "ignore", message=".*[Dd]onated buffers? w[a-z]* not usable.*")


def set_donation(enabled: bool) -> None:
    """Operator/boot toggle (cli --donation)."""
    global _DONATE
    _DONATE = bool(enabled)


def donation_enabled() -> bool:
    return _DONATE


def _run_chain(specs, x, params, wide, layout):
    h, w, dyns = unpack_operands(params, wide, layout)
    x = x.astype(jnp.float32)
    for spec, dyn in zip(specs, dyns):
        x, h, w = spec.apply(x, h, w, dyn)
    if specs and getattr(specs[-1], "out_dtype", None) == "int16":
        # coefficient drain (ToDctSpec): signed quantized values, NOT
        # pixels — the uint8 clamp below would destroy them. Static
        # branch: specs is the jit static argument.
        x = jnp.clip(jnp.round(x), -32768.0, 32767.0).astype(jnp.int16)
    else:
        x = jnp.clip(x + 0.5, 0.0, 255.0).astype(jnp.uint8)  # round-to-nearest
    return x, h, w


# A dyn value of at most this many 4-byte elements an item rides in the
# launch's packed int32 array; a larger one keeps its own operand.
_PACK_MAX = 4


def operand_layout(plan: ImagePlan) -> tuple:
    """Where each stage's dyn values ride in a launch of this plan: per
    stage, (key, per-item shape, dtype, column) — column None for a value
    that keeps its own operand. Columns 0 and 1 hold h and w. A static
    function of the spec key and the dyn shapes, so it joins the compile
    key. dtypes are the ones jnp.asarray would give (float64 -> float32,
    int64 -> int32)."""
    col = 2
    layout = []
    for st in plan.stages:
        entries = []
        for key, v in st.dyn.items():
            a = np.asarray(v)
            dt = np.dtype(jax.dtypes.canonicalize_dtype(a.dtype))
            if a.size <= _PACK_MAX and dt.itemsize == 4:
                entries.append((key, a.shape, dt.name, col))
                col += a.size
            else:
                entries.append((key, a.shape, dt.name, None))
        layout.append(tuple(entries))
    return tuple(layout)


def pack_operands(plans: list, h, w) -> tuple:
    """(params, wide, layout) for one launch: params int32[B, K] holds h,
    w and every small dyn value bit-cast to int32; wide the larger values
    stacked over the batch, in layout order."""
    layout = operand_layout(plans[0])
    b = len(plans)
    ncol = 2 + sum(int(np.prod(shape)) for entries in layout
                   for _, shape, _, col in entries if col is not None)
    params = np.empty((b, ncol), dtype=np.int32)
    params[:, 0] = h
    params[:, 1] = w
    wide = []
    for i, entries in enumerate(layout):
        for key, shape, dtype, col in entries:
            v = np.stack([p.stages[i].dyn[key] for p in plans]).astype(
                dtype, copy=False)
            if col is None:
                wide.append(v)
            else:
                params[:, col:col + v[0].size] = v.reshape(b, -1).view(np.int32)
    return params, tuple(wide), layout


def unpack_operands(params, wide, layout) -> tuple:
    """(h, w, dyns) out of pack_operands' arrays, traced or eager: the
    same shapes, dtypes and bits a per-key jnp.asarray of the stacked
    values gives."""
    b = params.shape[0]
    wide = iter(wide)
    dyns = []
    for entries in layout:
        d = {}
        for key, shape, dtype, col in entries:
            if col is None:
                d[key] = next(wide)
                continue
            v = params[:, col:col + int(np.prod(shape))]
            if dtype != "int32":
                v = jax.lax.bitcast_convert_type(v, jnp.dtype(dtype))
            d[key] = v.reshape((b,) + shape)
        dyns.append(d)
    return params[:, 0], params[:, 1], tuple(dyns)


# Arrays launches put host->device, counted per thread: the executor books
# the difference across its own launch_batch call (stats.launch_puts).
_PUTS = threading.local()


def thread_puts() -> int:
    return getattr(_PUTS, "n", 0)


def _count_puts(n: int) -> None:
    _PUTS.n = thread_puts() + n


# Mesh topology generation, bumped by the executor whenever the healthy
# device set changes (quarantine or re-admission rebuilds the serving
# mesh). Part of every SHARDED compile-cache key: two degraded meshes of
# the same SHAPE but different surviving devices would otherwise share a
# key, and jax's internal recompile for the new device set would be
# booked as a warm cost-model sample — the exact mis-attribution ADVICE
# r2 fixed for resharded relaunches. With the generation in the key,
# chip loss recompiles ONCE per topology epoch (a detectable cache-size
# bump), not silently per request. Stays 0 forever on the parity path.
_MESH_GEN = 0


def set_mesh_generation(gen: int) -> None:
    global _MESH_GEN
    _MESH_GEN = int(gen)


def mesh_generation() -> int:
    return _MESH_GEN


def _sharding_cache_key(sharding):
    """Hashable descriptor of an input sharding. Part of the compile-cache
    key so the FIRST launch of a (signature, sharding) pair registers as a
    cache-size bump: the executor's cold-compile detector reads that bump,
    and a resharded relaunch recompiles inside jax.jit — without this it
    would be booked as a warm cost-model sample (ADVICE r2). Carries the
    mesh generation (set_mesh_generation) so each topology epoch keys —
    and recompiles — exactly once."""
    if sharding is None:
        return None
    try:
        return (
            tuple(sharding.mesh.axis_names),
            tuple(sharding.mesh.devices.shape),
            str(sharding.spec),
            _MESH_GEN,
        )
    except AttributeError:  # non-Named shardings: coarse but safe
        return repr(sharding)


def _device_cache_key(device):
    """Hashable descriptor of an explicit device placement (per-device
    fault-domain routing, engine/executor.py). Part of the compile-cache
    key for the same reason _sharding_cache_key is: the first launch of a
    signature on a NEW device recompiles inside jax.jit, and the
    executor's cold-drain detector must see that as a cache-size bump."""
    if device is None:
        return None
    try:
        return (device.platform, device.id)
    except AttributeError:  # pragma: no cover - exotic device objects
        return repr(device)


def _compiled(specs: tuple, in_shape: tuple, layout: tuple, shard_key=None,
              device_key=None, donate: bool = False):
    key = (specs, in_shape, layout, shard_key, device_key, donate)
    fn = _CACHE.get(key)
    if fn is None:
        with _LOCK:
            fn = _CACHE.get(key)
            if fn is None:
                # donate the batch operand only (argnum 1 of _run_chain):
                # the param operands are bytes-trivial and donating them
                # would invalidate arrays the caller may share across a group
                fn = jax.jit(_run_chain, static_argnums=(0, 4),
                             donate_argnums=(1,) if donate else ())
                _CACHE[key] = fn
    return fn


def cache_size() -> int:
    return len(_CACHE)


def single_is_warm(arr: np.ndarray, plan: ImagePlan, sharding=None,
                   device=None) -> bool:
    """True when a batch-of-one launch of this (chain, bucket) pair would
    hit the compile cache. Used to gate cost-model shadow probes: a probe
    measures the LINK, and paying a fresh XLA compile (minutes on a CPU
    fallback backend) to learn a transfer rate would starve the host it is
    supposed to be protecting."""
    specs = plan.spec_key()
    if not specs:
        return True
    if plan.in_bucket is not None:
        shape = (1,) + arr.shape
    else:
        hb, wb = bucket_shape(arr.shape[0], arr.shape[1])
        shape = (1, hb, wb, arr.shape[2])
    return (specs, shape, operand_layout(plan), _sharding_cache_key(sharding),
            _device_cache_key(device), _DONATE) in _CACHE


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()


def pad_to_bucket(arr: np.ndarray) -> np.ndarray:
    """Zero-pad HWC uint8 to bucket dims."""
    h, w = arr.shape[:2]
    hb, wb = bucket_shape(h, w)
    if (hb, wb) == (h, w):
        return arr
    out = np.zeros((hb, wb, arr.shape[2]), dtype=arr.dtype)
    out[:h, :w] = arr
    return out


def _device_cached_parts(arrs, plans, dc, device=None) -> list:
    """Per-item staged device arrays, served from the device frame cache.

    A hit means the packed input never re-crosses the link; a miss stages
    that one item (booked to the wire ledger) and caches the resident
    buffer under the plan's frame_key. The key carries the packed dims, so
    a cached buffer always matches the batch geometry it joins.

    `device` pins a lane-routed launch: the cache key grows the device
    descriptor (a frame resident on chip K's HBM is useless to chip J's
    launch — jnp.stack would drag it across ICI), misses stage onto that
    chip, and the wire charge is attributed to it. The default path keys
    and stages exactly as before.
    """
    parts = []
    dkey = _device_cache_key(device)
    for a, p in zip(arrs, plans):
        key = p.frame_key if dkey is None else (p.frame_key, dkey)
        dev = dc.get(key)
        if dev is None:
            WIRE.add("h2d", a.nbytes, device=dkey)
            _count_puts(1)
            dev = jax.device_put(a) if device is None \
                else jax.device_put(a, device)
            dc.put(key, dev, a.nbytes)
        parts.append(dev)
    return parts


def launch_batch(arrs: list, plans: list, sharding=None, device=None,
                 device_cache: bool = False):
    """Stage + dispatch one batched device call WITHOUT waiting for it.

    arrs: list of HWC uint8 arrays, all with the same bucket shape and C.
    plans: matching ImagePlans with identical spec_key().
    sharding: optional NamedSharding over the leading batch dim — inputs are
    placed with it and the jitted program partitions over the mesh.
    device: optional explicit jax.Device — inputs are placed there and the
    computation follows them (per-device fault-domain routing; mutually
    exclusive with sharding, which wins when both are given).
    device_cache: opt-in (the lane dispatch path): let a device-pinned
    launch use the device frame cache with per-device keys, so repeats
    with lane affinity skip the H2D entirely. Off by default — the
    legacy failover ladder bypasses the cache for pinned launches, and
    that behavior must stay byte-identical when lanes are off.
    Returns the device output array (uint8, still computing), or None for an
    identity chain. JAX dispatch is async, so host->device transfer and
    compute proceed while the caller pipelines further batches; pair with
    fetch_batch (ideally on a dedicated thread — device->host readback is
    the link's scarce, serialize-me resource).
    """
    specs = plans[0].spec_key()
    if not specs:
        return None
    dev_parts = None
    if plans[0].in_bucket is not None:
        # packed-transport items arrive pre-padded to the bucket (the native
        # decoder writes straight into the packed layout); the image dims
        # are NOT the array dims, they ride on the plan
        dc = _DEVICE_FRAMES
        if (dc is not None and dc.enabled and sharding is None
                and (device is None or device_cache)
                and all(p.frame_key is not None for p in plans)):
            dev_parts = _device_cached_parts(arrs, plans, dc, device=device)
        batch = None if dev_parts is not None else np.stack(arrs)
        in_shape = (len(arrs),) + tuple(arrs[0].shape)
        h = np.array([p.in_h for p in plans], dtype=np.int32)
        w = np.array([p.in_w for p in plans], dtype=np.int32)
    else:
        batch = np.stack([pad_to_bucket(a) for a in arrs])
        in_shape = batch.shape
        h = np.array([a.shape[0] for a in arrs], dtype=np.int32)
        w = np.array([a.shape[1] for a in arrs], dtype=np.int32)
    params, wide, layout = pack_operands(plans, h, w)
    # The pixels (unless the frame cache holds them), the packed params and
    # any wide values go host->device in ONE device_put: one shard_args
    # per launch instead of one per operand. Explicit on EVERY path, the
    # default device included: the H2D copy is issued asynchronously from
    # the calling thread — the executor's collector — so staging chunk N+1
    # overlaps compute of chunk N and the fetcher's D2H of chunk N-1. A
    # pinned `device` takes the whole call there: jit follows the
    # operands' placement, so a quarantine-routed batch never touches the
    # sick chip it was steered away from.
    host = ([] if batch is None else [batch]) + [params, *wide]
    place = device
    if sharding is not None:
        # `sharding` may partition more than the batch axis (spatial
        # W-sharding for huge buckets). The param operands shard on the
        # batch axis only.
        vec_sharding = sharding
        from jax.sharding import NamedSharding, PartitionSpec

        if isinstance(sharding, NamedSharding) and len(sharding.spec) > 1:
            vec_sharding = NamedSharding(sharding.mesh, PartitionSpec(sharding.spec[0]))
        place = [vec_sharding] * len(host)
        if batch is not None:
            place[0] = sharding
    staged = jax.device_put(host, place)
    _count_puts(len(host))
    if batch is None:
        # device-cached parts skip the link entirely: jnp.stack of resident
        # arrays runs on-device and its output is a fresh buffer, so
        # donation stays aliasing-safe and the cached per-item arrays are
        # never consumed
        x = jnp.stack(dev_parts)
        params_d, *wide_d = staged
    else:
        # a fresh device buffer over the np.stack copy above, which is what
        # makes donating it aliasing-safe
        WIRE.add("h2d", batch.nbytes,
                 device="mesh" if sharding is not None
                 else _device_cache_key(device))
        x, params_d, *wide_d = staged
    shard_key = _sharding_cache_key(sharding)
    dev_key = _device_cache_key(None if sharding is not None else device)
    fn = _compiled(specs, in_shape, layout, shard_key, dev_key,
                   donate=_DONATE)
    y, _, _ = fn(specs, x, params_d, tuple(wide_d), layout)
    return y


def fetch_groups(ys: list, device=None) -> list:
    """Drain several launch_batch outputs with ONE parallel device_get.

    The link's D2H path has a large fixed cost and benefits from concurrent
    per-buffer streams; device_get on the whole list overlaps them.
    Entries may be None (identity chains) and pass through unchanged.
    `device` only attributes the wire charge (per-lane D2H accounting) —
    the buffers already live where their launch placed them.
    """
    live = [y for y in ys if y is not None]
    if live:
        WIRE.add("d2h", sum(int(y.nbytes) for y in live), device=device)
        fetched = iter(jax.device_get(live))
        return [np.asarray(next(fetched)) if y is not None else None for y in ys]
    return [None] * len(ys)


def finish_batch(host_y, arrs: list, plans: list) -> list:
    """Slice per-image outputs out of a fetched (host) batch array.

    Slices are copied: a view would pin the whole fetched group buffer
    (up to max_group padded images) for as long as any single consumer
    holds its output, and encoders want contiguous data anyway.

    yuv420-transport plans return YuvPlanes (Y/U/V arrays sliced out of the
    packed layout) — the raw JPEG encoder consumes them directly.
    """
    if host_y is None:
        return [np.asarray(a) for a in arrs]
    if getattr(plans[0], "egress", "") == "dct":
        # compressed-domain egress: the chain ended in ToDctSpec, so the
        # fetched buffer holds quantized int16 coefficient planes in the
        # yuv420 packed layout. Re-block into MCU grids here; the host
        # entropy encoder (codecs/jpeg_dct.encode_quantized) drains them.
        from imaginary_tpu.codecs.jpeg_dct import unpack_dct_egress

        out = []
        for i, p in enumerate(plans):
            hb, wb = p.out_bucket
            out.append(
                unpack_dct_egress(host_y[i], p.out_h, p.out_w, hb, wb,
                                  p.egress_quality))
        return out
    if plans[0].transport in ("yuv420", "dct"):
        # dct chains end in the same ToYuv420Spec repack, so both packed
        # transports slice planes out of the identical layout
        from imaginary_tpu.codecs import unpack_planes

        return [
            unpack_planes(host_y[i], p.out_h, p.out_w, *p.out_bucket)
            for i, p in enumerate(plans)
        ]
    return [np.ascontiguousarray(host_y[i, : p.out_h, : p.out_w]) for i, p in enumerate(plans)]


def fetch_batch(y, arrs: list, plans: list) -> list:
    """Block on a launch_batch result and slice out per-image outputs."""
    if y is None:
        return [np.asarray(a) for a in arrs]
    WIRE.add("d2h", int(y.nbytes))
    return finish_batch(np.asarray(jax.device_get(y)), arrs, plans)


def run_batch(arrs: list, plans: list, sharding=None, device=None) -> list:
    """Synchronous convenience: launch + fetch in one call. `device`
    pins the launch (the executor's OOM bisect-retry relaunches halves
    on the SAME device the full batch overflowed — the failure was
    capacity, not the chip, so moving would only spread the pressure)."""
    return fetch_batch(
        launch_batch(arrs, plans, sharding=sharding, device=device),
        arrs, plans)


# Substrings that identify an allocator/HBM exhaustion in the zoo of
# exceptions the device runtime can raise: jaxlib surfaces XLA's status
# as XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory ..."), the CPU
# fallback raises plain MemoryError from numpy staging, and the
# device.oom chaos site mints FailpointErrors named for itself.
_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory",
                "failed to allocate", "device.oom")


def is_oom_error(e: BaseException) -> bool:
    """True when an exception reads as memory exhaustion rather than a
    chip/link fault. The executor routes these to bisect-retry (a
    capacity event) instead of the per-device breaker (a fault event):
    half the batch usually fits, and quarantining a healthy chip for an
    oversized launch would turn a sizing problem into an outage."""
    if isinstance(e, MemoryError):
        return True
    s = str(e).lower()
    return any(m in s for m in _OOM_MARKERS)


def run_single(arr: np.ndarray, plan: ImagePlan) -> np.ndarray:
    """Single-image convenience wrapper (tests, sync path)."""
    return run_batch([arr], [plan])[0]


def output_checksum(out) -> int:
    """Order-sensitive CRC32 over a staged output's bytes (an ndarray or
    YuvPlanes), for the output-integrity layer: two devices running the
    SAME compiled program on the same input are expected bit-identical,
    so chip-vs-chip cross-verification and the golden-probe telemetry
    compare these. Host-vs-device comparisons must NOT use it — the host
    interpreter is PSNR-equivalent, not bit-identical (see
    engine/integrity.outputs_match's tolerance path). CRC32, not a
    cryptographic hash: the adversary is a flaky multiplier, not an
    attacker, and this runs per sampled production batch."""
    import zlib

    if out is None:
        return 0
    if isinstance(out, np.ndarray):
        return zlib.crc32(np.ascontiguousarray(out).tobytes())
    planes = [getattr(out, k, None) for k in ("y", "u", "v")]
    crc = 0
    for p in planes:
        if p is not None:
            crc = zlib.crc32(np.ascontiguousarray(p).tobytes(), crc)
    return crc
