"""Compile-cache warming + persistence.

The reference is stateless and restart-is-recovery (SURVEY.md section 5.4);
our only restart cost is XLA compilation. Two mitigations:

  1. a persistent XLA compilation cache on disk (jax's native cache), so a
     restarted server reuses every executable it ever built;
  2. optional startup prewarming of the most common (chain, bucket) pairs
     so the first real request never pays a cold compile (SURVEY.md
     section 7 hard-part #1).
"""

from __future__ import annotations

import os
import time

import numpy as np

from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.plan import plan_operation


# One fixed directory inside the checkout: the path is part of what the
# cache is found by, so it never carries a temp name, a pid or a time.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, jax already reads it and this
    sets no directory; otherwise the cache lives at CACHE_DIR."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    try:
        if not path:
            path = CACHE_DIR
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    except Exception:
        # unwritable checkout (container USER nobody, read-only fs): serve
        # without a persistent cache rather than dying before bind
        return ""
    return path


# Golden-probe canary (engine/integrity.py): a fixed synthetic input and
# a REAL resize op-chain — the same separable-resample program production
# requests compile — whose reference output is computed once, on the host
# interpreter, at first use. The old re-admission probe (device_put+add)
# exercised the transfer path only; a chip corrupting its conv/resize
# units passed it while serving garbage. Dims are deliberately small
# (96x128 -> 48x36): the probe runs on quarantined chips at cooldown
# cadence and must stay cheap.
_GOLDEN_H, _GOLDEN_W = 96, 128
_GOLDEN_OUT_W, _GOLDEN_OUT_H = 48, 36


def golden_input() -> np.ndarray:
    """Deterministic SMOOTH gradient (no content discontinuities: the
    host and device resamplers diverge most at hard edges, and the
    golden comparison's tolerance must stay far above honest kernel
    rounding and far below any corrupted byte)."""
    yy, xx = np.mgrid[0:_GOLDEN_H, 0:_GOLDEN_W]
    r = (xx * 255) // max(1, _GOLDEN_W - 1)
    g = (yy * 255) // max(1, _GOLDEN_H - 1)
    b = ((xx + yy) * 255) // max(1, _GOLDEN_H + _GOLDEN_W - 2)
    return np.stack([r, g, b], axis=-1).astype(np.uint8)


def golden_case() -> tuple:
    """(input, plan, host_reference): the canary computed once at boot
    on the HOST (engine/integrity.golden caches it). The host output is
    ground truth — it never transits the hardware under suspicion."""
    from imaginary_tpu.engine import host_exec

    arr = golden_input()
    plan = plan_operation(
        "resize", ImageOptions(width=_GOLDEN_OUT_W, height=_GOLDEN_OUT_H),
        _GOLDEN_H, _GOLDEN_W, 0, 3)
    return arr, plan, host_exec.run(arr, plan)


# (operation, options, source dims) matrix covering the hot routes at the
# common source sizes; extend as real traffic data accumulates.
_COMMON = [
    ("resize", ImageOptions(width=300), (1080, 1920)),
    ("resize", ImageOptions(width=300, height=200), (1080, 1920)),
    ("thumbnail", ImageOptions(width=100), (1080, 1920)),
    ("crop", ImageOptions(width=300, height=260), (1080, 1920)),
    ("resize", ImageOptions(width=300), (740, 550)),
    ("fit", ImageOptions(width=300, height=300), (740, 550)),
]


def prewarm_common_chains(batch_sizes=None, verbose: bool = True) -> int:
    """Compile the common chain matrix; returns number of programs built.

    Two production realities shape what gets warmed:
      - the executor pads micro-batches to powers of two, so every ladder
        size up to max_batch is its own XLA program — warming only b=1
        leaves the first loaded minute paying three more compiles per
        chain (the latency harness measured those stalls snowballing an
        open-loop queue);
      - JPEG requests decode at the proven shrink-on-load fraction, so the
        bucket production actually serves is the SHRUNK one, not the full
        source dims.
    """
    from imaginary_tpu.engine.executor import batch_ladder

    if batch_sizes is None:
        env = os.environ.get("IMAGINARY_TPU_PREWARM_BATCHES", "")
        if env:
            try:
                batch_sizes = tuple(int(x) for x in env.split(",") if x.strip())
            except ValueError:
                batch_sizes = batch_ladder()  # degrade, never die before bind
        else:
            # derive from the executor's chunk cap so every padded batch
            # size a default deployment can form is compiled before bind
            batch_sizes = batch_ladder()
    built = 0
    seen = set()
    warmed: list = []  # (plan, kind, dh, dw, b) that compiled+ran clean
    t0 = time.time()
    for op, opts, (h, w) in _COMMON:
        built += warm_chain(op, opts, h, w, batch_sizes,
                            seen=seen, warmed=warmed)
    seeded = _seed_link_rate(warmed)
    if verbose:
        msg = f"prewarmed {built} op-chain programs in {time.time() - t0:.1f}s"
        if seeded:
            msg += f"; link seeded at {seeded[0]:.2f} ms/MB (floor {seeded[1]:.1f} ms)"
        print(msg)
    return built


def warm_chain(op: str, opts: ImageOptions, h: int, w: int,
               batch_sizes, seen=None, warmed=None) -> int:
    """Compile-and-run every device program one (operation, options,
    source dims) combination can hit: the full bucket (PNG/WebP traffic
    decodes full-size) AND the shrink-on-load bucket JPEG traffic actually
    serves, the RGB and (when the native codec is present) packed-YUV420
    transports, at every requested batch-ladder rung. Returns the number
    of programs built. Shared by boot prewarm (prewarm_common_chains) and
    by bench_device.py's policy A/B row, which warms exactly its own
    chain through this function and then asserts the executor's
    compile_misses counter stays 0 for the whole run."""
    from imaginary_tpu.ops.plan import choose_decode_shrink

    if seen is None:
        seen = set()
    built = 0
    try:
        shrink = choose_decode_shrink(op, opts, h, w, 0, 3)
    except Exception:
        shrink = 1
    # map decode dims -> the shrink that produced them: the dct transport
    # compiles a DIFFERENT program per (bucket, shrink) because the fold
    # factor k = 8//shrink is baked into the FromDctSpec shapes
    dim_shrink = {(h, w): 1}
    dim_shrink.setdefault(
        ((h + shrink - 1) // shrink, (w + shrink - 1) // shrink), shrink)
    try:
        from imaginary_tpu import codecs as _codecs

        warm_yuv = _codecs.yuv420_supported()
    except Exception:
        warm_yuv = False
    try:
        from imaginary_tpu import pipeline as pipeline_mod

        warm_dct = pipeline_mod.transport_dct_enabled()
    except Exception:
        warm_dct = False
    for (dh, dw), dshrink in dim_shrink.items():
        try:
            plan = plan_operation(op, opts, dh, dw, 0, 3)
        except Exception:
            continue
        plans = [(plan, None)]
        if warm_yuv and plan.stages:
            # JPEG traffic serves over the packed-YUV420 transport: warm
            # that chain too, with a pre-padded packed dummy input
            from imaginary_tpu.ops.plan import wrap_plan_yuv420

            plans.append((wrap_plan_yuv420(plan, dh, dw), "yuv"))
        if warm_dct and plan.stages and dshrink in (1, 2, 4, 8):
            # compressed-domain transport: the device runs IDCT + color
            # convert on packed int16 coefficients (ops FromDctSpec)
            from imaginary_tpu.ops.plan import wrap_plan_dct

            plans.append((wrap_plan_dct(plan, h, w, dshrink), "dct"))
            try:
                from imaginary_tpu import pipeline as pipeline_mod

                warm_egress = pipeline_mod.transport_dct_egress_enabled()
            except Exception:
                warm_egress = False
            if warm_egress:
                # egress chains end in ToDctSpec instead of ToYuv420Spec —
                # a distinct program per chain. Quality rides as dyn
                # (quantizer tables), so one warm covers every quality.
                plans.append((wrap_plan_dct(plan, h, w, dshrink,
                                            egress="dct", egress_quality=80),
                              "dct"))
        for pl, kind in plans:
            for b in batch_sizes:
                key = (pl.spec_key(), chain_mod.bucket_shape(dh, dw), b)
                if key in seen:
                    continue
                seen.add(key)
                try:
                    arr = _dummy_input(pl, kind, dh, dw)
                    chain_mod.run_batch([arr] * b, [pl] * b)
                    built += 1
                    if warmed is not None:
                        warmed.append((pl, kind, dh, dw, b))
                except Exception:
                    continue
    return built


def warm_mesh_paths(ex, op: str, opts: ImageOptions, h: int, w: int,
                    batch_sizes=None) -> int:
    """Warm the LANE TIER's compile keys for one (op, options, dims)
    combination on an executor with mesh_policy armed: the per-device
    placement keys (one per lane — pinned launches key the compile cache
    on _device_cache_key), the batch-axis sharded keys at every
    mesh-multiple rung, and the oversize-single spatial key when that
    route is live. Run AFTER warm_chain covers the unpinned keys; with
    both, stats.compile_misses stays 0 across a multi-chip run exactly
    as the single-lane prewarm contract promises (bench_device.py's mesh
    A/B row asserts it on both arms). A topology change recompiles once
    per shape by design — the mesh generation is part of the sharded
    key, and warming future generations is unknowable. Returns the
    number of programs built."""
    from imaginary_tpu.engine.executor import batch_ladder

    if getattr(ex, "_lanes", None) is None:
        return 0
    if batch_sizes is None:
        batch_sizes = batch_ladder()
    try:
        plan = plan_operation(op, opts, h, w, 0, 3)
    except Exception:
        return 0
    if not plan.stages:
        return 0
    arr = np.zeros((h, w, 3), dtype=np.uint8)
    before = chain_mod.cache_size()
    for ln in ex._lanes.lanes:
        for b in batch_sizes:
            try:
                chain_mod.run_batch([arr] * b, [plan] * b, device=ln.device)
            # itpu: allow[ITPU004] prewarm degrades, never dies before bind
            except Exception:
                continue
    if ex._lane_sharding is not None:
        m = max(1, ex._lane_mesh_batch)
        seen_t = set()
        for b in batch_sizes:
            t = ((b + m - 1) // m) * m
            if t in seen_t:
                continue
            seen_t.add(t)
            try:
                chain_mod.run_batch([arr] * t, [plan] * t,
                                    sharding=ex._lane_sharding)
            # itpu: allow[ITPU004] prewarm degrades, never dies before bind
            except Exception:
                continue
    if ex._spatial_sharding is not None:
        hb, wb = chain_mod.bucket_shape(h, w)
        if (hb * wb >= ex.config.spatial_threshold_px
                and wb % ex._mesh_spatial == 0):
            t = max(1, ex._lane_spatial_batch)
            try:
                chain_mod.run_batch([arr] * t, [plan] * t,
                                    sharding=ex._spatial_sharding)
            # itpu: allow[ITPU004] prewarm degrades, never dies before bind
            except Exception:
                pass
    return chain_mod.cache_size() - before


def _dummy_input(pl, kind, dh, dw) -> np.ndarray:
    if kind == "yuv":
        ph, wb = pl.in_bucket
        return np.zeros((ph, wb, 1), dtype=np.uint8)
    if kind == "dct":
        # full-scale 420/422 pack Y+U+V into one int16 plane (stacked
        # rows) and grayscale is single-plane at any scale; every other
        # (layout, scale) channel-packs Y/U/V folded coefficients — must
        # mirror codecs/jpeg_dct.pack_dct exactly or the warmed jit
        # signature misses
        ph, wb = pl.in_bucket
        spec = pl.stages[0].spec
        layout = getattr(spec, "layout", "420")
        one = layout == "gray" or (layout in ("420", "422") and spec.k == 8)
        return np.zeros((ph, wb, 1 if one else 3), dtype=np.int16)
    return np.zeros((dh, dw, 3), dtype=np.uint8)


def _wire_mb(pl, kind, dh, dw) -> float:
    """Wire megabytes one item of this plan moves across the link —
    priced by the executor's OWN item accounting (_Item.wire_mb), so the
    seed and the EWMA that refines it can never diverge in unit."""
    from imaginary_tpu.engine.executor import _Item

    return _Item(_dummy_input(pl, kind, dh, dw), pl).wire_mb


def _seed_link_rate(warmed: list):
    """Time two already-compiled drains of very different wire sizes and
    install the solved (ms/MB, fixed floor) into the executor module, so
    the first executor created prices the device link from measurement
    instead of assuming it is free (engine/executor.py seed_link_rate).
    Returns the installed (rate, floor) or None."""
    if not warmed:
        return None
    from imaginary_tpu.engine import executor as executor_mod

    cands = [(_wire_mb(pl, kind, dh, dw) * b, pl, kind, dh, dw, b)
             for pl, kind, dh, dw, b in warmed]
    small = min(cands, key=lambda c: c[0])
    big = max(cands, key=lambda c: c[0])
    if big[0] - small[0] < 0.25:  # need spread to fit a slope
        return None

    def timed(c) -> float:
        mb, pl, kind, dh, dw, b = c
        arr = _dummy_input(pl, kind, dh, dw)
        best = float("inf")
        for _ in range(2):  # min-of-2 dodges a one-off GC pause
            t = time.monotonic()
            chain_mod.run_batch([arr] * b, [pl] * b)
            best = min(best, (time.monotonic() - t) * 1000.0)
        return best

    try:
        t_small = timed(small)
        t_big = timed(big)
    except Exception:
        return None  # device died mid-prewarm: serve unseeded
    rate = (t_big - t_small) / (big[0] - small[0])
    if rate <= 0.0:
        # Jitter inverted the slope (a stall on the small candidate's both
        # runs). A 0.0 seed would be a permanent wedge: the EWMA's
        # multiplicative clamps (min(per_mb, 4x prev)) can never escape
        # prev == 0, so the link would be priced free forever. Serve
        # unseeded — the first real drain prices it.
        return None
    floor = max(t_small - small[0] * rate, 0.0)
    executor_mod.seed_link_rate(rate, floor)
    return rate, floor
