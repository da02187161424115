"""Compile-cache warming (prewarm.py): ladder + shrink-bucket coverage."""

import os

import numpy as np

from imaginary_tpu.options import ImageOptions


def test_prewarm_ladder_and_shrink_bucket(monkeypatch):
    """Prewarm compiles every requested batch size, at the SHRUNK decode
    dims production serves (not the full source dims), deduped by
    (chain, bucket, batch)."""
    from imaginary_tpu import prewarm
    from imaginary_tpu.ops import chain as chain_mod
    from imaginary_tpu.ops.plan import choose_decode_shrink

    monkeypatch.setattr(
        prewarm, "_COMMON", [("resize", ImageOptions(width=24), (64, 96))]
    )
    before = chain_mod.cache_size()
    n = prewarm.prewarm_common_chains(batch_sizes=(1, 2), verbose=False)
    # both the full bucket (PNG/WebP traffic) and the shrink-on-load bucket
    # (JPEG traffic) are warmed, per batch size, deduped by (chain, bucket, b);
    # when the native raw codec is present the packed-YUV420 transport chain
    # warms alongside each RGB chain
    from imaginary_tpu import codecs

    shrink = choose_decode_shrink("resize", ImageOptions(width=24), 64, 96, 0, 3)
    expected_dims = {(64, 96), ((64 + shrink - 1) // shrink, (96 + shrink - 1) // shrink)}
    transports = 2 if codecs.yuv420_supported() else 1
    assert n == 2 * len(expected_dims) * transports
    assert chain_mod.cache_size() >= before  # programs landed in the cache


def test_prewarm_env_override(monkeypatch):
    from imaginary_tpu import prewarm
    from imaginary_tpu.ops.plan import choose_decode_shrink

    monkeypatch.setattr(
        prewarm, "_COMMON", [("resize", ImageOptions(width=16), (32, 48))]
    )
    from imaginary_tpu import codecs

    shrink = choose_decode_shrink("resize", ImageOptions(width=16), 32, 48, 0, 3)
    dims = {(32, 48), ((32 + shrink - 1) // shrink, (48 + shrink - 1) // shrink)}
    transports = 2 if codecs.yuv420_supported() else 1
    monkeypatch.setenv("IMAGINARY_TPU_PREWARM_BATCHES", "1")
    assert prewarm.prewarm_common_chains(verbose=False) == len(dims) * transports


def test_prewarm_bad_env_degrades(monkeypatch):
    """Malformed batch env must not kill the server before bind."""
    from imaginary_tpu import prewarm

    monkeypatch.setattr(
        prewarm, "_COMMON", [("resize", ImageOptions(width=16), (32, 48))]
    )
    monkeypatch.setenv("IMAGINARY_TPU_PREWARM_BATCHES", "1 2;bogus")
    assert prewarm.prewarm_common_chains(verbose=False) >= 1  # fell back to ladder


def test_seed_link_rate_consumed_by_new_executor(monkeypatch):
    """A prewarm-installed link seed prices the device for executors
    created afterwards: a host-executable item whose estimated device
    wait exceeds spill_factor x host cost spills on the FIRST request —
    no unpriced ride over a slow link (the r4 cold-start wart: a fresh
    server's first requests each ate a full drain the host path serves
    in ~10 ms)."""
    from imaginary_tpu.engine import executor as executor_mod
    from imaginary_tpu.engine.executor import Executor, ExecutorConfig
    from imaginary_tpu.ops.plan import plan_operation

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)
    executor_mod.seed_link_rate(500.0, 40.0)  # a dreadful link: 500 ms/MB
    ex = Executor(ExecutorConfig(host_spill=True))
    try:
        assert ex._device_ms_per_mb == 500.0
        assert ex._drain_floor_ms == 40.0
        arr = np.zeros((256, 384, 3), dtype=np.uint8)
        plan = plan_operation("resize", ImageOptions(width=64), 256, 384, 0, 3)
        out = ex.process(arr, plan, timeout=60)
        assert out.shape[0] > 0
        assert ex.stats.spilled == 1  # priced link -> host, no device ride
        assert ex.stats.items == 0
    finally:
        ex.shutdown()


def test_seed_link_rate_solved_from_warm_drains(monkeypatch):
    """_seed_link_rate times a small and a large warm drain and installs a
    nonnegative (ms/MB, floor) pair."""
    from imaginary_tpu import prewarm
    from imaginary_tpu.engine import executor as executor_mod
    from imaginary_tpu.ops.plan import plan_operation

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)
    small = plan_operation("resize", ImageOptions(width=24), 64, 96, 0, 3)
    big = plan_operation("resize", ImageOptions(width=300), 512, 768, 0, 3)
    got = prewarm._seed_link_rate(
        [(small, None, 64, 96, 1), (big, None, 512, 768, 2)]
    )
    assert got is not None
    rate, floor = got
    assert rate >= 0.0 and floor >= 0.0
    assert executor_mod.link_seed() == (rate, floor)


def test_seed_link_rate_rejects_inverted_slope(monkeypatch):
    """Jitter can time the big drain FASTER than the small one; a 0.0
    seed would wedge the EWMA at 'link is free' forever (multiplicative
    clamps never leave 0), so no seed must install."""
    from imaginary_tpu import prewarm
    from imaginary_tpu.engine import executor as executor_mod
    from imaginary_tpu.ops.plan import plan_operation

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)

    def stalled_small(arrs, pls):
        # deterministic inversion: the SMALL drain (b=1) stalls, the big
        # one returns instantly -> negative slope, guaranteed
        import time as _t

        if len(arrs) == 1:
            _t.sleep(0.02)

    monkeypatch.setattr(prewarm.chain_mod, "run_batch", stalled_small)
    small = plan_operation("resize", ImageOptions(width=24), 64, 96, 0, 3)
    big = plan_operation("resize", ImageOptions(width=300), 512, 768, 0, 3)
    assert prewarm._seed_link_rate(
        [(small, None, 64, 96, 1), (big, None, 512, 768, 2)]
    ) is None  # inverted slope -> unseeded
    assert executor_mod.link_seed() is None


def test_zero_rate_seed_treated_as_unpriced(monkeypatch):
    """Even if seed_link_rate is handed a 0.0 rate directly, a new
    executor must treat the link as unpriced, not free."""
    from imaginary_tpu.engine import executor as executor_mod
    from imaginary_tpu.engine.executor import Executor, ExecutorConfig

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)
    executor_mod.seed_link_rate(0.0, 5.0)
    ex = Executor(ExecutorConfig(host_spill=True))
    try:
        assert ex._device_ms_per_mb is None
    finally:
        ex.shutdown()


def test_seed_link_rate_skips_degenerate_spread(monkeypatch):
    """Two near-identical wire sizes cannot fit a slope: no seed installed."""
    from imaginary_tpu import prewarm
    from imaginary_tpu.engine import executor as executor_mod
    from imaginary_tpu.ops.plan import plan_operation

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)
    pl = plan_operation("resize", ImageOptions(width=24), 64, 96, 0, 3)
    assert prewarm._seed_link_rate([(pl, None, 64, 96, 1)]) is None
    assert executor_mod.link_seed() is None


def _record_config(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    process's compiles must not start writing to a persistent cache."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_persistent_cache_degrades_on_unwritable(monkeypatch):
    """chmod can't stop root, so simulate the read-only fs directly."""
    from imaginary_tpu import prewarm

    def boom(*a, **k):
        raise PermissionError("read-only file system")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config(monkeypatch)
    monkeypatch.setattr(prewarm.os, "makedirs", boom)
    assert prewarm.enable_persistent_cache() == ""  # degrade, not die
    assert calls == []


def test_persistent_cache_honours_jax_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is jax's own: the code sets no directory
    and keeps only the min-compile-time setting."""
    from imaginary_tpu import prewarm

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    calls = _record_config(monkeypatch)
    assert prewarm.enable_persistent_cache() == str(tmp_path / "xla")
    assert calls == [("jax_persistent_cache_min_compile_time_secs", 0.5)]


def test_persistent_cache_fixed_checkout_path(monkeypatch):
    """Unset, the cache is one fixed directory inside the checkout — the
    same on every call, with no temp name, pid or time in it."""
    from imaginary_tpu import prewarm

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config(monkeypatch)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = prewarm.enable_persistent_cache()
    assert first == os.path.join(root, ".jax_cache")
    assert prewarm.enable_persistent_cache() == first
    assert calls.count(("jax_compilation_cache_dir", first)) == 2
