"""Host SIMD spill backend: correctness vs the device path, and the
executor's cost-model placement policy (engine/host_exec.py, executor.py)."""

import numpy as np
import pytest

from imaginary_tpu.engine import Executor, ExecutorConfig, host_exec
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain
from imaginary_tpu.ops.plan import plan_operation


from tests.conftest import psnr as _psnr


@pytest.fixture(scope="module")
def img():
    rng = np.random.default_rng(42)
    # smooth-ish content: kernel differences on pure noise are worst-case
    base = rng.integers(0, 256, (34, 60, 3), np.uint8)
    big = np.kron(base, np.ones((8, 8, 1), np.uint8))[:270, :480]
    return np.ascontiguousarray(big)


CASES = [
    ("resize", ImageOptions(width=300, height=200)),
    ("crop", ImageOptions(width=100, height=120)),
    ("fit", ImageOptions(width=200, height=200)),
    ("extract", ImageOptions(top=10, left=20, area_width=200, area_height=100)),
    ("flip", ImageOptions()),
    ("flop", ImageOptions()),
    ("rotate", ImageOptions(rotate=90)),
    ("blur", ImageOptions(sigma=2.0)),
    ("zoom", ImageOptions(factor=2)),
    # pure enlarge and mixed shrink/enlarge: the separable precomputed-tap
    # resample paths (native or numpy taps), graded against the device
    ("enlarge", ImageOptions(width=600, height=400)),
    ("resize-mixed", ImageOptions(width=600, height=100, force=True)),
]


@pytest.mark.parametrize("name,o", CASES, ids=[c[0] for c in CASES])
def test_host_matches_device(img, name, o):
    name = name.split("-")[0]  # "resize-mixed" is a resize with mixed axes
    plan = plan_operation(name, o, img.shape[0], img.shape[1], 1, 3)
    assert host_exec.can_execute(plan)
    hy = host_exec.run(img, plan)
    dy = chain.run_single(img, plan)
    assert hy.shape == dy.shape
    assert _psnr(hy, dy) > 28.0, f"{name}: host/device divergence too large"


class TestSeparableResample:
    """The spill path's resampler: precomputed-tap numpy fallback and the
    native SIMD entry point (when buildable), both graded against the
    dense device-port math they replaced."""

    def _dense_reference(self, x, dh, dw, kernel):
        # the pre-rewrite dense sampling-matrix port, kept here as the
        # oracle: same weights as ops/stages.sample_matrix
        f = x.astype(np.float32)

        def mat(out_n, in_n, kind):
            y = np.arange(out_n, dtype=np.float32)[:, None]
            k = np.arange(in_n, dtype=np.float32)[None, :]
            scale = out_n / in_n
            centre = (y + 0.5) / scale - 0.5
            stretch = max(1.0, 1.0 / scale)
            wts = host_exec._np_kernel(kind, (k - centre) / stretch)
            norm = wts.sum(axis=-1, keepdims=True)
            return np.where(norm > 1e-6, wts / np.maximum(norm, 1e-6), 0.0)

        t = np.einsum("yk,kwc->ywc", mat(dh, f.shape[0], kernel), f)
        return np.einsum("xw,ywc->yxc", mat(dw, f.shape[1], kernel), t)

    GEOMS = [(120, 300, "lanczos3"), (400, 90, "cubic"), (301, 481, "linear"),
             (500, 600, "lanczos3"), (33, 77, "nearest"), (90, 120, "lanczos2")]

    def test_numpy_taps_match_dense_port(self, img):
        for dh, dw, kernel in self.GEOMS:
            ref = np.clip(self._dense_reference(img, dh, dw, kernel) + 0.5,
                          0, 255).astype(np.uint8)
            got = np.clip(host_exec._np_resize(img, dh, dw, kernel) + 0.5,
                          0, 255).astype(np.uint8)
            assert got.shape == ref.shape
            diff = np.abs(ref.astype(int) - got.astype(int)).max()
            assert diff <= 1, f"{dh}x{dw} {kernel}: maxdiff {diff}"

    @pytest.fixture(scope="class")
    def native_resize(self):
        from imaginary_tpu.codecs import native_backend

        if not native_backend.resample_available():
            try:
                from imaginary_tpu.native.build import build_resample

                build_resample(verbose=False)
            except Exception as e:
                pytest.skip(f"native resample build failed: {e}")
            import importlib

            importlib.reload(native_backend)
            if not native_backend.resample_available():
                pytest.skip("native resampler unavailable after build")
        return native_backend.resize_separable

    def test_native_matches_numpy_taps(self, img, native_resize):
        for dh, dw, kernel in self.GEOMS:
            ref = np.clip(host_exec._np_resize(img, dh, dw, kernel) + 0.5,
                          0, 255).astype(np.uint8)
            got = native_resize(img, dh, dw, kernel)
            assert got.shape == ref.shape
            diff = np.abs(ref.astype(int) - got.astype(int)).max()
            assert diff <= 1, f"{dh}x{dw} {kernel}: maxdiff {diff}"

    def test_native_concurrent_calls_consistent(self, img, native_resize):
        # the entry point releases the GIL; hammer it from threads and
        # check every result is identical to the serial answer
        import threading

        ref = native_resize(img, 190, 333, "lanczos3")
        errs = []

        def worker():
            for _ in range(5):
                out = native_resize(img, 190, 333, "lanczos3")
                if not np.array_equal(out, ref):
                    errs.append("divergent result under concurrency")

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs

    def test_fallback_when_native_absent(self, img, monkeypatch):
        # simulate a host where no native module built: the interpreter
        # must serve identically-shaped output via the numpy taps
        monkeypatch.setattr(host_exec, "_NATIVE_RESAMPLE", False)
        o = ImageOptions(width=600, height=400)
        plan = plan_operation("enlarge", o, img.shape[0], img.shape[1], 1, 3)
        hy = host_exec.run(img, plan)
        dy = chain.run_single(img, plan)
        assert hy.shape == dy.shape
        assert _psnr(hy, dy) > 28.0

    def test_tap_tables_are_cached(self):
        host_exec._tap_table.cache_clear()
        host_exec._np_resize(np.zeros((50, 60, 3), np.uint8), 20, 30, "cubic")
        host_exec._np_resize(np.zeros((50, 60, 3), np.uint8), 20, 30, "cubic")
        info = host_exec._tap_table.cache_info()
        assert info.misses == 2  # one per axis
        assert info.hits == 2  # second call reused both


def test_smartcrop_never_spills(img):
    o = ImageOptions(width=64, height=64)
    plan = plan_operation("smartcrop", o, img.shape[0], img.shape[1], 1, 3)
    # interpretable on host (full-host deployments)...
    assert host_exec.can_execute(plan, for_spill=False)
    # ...but excluded from load-dependent placement: the crop window must
    # not depend on link pressure
    assert not host_exec.can_execute(plan, for_spill=True)


def test_spill_triggers_when_device_saturated(img):
    from imaginary_tpu.engine.executor import last_placement, reset_placement

    ex = Executor(ExecutorConfig(host_spill=True, spill_factor=1.0))
    try:
        # simulate a measured slow link: 1s per item drain
        ex._device_ms_per_mb = 10000.0
        o = ImageOptions(width=64, height=48)
        plan = plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3)
        reset_placement()
        out = ex.process(img, plan)
        assert out.shape == (48, 64, 3)
        assert ex.stats.spilled == 1
        assert ex.stats.items == 0  # never reached the device queue
        assert last_placement() == "host"  # X-Imaginary-Backend source
    finally:
        ex.shutdown()


def test_cost_model_is_size_aware(img):
    """Placement estimates are per-unit (wire MB / source Mpix): a 4K-class
    item carries a ~600x larger wait/cost footprint than a thumbnail-class
    one, and a 4K item sitting in the device queue delays a small follower
    by ITS byte count — one global per-item EWMA could express neither
    (r4: the 4K pipeline route was mis-costed by exactly this)."""
    ex = Executor(ExecutorConfig(host_spill=True, probe_interval=10**9))
    try:
        from imaginary_tpu.engine.executor import _Item

        o = ImageOptions(width=64, height=48)
        small = _Item(img, plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3))
        big_arr = np.zeros((2160, 3840, 3), np.uint8)
        big = _Item(big_arr, plan_operation("resize", ImageOptions(width=1280),
                                            2160, 3840, 0, 3))
        assert big.wire_mb > 50 * small.wire_mb  # 270x480 vs 4K source
        assert big.mpix > 50 * small.mpix
        # slow-link-class rates: both sizes prefer the host...
        ex._device_ms_per_mb = 33.0
        ex._host_ms_per_mpix = 8.0
        assert ex._should_spill(big)
        assert ex._should_spill(small)
        # ...PCIe-class rates: neither spills...
        ex._device_ms_per_mb = 0.05
        assert not ex._should_spill(big)
        assert not ex._should_spill(small)
        # ...and one queued 4K item's estimated MILLISECONDS (not its item
        # count) are what push a small follower over the spill threshold
        assert not ex._should_spill(small)
        ex._device_ms_per_mb = 1.0
        ex._owed_ms = big.wire_mb * 1.0  # a queued 4K item's worth
        assert ex._should_spill(small)
        ex._owed_ms = small.wire_mb * 1.0  # same queue LENGTH, tiny bytes
        assert not ex._should_spill(small)
    finally:
        ex._owed_ms = 0.0
        ex.shutdown()


def test_shadow_probes_rate_limited_by_wall_clock(img):
    """The probe count gate is backed by probe_min_interval_s: on a 1-CPU
    host each shadow's H2D staging steals ~20 ms from whatever request it
    coincides with (measured as the latency bench's remaining p99
    stragglers), so within one interval at most ONE shadow ships no
    matter how many count slots pass — and stale-but-CHEAP slots must not
    feed the 16-slot ungated escape (that would re-open the very cadence
    the gate closes, minus its budget/warmth safety checks)."""
    from imaginary_tpu.ops import chain as chain_mod

    o = ImageOptions(width=64, height=48)
    plan = plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3)
    chain_mod.run_single(img, plan)  # warm: the cheap gate checks the cache
    # spill_factor ~0 forces every request to spill while the small rate
    # keeps the probe well under probe_budget_ms — the cheap path is
    # genuinely open and ONLY the wall clock blocks it
    ex = Executor(ExecutorConfig(host_spill=True, spill_factor=0.001,
                                 probe_interval=2, probe_min_interval_s=3600.0))
    try:
        ex._device_ms_per_mb = 10.0
        ex._drain_floor_ms = 5.0
        for _ in range(40):
            ex.process(img, plan)
        assert ex.stats.spilled == 40
        # 20 count slots: the first ships (never probed before), the other
        # 19 are cheap+stale -> blocked, and they must NOT accumulate into
        # the escape (19 > 16 would ship a second, ungated, shadow)
        assert ex.stats.shadow_probes == 1
        # skipped==0 proves the ship rode the CHEAP path (budget+warmth
        # open) — an escape-path ship would leave a nonzero residue
        assert ex._probe_slots_skipped == 0
    finally:
        ex.shutdown()


def test_host_occupancy_backpressures_spill(img):
    """The host side of the placement comparison includes the pool's
    owed-megapixel backlog (mirroring the device's owed_mb ledger): a
    saturated host pool must push new arrivals back toward the device
    instead of convoying them behind each other — the r5 p99 signature."""
    ex = Executor(ExecutorConfig(host_spill=True, probe_interval=10**9))
    try:
        from imaginary_tpu.engine.executor import _Item

        o = ImageOptions(width=64, height=48)
        item = _Item(img, plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3))
        ex._device_ms_per_mb = 33.0  # slow-link-class: spill preferred...
        ex._host_ms_per_mpix = 8.0
        # a real accelerator (independent silicon): on the cpu-jax test
        # backend the queue term deliberately cancels, so pin the probe
        ex._device_shares_cpu = False
        assert ex._should_spill(item)
        # ...until the host pool itself is saturated: with enough owed
        # megapixels in flight, the estimated host wait dominates
        ex._host_owed_mpix = 1000.0 * ex._ncpus
        assert not ex._should_spill(item)
        ex._host_owed_mpix = 0.0
        assert ex._should_spill(item)
    finally:
        ex.shutdown()


def test_spill_books_and_releases_host_occupancy(img):
    ex = Executor(ExecutorConfig(host_spill=True, spill_factor=1.0,
                                 probe_interval=10**9))
    try:
        ex._device_ms_per_mb = 10000.0
        o = ImageOptions(width=64, height=48)
        plan = plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3)
        ex.process(img, plan)
        assert ex.stats.spilled == 1
        # the ledger balances after completion and the gauges surface it
        assert ex._host_inflight == 0
        assert ex._host_owed_mpix == 0.0
        d = ex.stats.to_dict()
        assert d["host_inflight"] == 0
        assert d["host_owed_mpix"] == 0.0
        assert "host_spill_p50_ms" in d and "host_spill_p99_ms" in d
    finally:
        ex.shutdown()


def test_force_host_pins_placement(img):
    """force_host (the bench's measurement override) routes every
    host-executable plan to the interpreter even when the device is
    unpriced/fast — and books it as a spill."""
    from imaginary_tpu.engine.executor import last_placement, reset_placement

    ex = Executor(ExecutorConfig(force_host=True))
    try:
        o = ImageOptions(width=64, height=48)
        plan = plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3)
        reset_placement()
        out = ex.process(img, plan)
        assert out.shape == (48, 64, 3)
        assert ex.stats.spilled == 1
        assert ex.stats.items == 0
        assert last_placement() == "host"
    finally:
        ex.shutdown()


def test_no_spill_when_device_fast(img):
    from imaginary_tpu.engine.executor import last_placement, reset_placement

    ex = Executor(ExecutorConfig(host_spill=True))
    try:
        ex._device_ms_per_mb = 0.01  # fast PCIe-class link
        o = ImageOptions(width=64, height=48)
        plan = plan_operation("resize", o, img.shape[0], img.shape[1], 1, 3)
        reset_placement()
        out = ex.process(img, plan)
        assert out.shape == (48, 64, 3)
        assert ex.stats.spilled == 0
        assert ex.stats.items == 1
        assert last_placement() == "device"
    finally:
        ex.shutdown()


def test_embed_modes_match_device(img):
    from imaginary_tpu.options import Extend

    small = img[:100, :150]
    for extend in (Extend.MIRROR, Extend.COPY, Extend.WHITE, Extend.BLACK,
                   Extend.BACKGROUND):
        o = ImageOptions(width=300, height=200, embed=True, extend=extend,
                         background=(10, 200, 30))
        o.mark_defined("embed")
        plan = plan_operation("resize", o, 100, 150, 1, 3)
        hy = host_exec.run(small, plan)
        dy = chain.run_single(small, plan)
        assert hy.shape == dy.shape
        assert _psnr(hy, dy) > 28.0, extend


def test_watermark_composite_matches_device(img):
    o = ImageOptions(width=200, text="hello tpu", opacity=0.7)
    plan = plan_operation("watermark", o, img.shape[0], img.shape[1], 1, 3)
    if not host_exec.can_execute(plan):
        pytest.skip("composite not host-executable")
    hy = host_exec.run(img, plan)
    dy = chain.run_single(img, plan)
    assert hy.shape == dy.shape
    assert _psnr(hy, dy) > 25.0
