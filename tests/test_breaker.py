"""Device circuit breaker (SURVEY.md section 5.3 analogue): after
breaker_threshold CONSECUTIVE failed device dispatches, host-executable
requests fail over to the host interpreter instead of 400-ing one by one;
a device success closes the breaker."""

import numpy as np
import pytest

from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.engine.executor import last_placement, reset_placement
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation


def _img(h=96, w=128, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def _plan(h=96, w=128, width=48):
    return plan_operation("resize", ImageOptions(width=width), h, w, 0, 3)


@pytest.fixture
def broken_device(monkeypatch):
    """Every device launch raises, as a dead link would."""
    from imaginary_tpu.engine import executor as ex_mod

    def boom(*a, **k):
        raise RuntimeError("link down")

    monkeypatch.setattr(ex_mod.chain_mod, "launch_batch", boom)


def test_breaker_opens_after_consecutive_failures(broken_device):
    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                 breaker_threshold=3, breaker_cooldown_s=60))
    try:
        # first three device failures surface to their callers...
        for i in range(3):
            with pytest.raises(Exception):
                ex.process(_img(seed=i), _plan(), timeout=30)
        assert ex.stats.device_failures >= 3
        assert ex.stats.breaker_opens == 1
        # ...then the open breaker serves host-executable plans from the
        # host interpreter, no device attempt, correct pixels
        reset_placement()
        out = ex.process(_img(seed=9), _plan())
        assert out.shape == (36, 48, 3)
        assert ex.stats.breaker_host_served == 1
        assert last_placement() == "host"
    finally:
        ex.shutdown()


def test_breaker_serves_yuv_plans_during_outage(broken_device):
    """Packed-transport plans fail over too: the host interpreter returns
    YuvPlanes the raw encoder can consume."""
    from io import BytesIO

    from PIL import Image

    from imaginary_tpu import codecs
    from imaginary_tpu.ops.buckets import bucket_shape
    from imaginary_tpu.ops.plan import wrap_plan_yuv420

    if not codecs.yuv420_supported():
        pytest.skip("native YUV420 codec not built")
    out = BytesIO()
    Image.fromarray(_img(120, 160)).save(out, "JPEG", quality=85, subsampling=2)
    hb, wb = bucket_shape(120, 160)
    packed, h, w, _ = codecs.decode_yuv420(out.getvalue(), 1, hb, wb)
    wrapped = wrap_plan_yuv420(_plan(120, 160, 80), 120, 160)

    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                 breaker_threshold=2, breaker_cooldown_s=60))
    try:
        for i in range(2):
            with pytest.raises(Exception):
                ex.process(_img(seed=i), _plan(), timeout=30)
        got = ex.process(packed, wrapped)
        assert isinstance(got, codecs.YuvPlanes)
        assert got.y.shape == (60, 80)
        body = codecs.encode_yuv(got, codecs.EncodeOptions())
        assert Image.open(BytesIO(body)).size == (80, 60)
    finally:
        ex.shutdown()


def test_owed_accounting_balances_under_concurrency():
    """The owed-milliseconds ledger (charged at enqueue, released on
    completion) must return to zero after mixed-size concurrent traffic —
    a leak would ratchet the spill policy toward permanent host serving."""
    import threading

    # probes disabled: a shadow's drain may include an XLA compile (minutes
    # on CPU), which would park its charge past any sane polling window —
    # this test is about the ledger of REAL items
    ex = Executor(ExecutorConfig(max_form_ms=2, host_spill=True,
                                 probe_interval=10**9))
    try:
        # seed the device rate: the FIRST drain of a chain key is
        # compile-cold and excluded from the EWMA, so run each shape twice
        import time

        for s in (100, 101):
            ex.process(_img(seed=s), _plan())
            ex.process(_img(192, 256, seed=s), _plan(192, 256))
        for _ in range(100):
            if ex._device_ms_per_mb is not None:
                break
            time.sleep(0.02)
        assert ex._device_ms_per_mb is not None  # charges are non-zero
        errs = []

        def worker(i):
            try:
                h, w = (96, 128) if i % 3 else (192, 256)
                out = ex.process(_img(h, w, seed=i), _plan(h, w, 48 + (i % 5)))
                assert out.shape[1] == 48 + (i % 5)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for _ in range(100):  # last futures may still be resolving
            with ex._owed_lock:
                if abs(ex._owed_ms) < 1e-6:
                    break
            time.sleep(0.05)
        with ex._owed_lock:
            assert abs(ex._owed_ms) < 1e-6
    finally:
        ex.shutdown()


def test_breaker_closes_on_device_success(monkeypatch):
    from imaginary_tpu.engine import executor as ex_mod

    real = ex_mod.chain_mod.launch_batch
    fail = {"on": True}

    def flaky(*a, **k):
        if fail["on"]:
            raise RuntimeError("link down")
        return real(*a, **k)

    monkeypatch.setattr(ex_mod.chain_mod, "launch_batch", flaky)
    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                 breaker_threshold=2, breaker_cooldown_s=0.05))
    try:
        for i in range(2):
            with pytest.raises(Exception):
                ex.process(_img(seed=i), _plan(), timeout=30)
        assert ex.stats.breaker_opens == 1
        fail["on"] = False
        import time

        time.sleep(0.1)  # cooldown expires; next request probes the device
        reset_placement()
        out = ex.process(_img(seed=5), _plan())
        assert out.shape == (36, 48, 3)
        assert last_placement() == "device"
        assert not ex._breaker_is_open()
    finally:
        ex.shutdown()


class TestDrainWatchdog:
    """The breaker's blind spot: a half-dead device link HANGS inside the
    runtime instead of erroring, so no failure is ever booked and queued
    requests ride their full client timeout. The watchdog abandons the stuck drain, fails its futures
    fast, opens the breaker outright, and hands the queue to a fresh
    fetcher; the zombie drain's results are discarded if the call ever
    returns."""

    def test_hung_drain_abandoned_breaker_opens_and_host_serves(self, monkeypatch):
        import threading

        from imaginary_tpu.engine import executor as ex_mod

        release = threading.Event()
        hung = threading.Event()

        real_fetch = ex_mod.chain_mod.fetch_groups
        calls = {"n": 0}

        def hang_once(groups):
            calls["n"] += 1
            if calls["n"] == 1:
                hung.set()
                release.wait(timeout=30)  # blocked "forever" (test-bounded)
            return real_fetch(groups)

        monkeypatch.setattr(ex_mod.chain_mod, "fetch_groups", hang_once)
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     drain_watchdog_s=0.5,
                                     breaker_cooldown_s=60))
        try:
            fut = ex.submit(_img(), _plan())
            assert hung.wait(timeout=30)  # the drain is now stuck
            with pytest.raises(RuntimeError, match="watchdog"):
                fut.result(timeout=30)  # failed FAST, not at client timeout
            assert ex.stats.breaker_opens == 1
            assert ex.stats.device_failures >= 1
            # host-executable traffic now fails over immediately
            reset_placement()
            out = ex.process(_img(seed=1), _plan(), timeout=30)
            assert out.shape[0] > 0
            assert last_placement() == "host"
            assert ex.stats.breaker_host_served == 1
            # zombie unblocks: its results are discarded without incident,
            # and the replacement fetcher keeps serving once the breaker
            # cooldown is behind us (simulate by closing it)
            release.set()
            with ex._owed_lock:
                ex._breaker_open_until = 0.0
                ex._consec_device_failures = 0
            out2 = ex.process(_img(seed=2), _plan(), timeout=30)
            assert out2.shape[0] > 0
            assert calls["n"] >= 2  # replacement fetcher drained it
        finally:
            release.set()
            ex.shutdown()

    def test_groups_queued_behind_hung_drain_fail_fast(self, monkeypatch):
        import threading

        from imaginary_tpu.engine import executor as ex_mod

        release = threading.Event()
        calls = {"n": 0}

        def hang(groups):
            # only the FIRST drain hangs; any group the collector was
            # still holding when the watchdog drained the queue lands on
            # the REPLACEMENT fetcher, which must fail it fast, not block
            calls["n"] += 1
            if calls["n"] == 1:
                release.wait(timeout=30)
            raise RuntimeError("late failure")

        monkeypatch.setattr(ex_mod.chain_mod, "fetch_groups", hang)
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     drain_watchdog_s=0.5,
                                     breaker_cooldown_s=60))
        try:
            futs = [ex.submit(_img(seed=i), _plan()) for i in range(3)]
            for f in futs:
                with pytest.raises(RuntimeError):
                    f.result(timeout=30)
        finally:
            release.set()
            ex.shutdown()
