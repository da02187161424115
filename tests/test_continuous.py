"""Continuous batching + buffer donation (ISSUE 9).

Pins the three contracts the device-path overhaul added:
  * continuous admission — an item submitted while a chunk is in flight
    forms (and launches) the NEXT chunk instead of queueing behind the
    full drain;
  * donation aliasing safety — the jitted chain donates only the fresh
    staged batch buffer, never a caller-owned (frame-cache-resident)
    array, and a backend that rejects donation falls back undonated and
    latches the toggle off;
  * the queue_wait stage split (batch_form vs dispatch_wait) and the
    compile_misses prewarm-completeness counter.
"""

import threading
import time

import numpy as np
import pytest

from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.plan import plan_operation


def _img(h, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def _resize_plan(h, w, width):
    return plan_operation("resize", ImageOptions(width=width), h, w, 0, 3)


@pytest.fixture(autouse=True)
def _restore_donation():
    """Donation is a process-global latch (the donate flag keys the
    compile cache); tests that trip the rejection path must not leak a
    latched-off state into the rest of the suite."""
    yield
    chain_mod.set_donation(True)


class TestContinuousAdmission:
    def _slow_drain(self, monkeypatch, delay_s=0.4):
        real = chain_mod.fetch_groups

        def slow(ys):
            time.sleep(delay_s)
            return real(ys)

        monkeypatch.setattr(chain_mod, "fetch_groups", slow)

    def test_item_lands_in_next_chunk_not_behind_drain(self, monkeypatch):
        """Submit B while A's drain is in flight: under the continuous
        policy B launches as its own chunk immediately (a second device
        call exists long before A's slow drain returns)."""
        self._slow_drain(monkeypatch)
        ex = Executor(ExecutorConfig(max_form_ms=2.0, host_spill=False))
        try:
            plan = _resize_plan(100, 80, 40)
            fa = ex.submit(_img(100, 80), plan)
            for _ in range(600):  # until A is launched (may pay a compile)
                if ex.stats.batches >= 1:
                    break
                time.sleep(0.005)
            assert ex.stats.batches == 1
            fb = ex.submit(_img(100, 80, seed=1), plan)
            deadline = time.monotonic() + 0.15  # well inside A's 400ms drain
            while time.monotonic() < deadline and ex.stats.batches < 2:
                time.sleep(0.005)
            # B launched while A was still in flight — not behind the drain
            assert ex.stats.batches == 2
            assert not fa.done()
            assert fa.result(timeout=30).shape == (50, 40, 3)
            assert fb.result(timeout=30).shape == (50, 40, 3)
        finally:
            ex.shutdown()

    def test_coalesced_drain_preserves_per_item_results(self, monkeypatch):
        """Several chunk-sized groups queued behind one slow drain read
        back in a single coalesced device_get; every item still gets its
        own pixels (no cross-chunk mixing)."""
        self._slow_drain(monkeypatch, delay_s=0.1)
        ex = Executor(ExecutorConfig(max_form_ms=1.0, host_spill=False))
        try:
            plan = _resize_plan(100, 80, 40)
            arrs = [_img(100, 80, seed=i) for i in range(6)]
            futs = []
            for a in arrs:
                futs.append(ex.submit(a, plan))
                time.sleep(0.01)  # spread arrivals over several chunks
            outs = [f.result(timeout=60) for f in futs]
            assert ex.stats.batches >= 2  # genuinely multiple launches
            refs = [chain_mod.run_single(a, plan) for a in arrs]
            for out, ref in zip(outs, refs):
                np.testing.assert_array_equal(out, ref)
        finally:
            ex.shutdown()


class TestDonationSafety:
    def test_cache_resident_array_is_never_donated(self):
        """A frame-cache hit hands the SAME read-only ndarray to every
        request that shares the digest; donation must consume only the
        staged device copy, never mutate or invalidate the host array."""
        chain_mod.set_donation(True)
        arr = _img(100, 80, seed=7)
        arr.setflags(write=False)  # exactly how FrameCache serves frames
        pinned = arr.tobytes()
        plan = _resize_plan(100, 80, 40)
        out1 = chain_mod.run_single(arr, plan)
        out2 = chain_mod.run_single(arr, plan)  # second hit on the same frame
        assert arr.tobytes() == pinned  # input bytes untouched
        np.testing.assert_array_equal(out1, out2)

    def test_batched_launch_stages_a_copy(self):
        """launch_batch's donated operand is a fresh np.stack of the item
        arrays — submitting through the executor leaves the caller's
        buffers intact even when one array appears in padding twice."""
        ex = Executor(ExecutorConfig(max_form_ms=5.0, host_spill=False))
        try:
            plan = _resize_plan(64, 64, 32)
            arrs = [_img(64, 64, seed=i) for i in range(3)]  # pads to 4
            pinned = [a.tobytes() for a in arrs]
            futs = [ex.submit(a, plan) for a in arrs]
            for f in futs:
                f.result(timeout=60)
            assert [a.tobytes() for a in arrs] == pinned
        finally:
            ex.shutdown()

    @pytest.mark.parametrize("msg", [
        "chip fell over",
        # the TPU's use-after-donate error: it must surface, not be read
        # as a backend refusing donation
        "Buffer has been deleted or donated",
    ])
    def test_donated_call_errors_raise(self, monkeypatch, msg):
        """No error of a donated call is retried undonated: donation stays
        on and the error reaches the caller."""
        chain_mod.set_donation(True)

        def fake(*a, **k):
            def boom(*aa, **kk):
                raise RuntimeError(msg)

            return boom

        monkeypatch.setattr(chain_mod, "_compiled", fake)
        with pytest.raises(RuntimeError, match=msg):
            chain_mod.run_single(_img(100, 80), _resize_plan(100, 80, 40))
        assert chain_mod.donation_enabled()


class TestStageSplit:
    def test_batch_form_and_dispatch_wait_sum_to_queue_wait(self):
        from imaginary_tpu.engine.timing import TIMES

        TIMES.reset()
        ex = Executor(ExecutorConfig(max_form_ms=2.0, host_spill=False))
        try:
            ex.process(_img(100, 80), _resize_plan(100, 80, 40))
            ex.process(_img(100, 80, seed=1), _resize_plan(100, 80, 40))
        finally:
            ex.shutdown()
        snap = TIMES.snapshot()
        for stage in ("queue_wait", "batch_form", "dispatch_wait"):
            assert snap[stage]["count"] == 2, stage
        # the split is exact by construction (both halves stamped at the
        # same dispatch instant); means agree to measurement noise
        total = snap["batch_form"]["mean_ms"] + snap["dispatch_wait"]["mean_ms"]
        assert abs(total - snap["queue_wait"]["mean_ms"]) < 0.5
        # formation respected its cap (plus scheduler slack)
        assert snap["batch_form"]["p99_ms"] <= 2.0 + 50.0

    def test_stats_surface_the_split_and_donation(self):
        ex = Executor(ExecutorConfig(max_form_ms=2.0, host_spill=False))
        try:
            ex.process(_img(100, 80), _resize_plan(100, 80, 40))
            d = ex.stats.to_dict()
        finally:
            ex.shutdown()
        for k in ("batch_form_p50_ms", "batch_form_p99_ms",
                  "dispatch_wait_p50_ms", "dispatch_wait_p99_ms",
                  "compile_misses", "donation_enabled"):
            assert k in d, k
        snap = ex.debug_snapshot()
        assert snap["batch_form_cap_ms"] == 2.0


class TestCompileMisses:
    def test_cold_dispatch_counts_a_miss_and_warm_does_not(self):
        chain_mod.clear_cache()
        plan = _resize_plan(100, 80, 40)
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
        try:
            ex.process(_img(100, 80), plan)
            assert ex.stats.compile_misses == 1  # nothing was prewarmed
            ex.process(_img(100, 80, seed=1), plan)
            assert ex.stats.compile_misses == 1  # warm now
        finally:
            ex.shutdown()
        # a prewarmed executor never pays: warm the ladder the way
        # --prewarm does, then serve the same chain from a fresh executor
        from imaginary_tpu.prewarm import warm_chain

        warm_chain("resize", ImageOptions(width=40), 100, 80, (1, 2))
        ex2 = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
        try:
            ex2.process(_img(100, 80, seed=2), plan)
            assert ex2.stats.compile_misses == 0
        finally:
            ex2.shutdown()


class TestKnobDefaultsAgree:
    """One source of truth for the continuous-batching knobs across CLI /
    web config / executor (same pin style as TestBatchLadderUnification)."""

    def test_defaults_agree_everywhere(self):
        from imaginary_tpu.cli import build_parser
        from imaginary_tpu.web.config import ServerOptions

        args = build_parser().parse_args([])
        o = ServerOptions()
        assert (args.batch_form_ms == o.batch_form_ms
                == ExecutorConfig().max_form_ms == 5.0)
        assert (args.max_inflight == o.max_inflight
                == ExecutorConfig().max_inflight == 4)
        assert args.donation == "on"
        assert o.donation is True
