"""Per-device fault domains, hedged failover dispatch, and liveness
supervision (engine/devhealth.py + the ISSUE 6 executor/worker changes).

Covers: per-device breaker independence (chip k trips, its peers keep
serving), the quarantine -> probe -> re-admit cycle, hedge budget
enforcement + loser-cancellation ledger balance, the keyed
device.chip_error / worker.hang failpoint sites, supervisor hung-worker
kill/respawn at the subprocess level, and a parity pin that 1-device
registry behavior matches the PR 4 global-breaker semantics."""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from imaginary_tpu import failpoints
from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.engine.devhealth import (
    STATE_HALF_OPEN,
    STATE_HEALTHY,
    STATE_QUARANTINED,
    DeviceHealthRegistry,
)
from imaginary_tpu.engine.executor import last_placement, reset_placement
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _img(h=96, w=128, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def _plan(h=96, w=128, width=48):
    return plan_operation("resize", ImageOptions(width=width), h, w, 0, 3)


# --- registry unit behavior --------------------------------------------------


class TestRegistry:
    def test_breaker_independence(self):
        reg = DeviceHealthRegistry(4, threshold=3, cooldown_s=60)
        for _ in range(3):
            reg.note_failure(1, "chip 1 sick")
        assert reg.is_quarantined(1)
        assert not reg.is_quarantined(0)
        assert reg.healthy_indices() == [0, 2, 3]
        assert reg.any_available()
        # sticky pick skips the quarantined chip, never its peers
        assert reg.pick() == 0
        assert reg.pick(exclude={0}) == 2

    def test_one_device_parity_with_pr4_global_breaker(self):
        """The PR 4 semantics, spelled as assertions: trip on the Nth
        CONSECUTIVE failure, half-open at cooldown expiry, one more
        failure re-opens instantly, only a success resets."""
        reg = DeviceHealthRegistry(1, threshold=3, cooldown_s=0.2)
        assert reg.any_available()  # closed at rest
        reg.note_failure(0)
        reg.note_failure(0)
        assert reg.any_available()  # two strikes: still closed
        tripped = reg.note_failure(0)
        assert tripped and not reg.any_available()  # third: open
        rec = reg.record(0)
        assert rec.breaker_opens == 1
        # intervening success resets the count — PR 4's only reset path
        time.sleep(0.25)
        assert reg.any_available()  # half-open after cooldown
        assert rec.state(time.monotonic()) == STATE_HALF_OPEN
        # ONE more failure in the half-open window re-opens instantly
        assert reg.note_failure(0)
        assert not reg.any_available()
        time.sleep(0.25)
        reg.note_ok(0)
        assert rec.state(time.monotonic()) == STATE_HEALTHY
        assert rec.consecutive_failures == 0
        assert rec.readmissions == 1
        # closed means closed: a single new failure does not trip
        reg.note_failure(0)
        assert reg.any_available()

    def test_snapshot_shape(self):
        reg = DeviceHealthRegistry(2, threshold=1, cooldown_s=60)
        reg.note_failure(1, "boom")
        snap = reg.snapshot()
        assert snap["count"] == 2
        assert snap["healthy"] == 1
        assert snap["quarantined"] == 1
        states = {d["device"]: d["state"] for d in snap["per_device"]}
        assert states == {0: STATE_HEALTHY, 1: STATE_QUARANTINED}
        assert snap["per_device"][1]["last_error"] == "boom"

    def test_probe_readmits_and_respects_failures(self):
        reg = DeviceHealthRegistry(2, threshold=1, cooldown_s=0.1)
        sick = {1}

        def probe(idx):
            if idx in sick:
                raise RuntimeError("still sick")

        reg.note_failure(1)
        reg.start_probing(probe, timeout_s=2.0)
        try:
            time.sleep(0.5)
            # failing probes keep it quarantined (each failure re-opens)
            assert reg.record(1).probes >= 1
            assert not reg.healthy_indices() == [0, 1]
            sick.clear()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if reg.record(1).state(time.monotonic()) == STATE_HEALTHY:
                    break
                time.sleep(0.05)
            assert reg.record(1).state(time.monotonic()) == STATE_HEALTHY
            assert reg.record(1).readmissions == 1
        finally:
            reg.close()

    def test_hung_probe_books_a_failure(self):
        reg = DeviceHealthRegistry(2, threshold=1, cooldown_s=0.1)
        release = threading.Event()

        def probe(idx):
            release.wait(timeout=30)  # wedged inside the runtime

        reg.note_failure(1)
        before = reg.record(1).failures
        reg.start_probing(probe, timeout_s=0.3)
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if reg.record(1).failures > before:
                    break
                time.sleep(0.05)
            assert reg.record(1).failures > before
            assert not reg.is_quarantined(0)
        finally:
            release.set()
            reg.close()


# --- executor: chip failure -> failover -> quarantine -> re-admit ------------


class TestChipFailover:
    @pytest.fixture(autouse=True)
    def _need_multi_device(self):
        import jax

        if len(jax.local_devices()) < 2:
            pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")

    def test_sick_primary_fails_over_and_quarantines_alone(self, monkeypatch):
        """Chip 0 (the primary, device=None launches) dies; its chunks
        re-route to chip 1 and REQUESTS KEEP SUCCEEDING — losing one chip
        degrades capacity, not availability."""
        from imaginary_tpu.engine import executor as ex_mod
        from imaginary_tpu.obs import trace as obs_trace

        real = ex_mod.chain_mod.launch_batch

        def chip0_dead(arrs, plans, sharding=None, device=None):
            if device is None:  # the primary fault domain's launches
                raise RuntimeError("chip 0 down")
            return real(arrs, plans, sharding=sharding, device=device)

        monkeypatch.setattr(ex_mod.chain_mod, "launch_batch", chip0_dead)
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     breaker_threshold=3,
                                     breaker_cooldown_s=60))
        try:
            tr = obs_trace.RequestTrace("req-failover")
            token = obs_trace.activate(tr)
            try:
                reset_placement()
                out = ex.process(_img(), _plan(), timeout=120)
            finally:
                obs_trace.deactivate(token)
            assert out.shape == (36, 48, 3)
            assert last_placement() == "device"  # served by chip 1, not host
            assert tr.fields["placement_attempts"] == [
                "device:0:error", "device:1"]
            # two more requests: chip 0 trips its own breaker...
            for i in range(2):
                ex.process(_img(seed=i + 1), _plan(), timeout=120)
            assert ex.devhealth.is_quarantined(0)
            snap = ex.devhealth.snapshot()
            assert snap["quarantined"] == 1
            assert snap["healthy"] == len(snap["per_device"]) - 1
            # ...the fleet never went down, so no global outage was booked
            assert not ex._breaker_is_open()
            assert ex.stats.breaker_opens == 0
            assert ex.stats.breaker_host_served == 0
            # quarantined primary is no longer attempted: one clean hop
            tr2 = obs_trace.RequestTrace("req-after-quarantine")
            token = obs_trace.activate(tr2)
            try:
                ex.process(_img(seed=9), _plan(), timeout=120)
            finally:
                obs_trace.deactivate(token)
            assert tr2.fields["placement_attempts"] == ["device:1"]
        finally:
            ex.shutdown()

    def test_chip_error_failpoint_quarantine_and_probe_readmission(self):
        """The chaos contract end-to-end: device.chip_error[0] kills the
        primary fault domain specifically, traffic fails over, the chip
        quarantines, and after the fault clears the background probe
        re-admits it within a cooldown."""
        failpoints.activate("device.chip_error[0]=error")
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     breaker_threshold=2,
                                     breaker_cooldown_s=0.3))
        try:
            for i in range(2):
                out = ex.process(_img(seed=i), _plan(), timeout=120)
                assert out.shape == (36, 48, 3)
            assert ex.devhealth.is_quarantined(0)
            assert not ex._breaker_is_open()
            # counts surfaced on the keyed spelling
            snap = failpoints.snapshot()
            assert snap["sites"]["device.chip_error[0]"]["fired"] >= 2
            # while the fault is armed, probes FAIL: no re-admission flap
            time.sleep(0.8)
            assert ex.devhealth.record(0).state(time.monotonic()) != STATE_HEALTHY
            failpoints.deactivate()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if ex.devhealth.record(0).state(time.monotonic()) == STATE_HEALTHY:
                    break
                time.sleep(0.05)
            assert ex.devhealth.record(0).state(time.monotonic()) == STATE_HEALTHY
            assert ex.devhealth.record(0).readmissions >= 1
        finally:
            failpoints.deactivate()
            ex.shutdown()


# --- hedged failover dispatch ------------------------------------------------


class _BlockedDevice:
    """Monkeypatch helper: every launch blocks until released."""

    def __init__(self, monkeypatch):
        from imaginary_tpu.engine import executor as ex_mod

        self.release = threading.Event()
        real = ex_mod.chain_mod.launch_batch

        def blocked(*a, **k):
            self.release.wait(timeout=60)
            return real(*a, **k)

        monkeypatch.setattr(ex_mod.chain_mod, "launch_batch", blocked)


class TestHedging:
    def test_off_by_default_no_hedge_machinery(self):
        ex = Executor(ExecutorConfig(max_form_ms=1))
        try:
            fut = ex.submit(_img(), _plan())
            out = fut.result(timeout=120)
            assert out.shape == (36, 48, 3)
            assert not hasattr(fut, "_hedge_placement")
            assert ex.stats.hedges_launched == 0
        finally:
            ex.shutdown()

    def test_hedge_wins_over_stuck_device_and_ledger_balances(self, monkeypatch):
        blocked = _BlockedDevice(monkeypatch)
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     hedge_threshold_ms=50.0))
        try:
            reset_placement()
            t0 = time.monotonic()
            out = ex.process(_img(), _plan(), timeout=30)
            dt_ms = (time.monotonic() - t0) * 1000.0
            assert out.shape == (36, 48, 3)
            assert last_placement() == "host"  # the twin's pixels
            assert ex.stats.hedges_won == 1
            # the request resolved at hedge latency, not device latency
            assert dt_ms < 10_000.0
            blocked.release.set()
            # cancelled loser released its owed-ms charge; after the
            # zombie drain finishes, the ledger is at rest
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with ex._owed_lock:
                    if abs(ex._owed_ms) < 1e-6 and ex._device_items == 0:
                        break
                time.sleep(0.05)
            with ex._owed_lock:
                assert abs(ex._owed_ms) < 1e-6
                assert ex._device_items == 0
        finally:
            blocked.release.set()
            ex.shutdown()

    def test_hedge_budget_caps_concurrent_twins(self, monkeypatch):
        from imaginary_tpu.engine import executor as ex_mod

        blocked = _BlockedDevice(monkeypatch)
        # slow twins so they genuinely OVERLAP: the budget bounds
        # concurrency, and a fast twin that finishes before the next
        # timer fires frees its slot legitimately
        host_gate = threading.Event()
        real_host_run = ex_mod.host_exec.run

        def slow_host_run(arr, plan):
            host_gate.wait(timeout=30)
            return real_host_run(arr, plan)

        monkeypatch.setattr(ex_mod.host_exec, "run", slow_host_run)
        # budget 0.05 of 3 in-flight items floors at ONE concurrent hedge
        ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                     hedge_threshold_ms=50.0,
                                     hedge_budget=0.05))
        try:
            futs = [ex.submit(_img(seed=i), _plan()) for i in range(3)]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if ex.stats.hedges_launched + ex.stats.hedges_skipped >= 3:
                    break
                time.sleep(0.02)
            assert ex.stats.hedges_launched == 1
            assert ex.stats.hedges_skipped == 2
            host_gate.set()
            blocked.release.set()
            for f in futs:
                f.result(timeout=60)
        finally:
            host_gate.set()
            blocked.release.set()
            ex.shutdown()

    def test_batch_class_is_never_hedged(self):
        ex = Executor(ExecutorConfig(max_form_ms=1, hedge_threshold_ms=50.0))
        try:
            from imaginary_tpu.engine.executor import _BATCH_CLASS, _Item
            from imaginary_tpu.qos import CLASS_INDEX

            assert _BATCH_CLASS == CLASS_INDEX["batch"]  # literal stays honest
            item = _Item(_img(), _plan())
            item.qos = ("hog", _BATCH_CLASS, 0.5, None)
            assert ex._arm_hedge(item) is None
            item.qos = ("vip", CLASS_INDEX["interactive"], 0.5, None)
            outer = ex._arm_hedge(item)
            assert outer is not None
            item.future.set_result(_img())  # resolve primary; timer cancels
            outer.result(timeout=5)
        finally:
            ex.shutdown()

    def test_device_error_while_twin_runs_surfaces_device_error(self, monkeypatch):
        """Both paths fail: the caller sees the DEVICE error (the twin
        was speculative), and nothing hangs."""
        from imaginary_tpu.engine import executor as ex_mod

        def dead(*a, **k):
            raise RuntimeError("device fell over")

        monkeypatch.setattr(ex_mod.chain_mod, "launch_batch", dead)
        monkeypatch.setattr(ex_mod.host_exec, "run",
                            lambda arr, plan: (_ for _ in ()).throw(
                                RuntimeError("twin also fell over")))
        ex = Executor(ExecutorConfig(max_form_ms=200, host_spill=False,
                                     hedge_threshold_ms=50.0,
                                     breaker_threshold=100))
        try:
            # window 200ms > hedge 50ms: the twin launches (and fails)
            # BEFORE the device dispatch fails — the stashed-error path
            with pytest.raises(RuntimeError, match="fell over"):
                ex.process(_img(), _plan(), timeout=30)
        finally:
            ex.shutdown()


# --- keyed failpoint grammar -------------------------------------------------


class TestKeyedFailpoints:
    def teardown_method(self):
        failpoints.deactivate()

    def test_keyed_site_parses_and_scopes(self):
        failpoints.activate("device.chip_error[1]=error")
        failpoints.hit("device.chip_error", key=0)  # other chip: no-op
        with pytest.raises(failpoints.FailpointError):
            failpoints.hit("device.chip_error", key=1)
        snap = failpoints.snapshot()
        assert snap["sites"]["device.chip_error[1]"]["fired"] == 1

    def test_bare_site_matches_every_key(self):
        failpoints.activate("device.chip_error=error")
        with pytest.raises(failpoints.FailpointError):
            failpoints.hit("device.chip_error", key=3)
        with pytest.raises(failpoints.FailpointError):
            failpoints.hit("device.chip_error")

    def test_unknown_base_site_rejected(self):
        with pytest.raises(ValueError, match="unknown failpoint site"):
            failpoints.parse("device.nope[1]=error")

    def test_worker_hang_site_delays_synchronously(self):
        failpoints.activate("worker.hang=delay(30ms)")
        t0 = time.monotonic()
        failpoints.hit("worker.hang")
        assert time.monotonic() - t0 >= 0.025


# --- supervisor liveness: hung worker is killed and replaced -----------------


def _health(port, timeout=2.0):
    import json
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/health", headers={"Connection": "close"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_supervisor_replaces_hung_worker():
    """Subprocess-level: SIGSTOP wedges one worker (alive, never
    answering — exactly what a hung accelerator runtime looks like from
    outside); the supervisor's liveness probe notices, spawns a
    replacement FIRST, then SIGTERM -> grace -> SIGKILLs the victim."""
    from tests.conftest import free_port

    port = free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("IMAGINARY_TPU_WORKER", None)
    # per-sample interval = PROBE_INTERVAL / workers = 0.2s; a healthy
    # worker unseen for the whole 6s window (while the hung listener
    # still eats ~1/3 of connections) is ~(2/3)^30 — not a flake source
    env["IMAGINARY_TPU_SUPERVISOR_PROBE_INTERVAL"] = "0.4"
    env["IMAGINARY_TPU_SUPERVISOR_LIVENESS_TIMEOUT"] = "6"
    env["IMAGINARY_TPU_SUPERVISOR_HANG_GRACE"] = "1.5"
    env["IMAGINARY_TPU_SUPERVISOR_BOOT_GRACE"] = "60"
    sup = subprocess.Popen(
        [sys.executable, "-m", "imaginary_tpu.cli", "--workers", "2",
         "--port", str(port)],
        cwd=ROOT, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        # wait for both workers to answer (their pids are the probe's view)
        pids = set()
        end = time.monotonic() + 90
        while time.monotonic() < end and len(pids) < 2:
            try:
                pids.add(_health(port)["pid"])
            except Exception:
                time.sleep(0.3)
        assert len(pids) == 2, f"fleet never fully up (saw {pids})"
        victim = sorted(pids)[0]
        os.kill(victim, signal.SIGSTOP)
        # the supervisor must notice the silence, replace, and reap
        end = time.monotonic() + 90
        replaced = False
        while time.monotonic() < end:
            seen = set()
            for _ in range(8):
                try:
                    seen.add(_health(port)["pid"])
                except Exception:
                    time.sleep(0.2)
            victim_dead = False
            try:
                os.kill(victim, 0)
            except OSError:
                victim_dead = True
            if victim_dead and len(seen) == 2 and victim not in seen:
                replaced = True
                break
            time.sleep(0.5)
        assert replaced, "hung worker was not killed and replaced"
    finally:
        if sup.poll() is None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(timeout=20)
            except subprocess.TimeoutExpired:
                sup.kill()
                sup.wait()
