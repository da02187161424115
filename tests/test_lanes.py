"""Multi-chip sharded serving (ISSUE 15): per-chip batching lanes.

Pins the lane tier's contracts (engine/lanes.py + the executor's lane
loops):
  * placement — (queue depth x EWMA service time) scoring, device-frame-
    cache affinity with the imbalance fallback;
  * parity — mesh_policy="off" builds zero lane objects, adds zero new
    snapshot keys, and serves bytes identical to the direct chain;
  * routing — the sharded-dispatch profitability threshold and the
    oversize-single spatial route at the --spatial-mpix bar;
  * degraded mesh — drain-on-quarantine re-places every queued item onto
    survivors with the lane ledgers at rest afterwards, and the mesh
    generation (part of every sharded compile key) bumps exactly once
    per topology epoch so chip loss recompiles once, never per request;
  * prewarm — warm_mesh_paths covers the per-device and sharded compile
    keys, so compile_misses stays 0 across a run that loses a chip.
"""

import time
from concurrent.futures import Future

import numpy as np
import pytest

from imaginary_tpu import failpoints
from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.engine import lanes as lanes_mod
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.plan import plan_operation


def _img(h, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def _resize_plan(h, w, width=48):
    return plan_operation("resize", ImageOptions(width=width), h, w, 0, 3)


class _FakeItem:
    """Placement-unit stand-in: place() reads .plan.frame_key and
    .future only (the ledger primitives read .lane)."""

    class _Plan:
        def __init__(self, fk):
            self.frame_key = fk

    def __init__(self, frame_key=None):
        self.plan = self._Plan(frame_key)
        self.future = Future()
        self.lane = None
        self.hops = 0


@pytest.fixture(autouse=True)
def _no_failpoints():
    yield
    failpoints.deactivate()


# -- placement (pure scheduler, no devices) ----------------------------------


class TestLanePlacement:
    def test_least_loaded_by_depth_times_ewma(self):
        fast = lanes_mod.Lane(0, None)
        slow = lanes_mod.Lane(1, None)
        fast.note_service(10.0)
        slow.note_service(100.0)
        # equal depth: the faster lane scores lower and wins
        sched = lanes_mod.LaneScheduler([fast, slow])
        assert sched.place(_FakeItem()) is fast
        # pile depth onto the fast lane until its (owed+1) x ewma crosses
        # the slow lane's: 11 x 10 > 1 x 100
        for _ in range(10):
            lanes_mod._lane_owe(fast, _FakeItem())
        assert sched.place(_FakeItem()) is slow

    def test_affinity_prefers_resident_lane(self):
        a, b = lanes_mod.Lane(0, None), lanes_mod.Lane(1, None)
        sched = lanes_mod.LaneScheduler([a, b])
        it1 = _FakeItem(frame_key="digest-1")
        first = sched.place(it1)
        lanes_mod._lane_owe(first, it1)  # mild load on the chosen lane
        # the repeat prefers the lane holding the resident frame even
        # though the other lane now scores (slightly) better
        again = sched.place(_FakeItem(frame_key="digest-1"))
        assert again is first
        assert first.affinity_hits >= 1

    def test_imbalance_falls_back_to_least_loaded(self):
        a, b = lanes_mod.Lane(0, None), lanes_mod.Lane(1, None)
        sched = lanes_mod.LaneScheduler([a, b], imbalance=2.0)
        it1 = _FakeItem(frame_key="digest-2")
        first = sched.place(it1)
        other = b if first is a else a
        # convoy the affine lane far past the imbalance bar
        for _ in range(20):
            lanes_mod._lane_owe(first, _FakeItem())
        chosen = sched.place(_FakeItem(frame_key="digest-2"))
        assert chosen is other
        assert other.affinity_misses >= 1
        # the affinity map re-learns: the NEXT repeat prefers the new lane
        assert sched.place(_FakeItem(frame_key="digest-2")) is other

    def test_quarantined_and_excluded_lanes_skipped(self):
        a, b = lanes_mod.Lane(0, None), lanes_mod.Lane(1, None)
        sched = lanes_mod.LaneScheduler([a, b])
        a.active = False
        assert sched.place(_FakeItem()) is b
        assert sched.place(_FakeItem(), exclude={1}) is None

    def test_owe_moves_charge_and_done_callback_refunds(self):
        a, b = lanes_mod.Lane(0, None), lanes_mod.Lane(1, None)
        it = _FakeItem()
        lanes_mod._lane_owe(a, it)
        assert (a.owed, b.owed) == (1, 0)
        lanes_mod._lane_owe(b, it)  # re-placement refunds the old owner
        assert (a.owed, b.owed) == (0, 1)
        it.future.set_result(None)  # resolution refunds whoever owns it
        assert (a.owed, b.owed) == (0, 0)
        assert it.lane is None


# -- parity: mesh_policy="off" ------------------------------------------------


class TestPolicyOffParity:
    def test_off_builds_no_lanes_and_serves_identical_bytes(self):
        arr = _img(96, 96, seed=3)
        plan = _resize_plan(96, 96)
        direct = chain_mod.run_batch([arr], [plan])[0]
        ex = Executor(ExecutorConfig(max_form_ms=1.0))
        try:
            assert ex._lanes is None
            out = ex.submit(arr, plan).result(timeout=60)
            np.testing.assert_array_equal(out, direct)
            d = ex.stats.to_dict()
            assert "lanes" not in d
            assert "mesh_generation" not in d
            assert "lanes" not in ex.debug_snapshot()
        finally:
            ex.shutdown()

    def test_lanes_serve_same_bytes_as_direct_chain(self):
        arr = _img(96, 96, seed=4)
        plan = _resize_plan(96, 96)
        direct = chain_mod.run_batch([arr], [plan])[0]
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0))
        try:
            out = ex.submit(arr, plan).result(timeout=60)
            np.testing.assert_array_equal(out, direct)
        finally:
            ex.shutdown()


# -- routing ------------------------------------------------------------------


class TestShardedRouting:
    def _launch_spy(self, monkeypatch):
        calls = []
        real = chain_mod.launch_batch

        def spy(arrs, plans, sharding=None, device=None, device_cache=False):
            calls.append({"n": len(arrs), "sharding": sharding,
                          "device": device})
            return real(arrs, plans, sharding=sharding, device=device,
                        device_cache=device_cache)

        monkeypatch.setattr(chain_mod, "launch_batch", spy)
        return calls

    def test_below_threshold_rides_one_lane(self, monkeypatch):
        calls = self._launch_spy(monkeypatch)
        ex = Executor(ExecutorConfig(mesh_policy="sharded", n_devices=4,
                                     max_form_ms=2.0, shard_min_items=8))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            futs = [ex.submit(arr, plan) for _ in range(2)]
            [f.result(timeout=60) for f in futs]
        finally:
            ex.shutdown()
        assert calls and all(c["sharding"] is None and c["device"] is not None
                             for c in calls)

    def test_at_threshold_stages_sharded(self, monkeypatch):
        calls = self._launch_spy(monkeypatch)
        # placement spreads 16 arrivals over the 4 lanes (~4 each); with
        # the threshold at 2 every formed chunk crosses it and stages
        # sharded over the mesh
        ex = Executor(ExecutorConfig(mesh_policy="sharded", n_devices=4,
                                     max_form_ms=50.0, shard_min_items=2,
                                     max_batch=16))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            futs = [ex.submit(arr, plan) for _ in range(16)]
            [f.result(timeout=60) for f in futs]
        finally:
            ex.shutdown()
        sharded = [c for c in calls if c["sharding"] is not None]
        assert sharded
        assert all(c["n"] % 4 == 0 for c in sharded)  # mesh-axis multiple

    def test_spatial_route_at_mpix_bar(self):
        # (2, 2) mesh over 4 of the 8 virtual devices; the bucket for a
        # 512x512 single crosses a 0.2 Mpix bar and W splits evenly
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     spatial=2, spatial_mpix=0.2,
                                     max_form_ms=1.0))
        try:
            assert ex.config.spatial_threshold_px == 200_000
            assert ex._spatial_sharding is not None
            arr, plan = _img(512, 512), _resize_plan(512, 512)
            out = ex.submit(arr, plan).result(timeout=120)
            assert out.shape[1] == 48
            assert ex.stats.spatial_batches == 1
            # a small single stays below the bar: no new spatial batch
            small, splan = _img(96, 96), _resize_plan(96, 96)
            ex.submit(small, splan).result(timeout=60)
            assert ex.stats.spatial_batches == 1
        finally:
            ex.shutdown()


# -- degraded mesh ------------------------------------------------------------


class TestDegradedMesh:
    def test_quarantine_drains_lane_and_ledgers_rest(self):
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0, breaker_threshold=1,
                                     breaker_cooldown_s=300.0))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            [ex.submit(arr, plan).result(timeout=60) for _ in range(4)]
            gen0 = ex._mesh_generation
            failpoints.activate("device.chip_error[0]=error")
            futs = [ex.submit(arr, plan) for _ in range(24)]
            outs = [f.result(timeout=60) for f in futs]
            assert len(outs) == 24  # chip loss never costs availability
            failpoints.deactivate()
            deadline = time.monotonic() + 10.0
            lane0 = ex._lanes.lane(0)
            while lane0.active and time.monotonic() < deadline:
                time.sleep(0.02)
            assert not lane0.active
            # exactly one topology epoch for the single quarantine (the
            # compile-key pin: one recompile, not one per request)
            assert ex._mesh_generation - gen0 == 1
            # ledgers at rest: nothing owed or in flight anywhere
            for ln in ex._lanes.lanes:
                assert ln.owed == 0
                assert ln.inflight == 0
            snap = ex.stats.to_dict()
            assert [s["active"] for s in snap["lanes"]].count(False) == 1
        finally:
            ex.shutdown()

    def test_readmission_restores_lane_and_bumps_generation(self):
        # the cooldown must outlast the whole error storm: a shorter one
        # lets the half-open probe re-admit chip 0 MID-storm on a slow
        # host, fail again, and cycle twice (generation +4, not +2)
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0, breaker_threshold=1,
                                     breaker_cooldown_s=3.0))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            [ex.submit(arr, plan).result(timeout=60) for _ in range(4)]
            gen0 = ex._mesh_generation
            failpoints.activate("device.chip_error[0]=error")
            futs = [ex.submit(arr, plan) for _ in range(8)]
            [f.result(timeout=60) for f in futs]
            failpoints.deactivate()
            lane0 = ex._lanes.lane(0)
            deadline = time.monotonic() + 15.0
            while not lane0.active and time.monotonic() < deadline:
                # keep light traffic flowing so collectors poll
                ex.submit(arr, plan).result(timeout=60)
                time.sleep(0.1)
            assert lane0.active  # the half-open probe re-admitted chip 0
            assert ex._mesh_generation - gen0 == 2  # out + back in
        finally:
            ex.shutdown()


# -- prewarm / compile-key pin ------------------------------------------------


class TestMeshGenerationCompileKeys:
    def test_generation_is_part_of_sharded_compile_key(self):
        from imaginary_tpu.parallel import batch_sharding, get_mesh

        mesh = get_mesh(4, 1, local=True)
        sh = batch_sharding(mesh)
        try:
            k0 = chain_mod._sharding_cache_key(sh)
            chain_mod.set_mesh_generation(chain_mod.mesh_generation() + 1)
            k1 = chain_mod._sharding_cache_key(sh)
            assert k0 != k1
            assert chain_mod._sharding_cache_key(None) is None
        finally:
            chain_mod.set_mesh_generation(0)

    @pytest.mark.slow
    def test_no_compile_misses_across_chip_loss(self):
        opts = ImageOptions(width=48)
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0, breaker_threshold=1,
                                     breaker_cooldown_s=300.0))
        try:
            from imaginary_tpu.prewarm import warm_chain, warm_mesh_paths

            warm_chain("resize", opts, 96, 96, (1, 2, 4, 8, 16))
            warm_mesh_paths(ex, "resize", opts, 96, 96,
                            batch_sizes=(1, 2, 4, 8, 16))
            ex.stats.compile_misses = 0
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            futs = [ex.submit(arr, plan) for _ in range(16)]
            [f.result(timeout=60) for f in futs]
            failpoints.activate("device.chip_error[0]=error")
            futs = [ex.submit(arr, plan) for _ in range(16)]
            [f.result(timeout=60) for f in futs]
            failpoints.deactivate()
            # survivors' per-device keys were prewarmed: chip loss moved
            # traffic without a single post-boot compile
            assert ex.stats.compile_misses == 0
        finally:
            ex.shutdown()


# -- observability surface ----------------------------------------------------


class TestLaneObservability:
    def test_stats_and_debug_snapshots(self):
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            futs = [ex.submit(arr, plan) for _ in range(8)]
            [f.result(timeout=60) for f in futs]
            d = ex.stats.to_dict()
            assert len(d["lanes"]) == 4
            for s in d["lanes"]:
                for k in ("lane", "active", "queued", "inflight",
                          "dispatches", "ewma_ms", "affinity_hit_ratio"):
                    assert k in s
            assert sum(s["dispatches"] for s in d["lanes"]) >= 1
            dz = ex.debug_snapshot()["lanes"]
            assert dz["policy"] == "lanes"
            assert "stage_times" in dz and "mesh_generation" in dz
            # devhealth snapshot carries the same per-lane block (/health)
            dh = ex.devhealth.snapshot()
            assert len(dh["lanes"]) == 4
        finally:
            ex.shutdown()

    def test_wire_bytes_attributed_per_device(self):
        from imaginary_tpu.engine.timing import WIRE

        WIRE.reset()
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=1.0))
        try:
            arr, plan = _img(96, 96), _resize_plan(96, 96)
            futs = [ex.submit(arr, plan) for _ in range(8)]
            [f.result(timeout=60) for f in futs]
            d = ex.stats.to_dict()
            assert "wire_bytes_by_device" in d
            assert d["wire_bytes_by_device"]["h2d"]  # per-chip H2D booked
        finally:
            ex.shutdown()
            WIRE.reset()
