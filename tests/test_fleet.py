"""Fleet tier: crash-safe shared result cache, worker fencing, and the
ingress read guard (ISSUE 11).

The shm protocol tests drive the real mmap file — torn writes come from
a genuinely SIGKILLed subprocess (slow-marked) and from direct state
surgery (fast); corruption is a real flipped byte under a sealed
checksum. The HTTP tests pin the tiered-lookup contract: shm-hit bytes
identical to local-hit bytes, fleet-off byte parity, and the /health
/metrics /debugz surfaces. The supervisor-side fencing/roll transitions
live in tests/test_workers.py; the full process-kill story is the
`make chaos` fleet rows (bench_chaos.py).
"""

import asyncio
import hashlib
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from imaginary_tpu import cache as cache_mod
from imaginary_tpu import failpoints
from imaginary_tpu.fleet import shmcache
from imaginary_tpu.fleet.shmcache import (
    FREE,
    SEALED,
    WRITING,
    ShmCache,
)
from imaginary_tpu.web.config import ServerOptions
from tests.conftest import fixture_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _fixtures(testdata):
    return testdata


@pytest.fixture()
def shm(tmp_path):
    path = str(tmp_path / "fleet.shm")
    sup = ShmCache(path, create=True, size_mb=2.0, owner=True)
    worker = ShmCache(path, create=False, worker=0, epoch=0)
    yield sup, worker
    worker.close()
    sup.close()


def _key(tag: bytes) -> bytes:
    return hashlib.sha256(tag).digest()


# --- shm protocol ------------------------------------------------------------


class TestShmCache:
    def test_roundtrip_and_counters(self, shm):
        _, w = shm
        k = _key(b"a")
        assert w.get(k) is None
        assert w.stats.misses == 1
        assert w.put(k, b"image/jpeg\ndevice", b"B" * 1000)
        assert w.get(k) == (b"image/jpeg\ndevice", b"B" * 1000)
        assert w.stats.hits == 1 and w.stats.publishes == 1

    def test_cross_process_attach_sees_entries(self, shm, tmp_path):
        _, w = shm
        k = _key(b"shared")
        w.put(k, b"m", b"payload")
        sibling = ShmCache(w.path, create=False, worker=1, epoch=0)
        try:
            assert sibling.get(k) == (b"m", b"payload")
        finally:
            sibling.close()

    def test_oversize_entry_refused(self, shm):
        _, w = shm
        assert not w.put(_key(b"big"), b"m", b"x" * shmcache.SLOT_BYTES)
        assert w.stats.publish_oversize == 1

    def test_attach_rejects_non_cache_file(self, tmp_path):
        bogus = tmp_path / "bogus.shm"
        bogus.write_bytes(b"\x00" * 8192)
        with pytest.raises(ValueError):
            ShmCache(str(bogus), create=False)

    def test_fencing_blocks_publish_not_read(self, shm):
        sup, w = shm
        k = _key(b"f")
        assert w.put(k, b"m", b"body")
        sup.stamp_epoch(0, 7)  # a successor for index 0 was stamped
        assert w.fenced()
        assert not w.put(_key(b"f2"), b"m", b"body2")
        assert w.stats.fenced_publishes == 1
        # the deposed worker may still READ (immutable sealed entries)
        assert w.get(k) == (b"m", b"body")
        sup.stamp_epoch(0, 0)
        assert not w.fenced()

    def test_zombie_failpoint_forces_fenced_path(self, shm):
        _, w = shm
        failpoints.activate("worker.zombie=error")
        try:
            assert not w.put(_key(b"z"), b"m", b"b")
            assert w.stats.fenced_publishes == 1
        finally:
            failpoints.deactivate()

    def test_checksum_corruption_reads_as_miss_and_reclaims(self, shm):
        _, w = shm
        k = _key(b"c")
        w.put(k, b"m", b"D" * 256)
        idx = w._candidates(k)[0]
        off = w._slot_off(idx) + shmcache._SLOT_DATA_OFF + 10
        w._mm[off] ^= 0x80  # one flipped bit under a sealed checksum
        assert w.get(k) is None  # corrupt bytes are NEVER returned
        assert w.stats.corrupt == 1
        assert w.stats.corrupt_served == 0  # the tripwire stays zero
        assert w._slot_state(idx) == FREE  # reclaimed for reuse

    def test_write_failpoint_error_abandons_cleanly(self, shm):
        _, w = shm
        k = _key(b"e")
        failpoints.activate("fleet.write=error")
        try:
            assert not w.put(k, b"m", b"b")
        finally:
            failpoints.deactivate()
        # deliberate abandon resets FREE immediately (only writer DEATH
        # leaves WRITING behind); slot is reusable right away
        assert w._slot_state(w._candidates(k)[0]) == FREE
        assert w.put(k, b"m", b"b") and w.get(k) == (b"m", b"b")

    def test_torn_slot_skipped_and_swept(self, shm):
        _, w = shm
        k = _key(b"t")
        w.put(k, b"m", b"body")
        idx = w._candidates(k)[0]
        # surgical torn write: WRITING state with no live lock holder,
        # exactly what a SIGKILLed writer leaves (the subprocess variant
        # below proves the real thing; this one keeps the tier-1 run fast)
        import struct

        struct.pack_into("<I", w._mm, w._slot_off(idx), WRITING)
        assert w.get(k) is None  # readers skip unpublished slots
        assert w.sweep() == 1
        assert w._slot_state(idx) == FREE

    def test_eviction_prefers_oldest_tick(self, shm):
        _, w = shm
        for i in range(w.nslots * 12):
            w.put(_key(b"fill%d" % i), b"m", b"y" * 200)
        scan = w.slot_scan()
        assert scan["sealed"] <= w.nslots
        assert w.stats.evictions > 0

    def test_epoch_table_bounds(self, shm):
        sup, _ = shm
        sup.stamp_epoch(shmcache.MAX_WORKERS + 5, 9)  # clamped, no crash
        assert sup.epoch_of(shmcache.MAX_WORKERS - 1) == 9

    def test_snapshot_surfaces(self, shm):
        _, w = shm
        w.put(_key(b"s"), b"m", b"b")
        snap = w.snapshot()
        for field in ("worker", "epoch", "fenced", "slots", "sealed",
                      "hits", "misses", "publishes", "corrupt",
                      "corrupt_served", "torn_reclaimed"):
            assert field in snap
        dbg = w.debug_snapshot()
        assert dbg["path"] == w.path and "epochs" in dbg

    def test_shared_key_matches_etag_derivation(self):
        key = (hashlib.sha256(b"src").digest(), "resize", ("w", 300))
        assert cache_mod.strong_etag(key) == \
            '"' + cache_mod.shared_key(key).hex()[:32] + '"'

    @pytest.mark.slow
    def test_sigkilled_writer_leaves_reclaimable_torn_slot(self, tmp_path):
        path = str(tmp_path / "torn.shm")
        sup = ShmCache(path, create=True, size_mb=1.0, owner=True)
        code = (
            "import hashlib\n"
            "from imaginary_tpu import failpoints\n"
            "from imaginary_tpu.fleet.shmcache import ShmCache\n"
            "failpoints.activate('fleet.write=delay(30s)')\n"
            f"w = ShmCache({path!r}, create=False, worker=1, epoch=0)\n"
            "print('mid-write', flush=True)\n"
            "w.put(hashlib.sha256(b'torn').digest(), b'm', b'x' * 500)\n"
        )
        p = subprocess.Popen([sys.executable, "-c", code], cwd=ROOT,
                             stdout=subprocess.PIPE)
        try:
            assert b"mid-write" in p.stdout.readline()
            time.sleep(1.0)  # the deposit is inside the WRITING window
            p.kill()
            p.wait()
            k = hashlib.sha256(b"torn").digest()
            idx = sup._candidates(k)[0]
            assert sup._slot_state(idx) == WRITING
            assert sup.get(k) is None  # skipped, not served half-written
            assert sup.sweep() == 1  # kernel released the dead lock
            assert sup._slot_state(idx) == FREE
        finally:
            if p.poll() is None:
                p.kill()
                p.wait()
            sup.close()


# --- the tiered HTTP path ----------------------------------------------------


def run(options, fn):
    """test_cache.py's harness: run fn(client, app) on a fresh app."""

    async def runner():
        from imaginary_tpu.web.app import create_app

        app = create_app(options, log_stream=io.StringIO())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await fn(client, app)
        finally:
            await client.close()

    asyncio.run(runner())


def jpg() -> bytes:
    return fixture_bytes("imaginary.jpg")


def _post_kw():
    return {"data": jpg(), "headers": {"Content-Type": "image/jpeg"}}


class TestTieredLookup:
    def test_shm_hit_bytes_identical_to_local_hit(self, tmp_path):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            svc = app["service"]
            r1 = await client.post("/resize?width=120&height=90", **_post_kw())
            b1 = await r1.read()
            assert r1.status == 200
            r2 = await client.post("/resize?width=120&height=90", **_post_kw())
            assert await r2.read() == b1  # local hit
            svc.caches.result.clear()
            r3 = await client.post("/resize?width=120&height=90", **_post_kw())
            assert await r3.read() == b1  # shm hit: byte-identical
            assert r3.headers.get("X-Imaginary-Backend") == \
                r1.headers.get("X-Imaginary-Backend")
            assert r3.headers.get("ETag") == r1.headers.get("ETag")
            assert svc.caches.shm.stats.hits == 1

        run(ServerOptions(fleet_cache_mb=4.0, cache_result_mb=4.0), fn)

    def test_shm_tier_works_without_local_result_cache(self):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            svc = app["service"]
            r1 = await client.post("/resize?width=100", **_post_kw())
            b1 = await r1.read()
            assert r1.status == 200 and svc.caches.shm.stats.publishes == 1
            r2 = await client.post("/resize?width=100", **_post_kw())
            assert await r2.read() == b1
            assert svc.caches.shm.stats.hits == 1
            # the shm tier carries the strong ETag/304 contract alone
            etag = r1.headers.get("ETag")
            assert etag
            r3 = await client.post("/resize?width=100", data=jpg(), headers={
                "Content-Type": "image/jpeg", "If-None-Match": etag})
            assert r3.status == 200  # POST never 304s; GET does below

        run(ServerOptions(fleet_cache_mb=4.0), fn)

    def test_fleet_off_byte_parity(self):
        os.environ.pop(shmcache.PATH_ENV, None)
        bodies = {}

        async def baseline(client, app):
            r = await client.post("/resize?width=140&height=100", **_post_kw())
            bodies["off"] = await r.read()
            assert app["service"].caches.shm is None
            h = await client.get("/health")
            assert "fleet" not in await h.json()

        async def armed(client, app):
            r = await client.post("/resize?width=140&height=100", **_post_kw())
            bodies["on"] = await r.read()

        run(ServerOptions(), baseline)
        run(ServerOptions(fleet_cache_mb=4.0), armed)
        assert bodies["off"] == bodies["on"]

    def test_fenced_worker_serves_but_does_not_publish(self):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            svc = app["service"]
            svc.caches.shm.stamp_epoch(0, 99)  # depose worker 0
            r = await client.post("/resize?width=90", **_post_kw())
            assert r.status == 200  # serving is unaffected
            assert svc.caches.shm.stats.fenced_publishes == 1
            assert svc.caches.shm.stats.publishes == 0
            h = await (await client.get("/health")).json()
            assert h["fleet"]["fenced"] is True

        run(ServerOptions(fleet_cache_mb=4.0), fn)

    def test_fleet_write_fault_degrades_to_uncached_success(self):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            failpoints.activate("fleet.write=error")
            try:
                r = await client.post("/resize?width=80", **_post_kw())
                assert r.status == 200  # a broken deposit costs a miss only
            finally:
                failpoints.deactivate()
            assert app["service"].caches.shm.stats.publishes == 0

        run(ServerOptions(fleet_cache_mb=4.0), fn)

    def test_health_metrics_debugz_fleet_blocks(self):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            await client.post("/resize?width=70", **_post_kw())
            h = await (await client.get("/health")).json()
            assert h["epoch"] == 0
            fleet = h["fleet"]
            assert fleet["publishes"] == 1 and fleet["sealed"] == 1
            m = await (await client.get("/metrics")).text()
            assert "imaginary_tpu_fleet_cache_publishes_total 1" in m
            assert "imaginary_tpu_fleet_cache_corrupt_served_total 0" in m
            assert "imaginary_tpu_fleet_epoch 0" in m
            d = await (await client.get("/debugz")).json()
            assert d["fleet"]["path"] == app["service"].caches.shm.path

        run(ServerOptions(fleet_cache_mb=4.0, enable_debug=True), fn)

    def test_corrupt_shared_entry_recomputed_not_served(self):
        os.environ.pop(shmcache.PATH_ENV, None)

        async def fn(client, app):
            svc = app["service"]
            r1 = await client.post("/resize?width=60", **_post_kw())
            b1 = await r1.read()
            # scribble on the sealed entry, then force a shm lookup
            shm = svc.caches.shm
            for idx in range(shm.nslots):
                if shm._slot_state(idx) == SEALED:
                    shm._mm[shm._slot_off(idx) + shmcache._SLOT_DATA_OFF
                            + 24] ^= 0xFF
            svc.caches.result.clear()
            r2 = await client.post("/resize?width=60", **_post_kw())
            b2 = await r2.read()
            assert r2.status == 200 and b2 == b1  # recomputed, identical
            assert shm.stats.corrupt >= 1
            assert shm.stats.corrupt_served == 0

        # both replies must come from the device for their bytes to be
        # equal: the spill cost model can place one on the host under load
        run(ServerOptions(fleet_cache_mb=4.0, cache_result_mb=4.0,
                          host_spill=False), fn)


# --- ingress read guard ------------------------------------------------------


class _Echo(asyncio.Protocol):
    """Minimal inner protocol: answers any complete request-ish blob."""

    def connection_made(self, transport):
        self.transport = transport

    def data_received(self, data):
        pass

    def connection_lost(self, exc):
        pass

    def eof_received(self):
        return False


class TestReadTimeoutGuard:
    def _serve(self, timeout_s):
        from imaginary_tpu.web.ingress import IngressStats, ReadTimeoutGuard

        stats = IngressStats()

        async def start():
            loop = asyncio.get_running_loop()
            server = await loop.create_server(
                lambda: ReadTimeoutGuard(_Echo(), timeout_s, stats=stats),
                "127.0.0.1", 0)
            return server, server.sockets[0].getsockname()[1]

        return stats, start

    def test_stalled_header_read_is_closed(self):
        stats, start = self._serve(0.3)

        async def fn():
            server, port = await start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(b"POST /resize HTTP/1.1\r\nHost: x\r\n")  # never finishes
                await w.drain()
                got = await asyncio.wait_for(r.read(), timeout=3.0)
                assert got == b""  # server closed on us
            finally:
                server.close()
                await server.wait_closed()

        asyncio.run(fn())
        assert stats.read_timeouts == 1

    def test_flowing_slow_body_survives(self):
        stats, start = self._serve(0.4)

        async def fn():
            server, port = await start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(b"POST /x HTTP/1.1\r\nHost: x\r\n"
                        b"Content-Length: 50\r\n\r\n")
                await w.drain()
                for _ in range(10):  # 50 bytes trickled under the deadline
                    w.write(b"AAAAA")
                    await w.drain()
                    await asyncio.sleep(0.1)
                # body complete -> IDLE: the guard must now leave the
                # connection alone even well past the timeout window
                await asyncio.sleep(0.9)
                assert not w.transport.is_closing()
            finally:
                w.close()
                server.close()
                await server.wait_closed()

        asyncio.run(fn())
        assert stats.read_timeouts == 0

    def test_stalled_body_read_is_closed(self):
        stats, start = self._serve(0.3)

        async def fn():
            server, port = await start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(b"POST /x HTTP/1.1\r\nHost: x\r\n"
                        b"Content-Length: 1000\r\n\r\nonly-a-little")
                await w.drain()
                got = await asyncio.wait_for(r.read(), timeout=3.0)
                assert got == b""
            finally:
                server.close()
                await server.wait_closed()

        asyncio.run(fn())
        assert stats.read_timeouts == 1

    def test_idle_keepalive_connection_untouched(self):
        stats, start = self._serve(0.3)

        async def fn():
            server, port = await start()
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")  # complete
                await w.drain()
                await asyncio.sleep(0.9)  # idle well past the window
                assert not w.transport.is_closing()
            finally:
                w.close()
                server.close()
                await server.wait_closed()

        asyncio.run(fn())
        assert stats.read_timeouts == 0

    def test_read_timeout_off_is_parity(self):
        # with the flag at 0 the serving path never imports the guard:
        # ServerOptions default keeps read_timeout_s == 0
        assert ServerOptions().read_timeout_s == 0.0

    @pytest.mark.slow
    def test_real_server_closes_slowloris(self, tmp_path):
        """End-to-end: a real `serve()` process with --read-timeout must
        close a stalled header read while a well-behaved request on a
        second connection succeeds."""
        from tests.conftest import free_port

        port = free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("IMAGINARY_TPU_WORKER", None)
        proc = subprocess.Popen(
            [sys.executable, "-m", "imaginary_tpu.cli", "--port", str(port),
             "--read-timeout", "1.0"],
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        try:
            end = time.monotonic() + 60
            while time.monotonic() < end:
                try:
                    s = socket.create_connection(("127.0.0.1", port), 1)
                    s.close()
                    break
                except OSError:
                    time.sleep(0.3)
            # slowloris: headers started, never finished
            sl = socket.create_connection(("127.0.0.1", port), 5)
            sl.sendall(b"GET /health HTTP/1.1\r\nHost: x\r\n")
            sl.settimeout(5.0)
            t0 = time.monotonic()
            got = sl.recv(4096)  # server must CLOSE us (b"" = EOF)
            assert got == b"", got
            assert time.monotonic() - t0 < 4.0
            sl.close()
            # a healthy request still answers afterwards
            import urllib.request

            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/health", timeout=5) as r:
                body = json.loads(r.read())
            assert body["worker"] == 0
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


# --- supervisor fencing env contract ----------------------------------------


def test_worker_epoch_env_helper():
    from imaginary_tpu.web.workers import WORKER_EPOCH_ENV, worker_epoch

    assert worker_epoch() == 0
    os.environ[WORKER_EPOCH_ENV] = "17"
    try:
        assert worker_epoch() == 17
    finally:
        del os.environ[WORKER_EPOCH_ENV]
