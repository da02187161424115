"""The continuous policy's early close (engine/routes.py).

A pending chunk closes at once when every item in it has a route and no
request of those routes is on its way to the executor; items without a
route keep the formation cap. Pins:
  * the rule in the one formation loop, run by the global collector and
    by a lane's;
  * that a route with a request still on its way keeps its chunk open
    until that request submits, and only that route's chunk;
  * the per-route ledger: every exit of a request releases its token
    exactly once, and the counts return to 0;
  * the served path: a lone request's `batch_form` stays far under a
    long `--batch-form-ms`.
"""

import asyncio
import contextvars
import io
import re
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.engine import routes as routes_mod
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation
from imaginary_tpu.web.app import create_app
from imaginary_tpu.web.config import ServerOptions
from tests.conftest import fixture_bytes

CAP_MS = 200.0
FAST_MS = 20.0  # an early close lands well inside this; the cap never does


def _img(h, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def _plan(width):
    return plan_operation("resize", ImageOptions(width=width), 64, 64, 0, 3)


def _routed_submit(ex, arr, plan, token):
    """Submit as the web layer does: the token bound in a copied context."""
    ctx = contextvars.copy_context()
    ctx.run(routes_mod.bind, token)
    return ctx.run(ex.submit, arr, plan)


def _chunk_spy(ex):
    """Record (size, batch_form ms of its first item) of every chunk the
    collector dispatches (`_dispatch`, or `_lane_dispatch` on a lane
    executor), then dispatch it."""
    chunks = []
    attr = "_dispatch" if ex._lanes is None else "_lane_dispatch"
    real = getattr(ex, attr)

    def spy(*args):
        items = args[-1]
        chunks.append((len(items),
                       (items[0].t_close - items[0].t) * 1000.0))
        return real(*args)

    setattr(ex, attr, spy)
    return chunks


@pytest.fixture(params=["global", "lanes"])
def ex(request):
    if request.param == "global":
        ex = Executor(ExecutorConfig(max_form_ms=CAP_MS, max_batch=8,
                                     host_spill=False))
    else:
        ex = Executor(ExecutorConfig(mesh_policy="lanes", n_devices=4,
                                     max_form_ms=CAP_MS, max_batch=8,
                                     host_spill=False))
        # every item on lane 0, so companions share a lane: these cases
        # test formation, not placement (tests/test_lanes.py)
        lane0 = ex._lanes.lanes[0]
        ex._lanes.place = lambda item, exclude=(): lane0
    # compile the programs the tests use before any is timed
    for width in (32, 48):
        for n in (1, 2):
            futs = [ex.submit(_img(64, 64, seed=i), _plan(width))
                    for i in range(n)]
            for f in futs:
                f.result(timeout=120)
    yield ex
    ex.shutdown()


class TestCollector:
    """The global collector and a four-lane executor's: one loop."""

    def test_lone_routed_item_closes_at_once(self, ex):
        chunks = _chunk_spy(ex)
        before = ex.stats.early_closes
        tok = ex.routes.take("resize")
        out = _routed_submit(ex, _img(64, 64), _plan(32), tok).result(timeout=60)
        assert out.shape == (32, 32, 3)
        assert len(chunks) == 1
        size, form_ms = chunks[0]
        assert size == 1 and form_ms < FAST_MS, chunks
        assert ex.stats.early_closes == before + 1
        assert ex.stats.to_dict()["early_closes"] == before + 1
        assert ex.routes.on_the_way("resize") == 0

    def test_item_waits_for_its_route_then_closes_with_it(self, ex):
        chunks = _chunk_spy(ex)
        first, second = ex.routes.take("resize"), ex.routes.take("resize")
        f1 = _routed_submit(ex, _img(64, 64, seed=1), _plan(32), first)
        time.sleep(0.05)
        assert chunks == [], "closed while its route had a request on its way"
        f2 = _routed_submit(ex, _img(64, 64, seed=2), _plan(32), second)
        f1.result(timeout=60)
        f2.result(timeout=60)
        assert len(chunks) == 1, chunks
        size, form_ms = chunks[0]
        assert size == 2
        assert 40.0 <= form_ms < CAP_MS - 50.0, chunks
        assert ex.routes.on_the_way("resize") == 0

    def test_only_the_route_with_a_request_on_its_way_waits(self, ex):
        chunks = _chunk_spy(ex)
        lone = ex.routes.take("crop")
        waiting, coming = ex.routes.take("resize"), ex.routes.take("resize")
        fw = _routed_submit(ex, _img(64, 64, seed=3), _plan(48), waiting)
        fl = _routed_submit(ex, _img(64, 64, seed=4), _plan(32), lone)
        fl.result(timeout=60)
        assert not fw.done()
        assert chunks == [(1, chunks[0][1])] and chunks[0][1] < FAST_MS, chunks
        fc = _routed_submit(ex, _img(64, 64, seed=5), _plan(48), coming)
        fw.result(timeout=60)
        fc.result(timeout=60)
        assert [c[0] for c in chunks] == [1, 2], chunks
        assert chunks[1][1] < CAP_MS - 50.0, chunks

    def test_untagged_submit_keeps_the_cap(self, ex):
        chunks = _chunk_spy(ex)
        before = ex.stats.early_closes
        ex.submit(_img(64, 64), _plan(32)).result(timeout=60)
        assert len(chunks) == 1 and chunks[0][1] >= CAP_MS - 1.0, chunks
        assert ex.stats.early_closes == before

    def test_a_routed_item_beside_an_untagged_one_keeps_the_cap(self, ex):
        chunks = _chunk_spy(ex)
        fu = ex.submit(_img(64, 64, seed=5), _plan(32))
        fr = _routed_submit(ex, _img(64, 64, seed=6), _plan(32),
                            ex.routes.take("resize"))
        fu.result(timeout=60)
        fr.result(timeout=60)
        assert [c[0] for c in chunks] == [2]
        assert chunks[0][1] >= CAP_MS - 1.0, chunks


class TestRouteLedger:
    def test_release_is_idempotent_and_never_below_zero(self):
        ledger = routes_mod.RouteLedger()
        a, b = ledger.take("resize"), ledger.take("resize")
        assert ledger.on_the_way("resize") == 2
        for _ in range(3):
            a.release()
        assert ledger.on_the_way("resize") == 1
        b.release()
        b.release()
        assert ledger.on_the_way("resize") == 0
        assert ledger.on_the_way("crop") == 0

    def test_none_coming_needs_a_route_on_every_item(self):
        class It:
            def __init__(self, route):
                self.route = route

        ledger = routes_mod.RouteLedger()
        assert ledger.none_coming([It("resize"), It("crop")])
        assert not ledger.none_coming([It("resize"), It(None)])
        tok = ledger.take("crop")
        assert not ledger.none_coming([It("resize"), It("crop")])
        assert ledger.none_coming([It("resize")])
        tok.release()
        assert ledger.none_coming([It("resize"), It("crop")])


def _run(options, fn):
    async def runner():
        app = create_app(options, log_stream=io.StringIO())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await fn(client, app["service"])
        finally:
            await client.close()

    asyncio.run(runner())


def _counts(svc):
    return dict(svc.executor.routes._counts)


class TestServedPath:
    @pytest.mark.parametrize("path,body,status", [
        ("/resize?width=100", "imaginary.jpg", 200),
        # sniffs as JPEG, fails in the decoder: never reaches submit
        ("/resize?width=100", "truncated", 400),
        # an identity plan resolves in submit without an item
        ("/autorotate", "imaginary.jpg", 200),
    ], ids=["success", "decode_error", "identity_plan"])
    def test_counts_return_to_zero(self, path, body, status):
        data = (fixture_bytes("imaginary.jpg")[:600] if body == "truncated"
                else fixture_bytes(body))

        async def fn(client, svc):
            for _ in range(2):
                res = await client.post(path, data=data)
                assert res.status == status, await res.text()
            assert _counts(svc) == {}

        _run(ServerOptions(host_spill=False), fn)

    def test_counts_return_to_zero_after_a_host_spill(self):
        async def fn(client, svc):
            res = await client.post("/resize?width=100",
                                    data=fixture_bytes("imaginary.jpg"))
            assert res.status == 200
            assert res.headers["X-Imaginary-Backend"] == "host"
            assert _counts(svc) == {}

        _run(ServerOptions(force_host=True), fn)

    def test_counts_return_to_zero_when_cancelled_while_queued(self):
        from aiohttp.test_utils import make_mocked_request

        async def fn(client, svc):
            release = threading.Event()
            started = threading.Event()

            def blocker():
                started.set()
                release.wait(15)

            blockers = [svc.pool.submit(blocker)
                        for _ in range(svc._pool_workers)]
            assert started.wait(5)
            req = make_mocked_request("POST", "/resize?width=100")
            task = asyncio.ensure_future(
                svc._process_and_respond(req, "resize",
                                         fixture_bytes("imaginary.jpg")))
            for _ in range(500):
                if svc.executor.routes.on_the_way("resize"):
                    break
                await asyncio.sleep(0.01)
            assert _counts(svc) == {"resize": 1}
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            for _ in range(500):
                if not _counts(svc):
                    break
                await asyncio.sleep(0.01)
            assert _counts(svc) == {}, "cancelled-while-queued leaked a token"
            release.set()
            for b in blockers:
                b.result(timeout=10)

        _run(ServerOptions(cpus=1), fn)

    def test_sequential_requests_skip_the_formation_cap(self):
        async def fn(client, svc):
            forms = []
            for _ in range(4):
                res = await client.post("/resize?width=100",
                                        data=fixture_bytes("imaginary.jpg"))
                assert res.status == 200
                assert res.headers["X-Imaginary-Backend"] == "device"
                m = re.search(r"batch_form;dur=(\d+(?:\.\d+)?)",
                              res.headers.get("Server-Timing", ""))
                assert m, res.headers.get("Server-Timing")
                forms.append(float(m.group(1)))
            assert max(forms) < FAST_MS, forms
            assert svc.executor.stats.early_closes == 4

        _run(ServerOptions(batch_form_ms=CAP_MS, host_spill=False), fn)
