"""HTTP integration tests (modeled on server_test.go).

Each test spins an in-process aiohttp app (and, where needed, a fake origin
server — the reference's httptest.NewServer pattern, server_test.go:282-285)
and asserts on the wire: status, headers, and decoded output dimensions via
PIL.
"""

import asyncio
import io
import json

import numpy as np
import pytest
from aiohttp import FormData
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from imaginary_tpu.web.app import create_app
from imaginary_tpu.web.config import ServerOptions, parse_origins
from imaginary_tpu.web.middleware import sign_url
from tests.conftest import FIXTURES, fixture_bytes


def run(options, fn, origin_handler=None):
    """Run `fn(client, origin_url)` against a fresh app instance."""

    async def runner():
        from aiohttp import web

        origin_url = None
        origin = None
        if origin_handler is not None:
            oapp = web.Application()
            oapp.router.add_route("*", "/{tail:.*}", origin_handler)
            origin = TestServer(oapp)
            await origin.start_server()
            origin_url = f"http://127.0.0.1:{origin.port}"

        app = create_app(options, log_stream=io.StringIO())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await fn(client, origin_url)
        finally:
            await client.close()
            if origin is not None:
                await origin.close()

    asyncio.run(runner())


def oracle_size(body: bytes):
    im = Image.open(io.BytesIO(body))
    return im.width, im.height


def multipart_jpg():
    form = FormData()
    form.add_field("file", fixture_bytes("imaginary.jpg"),
                   filename="imaginary.jpg", content_type="image/jpeg")
    return form


@pytest.fixture(scope="module", autouse=True)
def _fixtures(testdata):
    return testdata


class TestPublicEndpoints:
    def test_index_versions(self):
        async def fn(client, _):
            res = await client.get("/")
            assert res.status == 200
            body = await res.json()
            assert "imaginary_tpu" in body and "jax" in body
            assert res.headers["Server"].startswith("imaginary-tpu")

        run(ServerOptions(), fn)

    def test_health(self):
        async def fn(client, _):
            res = await client.get("/health")
            body = await res.json()
            assert res.status == 200
            assert body["uptime"] >= 0 and "executor" in body

        run(ServerOptions(), fn)

    def test_form_html(self):
        async def fn(client, _):
            res = await client.get("/form")
            text = await res.text()
            assert res.status == 200
            assert 'action="/resize' in text and "multipart/form-data" in text

        run(ServerOptions(), fn)

    def test_unknown_path_404(self):
        async def fn(client, _):
            res = await client.get("/bogus-path")
            assert res.status == 404

        run(ServerOptions(), fn)

    def test_method_not_allowed(self):
        async def fn(client, _):
            res = await client.delete("/resize")
            assert res.status == 405

        run(ServerOptions(), fn)


class TestImagePost:
    def test_crop_multipart(self):
        async def fn(client, _):
            res = await client.post("/crop?width=300", data=multipart_jpg())
            assert res.status == 200, await res.text()
            assert res.headers["Content-Type"] == "image/jpeg"
            body = await res.read()
            assert oracle_size(body) == (300, 740)

        run(ServerOptions(), fn)

    def test_resize_raw_body(self):
        async def fn(client, _):
            res = await client.post(
                "/resize?width=200&height=150",
                data=fixture_bytes("imaginary.jpg"),
                headers={"Content-Type": "image/jpeg"},
            )
            assert res.status == 200
            assert oracle_size(await res.read()) == (200, 150)

        run(ServerOptions(), fn)

    def test_empty_body_400(self):
        async def fn(client, _):
            res = await client.post("/resize?width=200", data=b"",
                                    headers={"Content-Type": "image/jpeg"})
            assert res.status == 400

        run(ServerOptions(), fn)

    def test_non_image_payload_406(self):
        async def fn(client, _):
            res = await client.post("/resize?width=200", data=b"clearly not an image",
                                    headers={"Content-Type": "image/jpeg"})
            assert res.status == 406

        run(ServerOptions(), fn)

    def test_bad_param_400(self):
        async def fn(client, _):
            res = await client.post("/resize?width=bogus", data=multipart_jpg())
            assert res.status == 400
            body = await res.json()
            assert "width" in body["message"]

        run(ServerOptions(), fn)

    def test_info(self):
        async def fn(client, _):
            res = await client.post("/info", data=multipart_jpg())
            meta = await res.json()
            assert meta["width"] == 550 and meta["height"] == 740

        run(ServerOptions(), fn)

    def test_pipeline(self):
        async def fn(client, _):
            ops = json.dumps([
                {"operation": "crop", "params": {"width": 300, "height": 260}},
                {"operation": "convert", "params": {"type": "webp"}},
            ])
            res = await client.post(f"/pipeline?operations={ops}", data=multipart_jpg())
            assert res.status == 200, await res.text()
            assert res.headers["Content-Type"] == "image/webp"
            assert oracle_size(await res.read()) == (300, 260)

        run(ServerOptions(), fn)


class TestTypeAuto:
    """ref: TestTypeAuto server_test.go:178-233."""

    def test_accept_webp(self):
        async def fn(client, _):
            res = await client.post("/resize?width=100&type=auto", data=multipart_jpg(),
                                    headers={"Accept": "image/webp,*/*"})
            assert res.status == 200
            assert res.headers["Content-Type"] == "image/webp"
            assert res.headers["Vary"] == "Accept"

        run(ServerOptions(), fn)

    def test_chrome_accept_header(self):
        chrome = "text/html,application/xhtml+xml,application/xml;q=0.9,image/avif,image/webp,image/apng,*/*;q=0.8"
        async def fn(client, _):
            res = await client.post("/resize?width=100&type=auto", data=multipart_jpg(),
                                    headers={"Accept": chrome})
            assert res.headers["Content-Type"] == "image/webp"
            assert res.headers["Vary"] == "Accept"

        run(ServerOptions(), fn)

    def test_no_accept_keeps_source(self):
        async def fn(client, _):
            res = await client.post("/resize?width=100&type=auto", data=multipart_jpg())
            assert res.headers["Content-Type"] == "image/jpeg"
            assert res.headers["Vary"] == "Accept"

        run(ServerOptions(), fn)

    def test_invalid_type_400(self):
        async def fn(client, _):
            res = await client.post("/resize?width=100&type=bogus", data=multipart_jpg())
            assert res.status == 400

        run(ServerOptions(), fn)


class TestResolutionGuard:
    def test_too_many_pixels_422(self):
        async def fn(client, _):
            res = await client.post("/resize?width=100", data=multipart_jpg())
            assert res.status == 422

        run(ServerOptions(max_allowed_pixels=0.1), fn)


class TestMountSource:
    def test_fs_serving(self):
        async def fn(client, _):
            res = await client.get("/resize?file=imaginary.jpg&width=300")
            assert res.status == 200
            assert oracle_size(await res.read()) == (300, 404)

        run(ServerOptions(mount=FIXTURES), fn)

    def test_path_traversal_rejected(self):
        async def fn(client, _):
            res = await client.get("/resize?file=../../etc/passwd&width=100")
            assert res.status == 400

        run(ServerOptions(mount=FIXTURES), fn)

    def test_missing_file_400(self):
        async def fn(client, _):
            res = await client.get("/resize?file=nope.jpg&width=100")
            assert res.status == 400

        run(ServerOptions(mount=FIXTURES), fn)

    def test_get_without_sources_405(self):
        async def fn(client, _):
            res = await client.get("/resize?width=100")
            assert res.status == 405

        run(ServerOptions(), fn)


class TestURLSource:
    def test_remote_fetch(self):
        from aiohttp import web

        async def origin(request):
            return web.Response(body=fixture_bytes("large.jpg"), content_type="image/jpeg")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=300")
            assert res.status == 200
            w, h = oracle_size(await res.read())
            assert w == 300

        run(ServerOptions(enable_url_source=True), fn, origin_handler=origin)

    def test_origin_error_maps_to_502(self):
        """An origin error is OUR gateway failure, not the client's fault:
        the origin's status stays in the message only (PARITY.md r8 — the
        reference re-raised it verbatim, leaking e.g. an origin 401 as an
        imaginary-tpu auth failure)."""
        from aiohttp import web

        async def origin(request):
            return web.Response(status=404, text="not here")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/gone.jpg&width=300")
            assert res.status == 502
            body = await res.json()
            assert "status=404" in body["message"]

        run(ServerOptions(enable_url_source=True), fn, origin_handler=origin)

    def test_restricted_origin(self):
        from aiohttp import web

        async def origin(request):
            return web.Response(body=fixture_bytes("large.jpg"), content_type="image/jpeg")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=300")
            assert res.status == 400
            body = await res.json()
            assert "not allowed" in body["message"]

        run(
            ServerOptions(enable_url_source=True,
                          allowed_origins=parse_origins("https://images.example.com")),
            fn,
            origin_handler=origin,
        )

    def test_invalid_url_400(self):
        async def fn(client, _):
            res = await client.get("/resize?url=not-a-url&width=300")
            assert res.status == 400

        run(ServerOptions(enable_url_source=True), fn)


class TestAuthAndSignature:
    def test_api_key(self):
        async def fn(client, _):
            res = await client.post("/crop?width=100", data=multipart_jpg())
            assert res.status == 401
            res = await client.post("/crop?width=100", data=multipart_jpg(),
                                    headers={"API-Key": "s3cret"})
            assert res.status == 200
            res = await client.post("/crop?width=100&key=s3cret", data=multipart_jpg())
            assert res.status == 200

        run(ServerOptions(api_key="s3cret"), fn)

    def test_url_signature(self):
        key = "x" * 32

        async def fn(client, _):
            pairs = [("width", "100")]
            sig = sign_url(key, "/crop", pairs)
            res = await client.post(f"/crop?width=100&sign={sig}", data=multipart_jpg())
            assert res.status == 200
            res = await client.post("/crop?width=100&sign=invalid!!", data=multipart_jpg())
            assert res.status == 400
            bad = sign_url(key, "/crop", [("width", "999")])
            res = await client.post(f"/crop?width=100&sign={bad}", data=multipart_jpg())
            assert res.status == 403

        run(ServerOptions(enable_url_signature=True, url_signature_key=key), fn)


class TestMiddlewareExtras:
    def test_throttle_429(self):
        async def fn(client, _):
            first = await client.post("/crop?width=50", data=multipart_jpg())
            assert first.status == 200
            second = await client.post("/crop?width=50", data=multipart_jpg())
            assert second.status == 429
            assert "Retry-After" in second.headers

        run(ServerOptions(concurrency=1, burst=0), fn)

    def test_disabled_endpoint_501(self):
        async def fn(client, _):
            res = await client.post("/blur?sigma=3", data=multipart_jpg())
            assert res.status == 501
            res = await client.post("/crop?width=50", data=multipart_jpg())
            assert res.status == 200

        run(ServerOptions(endpoints=("blur",)), fn)

    def test_cache_headers(self):
        async def fn(client, _):
            res = await client.get("/resize?file=imaginary.jpg&width=100")
            assert res.headers["Cache-Control"] == "public, s-maxage=300, max-age=300, no-transform"
            assert "Expires" in res.headers
            # public paths excluded
            res = await client.get("/health")
            assert "Cache-Control" not in res.headers

        run(ServerOptions(mount=FIXTURES, http_cache_ttl=300), fn)

    def test_no_cache_ttl_zero(self):
        async def fn(client, _):
            res = await client.get("/resize?file=imaginary.jpg&width=100")
            assert res.headers["Cache-Control"] == "private, no-cache, no-store, must-revalidate"

        run(ServerOptions(mount=FIXTURES, http_cache_ttl=0), fn)

    def test_cors_headers(self):
        async def fn(client, _):
            res = await client.post("/crop?width=50", data=multipart_jpg())
            assert res.headers["Access-Control-Allow-Origin"] == "*"

        run(ServerOptions(cors=True), fn)

    def test_return_size_headers(self):
        async def fn(client, _):
            res = await client.post("/crop?width=120&height=90", data=multipart_jpg())
            assert res.headers["Image-Width"] == "120"
            assert res.headers["Image-Height"] == "90"

        run(ServerOptions(return_size=True), fn)


class TestPlaceholder:
    def test_placeholder_on_error(self):
        async def fn(client, _):
            # GET with no source configured would 405; use a failing decode
            res = await client.post("/resize?width=120&height=90", data=b"not an image",
                                    headers={"Content-Type": "image/jpeg"})
            assert res.status == 406  # original error status preserved
            assert res.headers["Content-Type"] == "image/jpeg"
            assert "Error" in res.headers
            assert oracle_size(await res.read()) == (120, 90)

        run(ServerOptions(enable_placeholder=True), fn)

    def test_placeholder_custom_status(self):
        async def fn(client, _):
            res = await client.post("/resize?width=60&height=60", data=b"junk",
                                    headers={"Content-Type": "image/jpeg"})
            assert res.status == 202

        run(ServerOptions(enable_placeholder=True, placeholder_status=202), fn)


class TestPathPrefix:
    def test_prefixed_routes(self):
        async def fn(client, _):
            res = await client.post("/api/v1/crop?width=50", data=multipart_jpg())
            assert res.status == 200
            res = await client.get("/api/v1/health")
            assert res.status == 200

        run(ServerOptions(path_prefix="/api/v1"), fn)


class TestBackendHeader:
    """X-Imaginary-Backend: operators must be able to detect mixed-backend
    traffic (spilled pixels are PSNR-equivalent, not bit-identical)."""

    def test_device_placement_header(self):
        async def fn(client, _):
            res = await client.post("/resize?width=100", data=multipart_jpg())
            assert res.status == 200
            assert res.headers["X-Imaginary-Backend"] == "device"
            # identity plans (re-encode only) never reach the executor but
            # still carry the header: untouched pixels cannot diverge
            res = await client.post("/convert?type=png", data=multipart_jpg())
            assert res.status == 200
            assert res.headers["X-Imaginary-Backend"] == "device"
            # /info never produces pixels: no header
            res = await client.post("/info", data=multipart_jpg())
            assert res.status == 200
            assert "X-Imaginary-Backend" not in res.headers

        run(ServerOptions(), fn)

    def test_host_spill_cli_flag(self):
        from imaginary_tpu.cli import build_parser, options_from_args

        for val, expect in (("auto", None), ("on", True), ("off", False)):
            args = build_parser().parse_args(["--host-spill", val])
            assert options_from_args(args).host_spill is expect
        # default is auto
        args = build_parser().parse_args([])
        assert options_from_args(args).host_spill is None


class TestGCRAEviction:
    def test_key_cap_evicts(self):
        """The TAT map is bounded like the reference's memstore
        (middleware.go:131, NewMemStore(65536)): rekeying the limiter by
        client must not leak memory."""
        import time as _time

        from imaginary_tpu.web.middleware import GCRARateLimiter

        rl = GCRARateLimiter(per_sec=1000, burst=1)
        rl.MAX_KEYS = 8  # shadow the class cap for the test
        for i in range(50):
            rl.allow(f"client-{i}")
        assert len(rl._tat) <= 8
        # expired entries are preferred victims: after their tat passes,
        # new keys slot in without nuking live state wholesale
        _time.sleep(0.005)
        rl.allow("fresh")
        assert "fresh" in rl._tat and len(rl._tat) <= 8

    def test_flood_does_not_reset_throttled_clients(self):
        """A unique-key flood must not wipe a throttled client's state
        (that would be a rate-limit bypass): eviction keeps the
        LARGEST-tat half, and a client throttled through its burst
        allowance has accumulated tat far above a one-shot flood key's."""
        from imaginary_tpu.web.middleware import GCRARateLimiter

        rl = GCRARateLimiter(per_sec=10, burst=3)  # emission 0.1s, tau 0.3s
        rl.MAX_KEYS = 8
        for _ in range(4):  # burn the burst: tat climbs ~0.4s ahead
            rl.allow("victim")
        blocked, retry = rl.allow("victim")
        assert not blocked and retry > 0  # throttled now
        for i in range(20):  # live-key flood past the cap
            rl.allow(f"flood-{i}")
        assert "victim" in rl._tat, "flood evicted a throttled client"
        still_blocked, _ = rl.allow("victim")
        assert not still_blocked, "flood reset a throttled client's TAT"


class TestSpatialServedRequest:
    """The W-axis spatial sharding engages on a SERVED request over the
    (batch x spatial) mesh (VERDICT r3 next #7 asked for a served-path
    proof, not just the executor-level test): request through HTTP, output
    dims exact, /health's executor counters show a spatial batch."""

    def test_served_request_routes_spatially(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        import numpy as np

        o = ServerOptions(
            use_mesh=True,
            spatial=2,
            # tiny threshold so the test doesn't pay a 4K-bucket XLA
            # compile on CPU; the sharding machinery is identical
            spatial_threshold_px=1,
            host_spill=False,
        )
        rng = np.random.default_rng(8)
        png = io.BytesIO()
        Image.fromarray(rng.integers(0, 256, (256, 512, 3), dtype=np.uint8)).save(
            png, "PNG"
        )
        form = FormData()
        form.add_field("file", png.getvalue(), filename="t.png",
                       content_type="image/png")

        async def fn(client, _origin):
            r = await client.post("/resize?width=128&type=png", data=form)
            assert r.status == 200
            body = await r.read()
            assert oracle_size(body) == (128, 64)
            h = await client.get("/health")
            stats = (await h.json())["executor"]
            assert stats["spatial_batches"] >= 1

        run(o, fn)


class TestTLSConfig:
    """TLS context mirrors the reference's pinned config (server.go:114-131):
    TLS >= 1.2, the ECDHE + AES-GCM/ChaCha20 cipher list, and — on
    Python >= 3.13, where ssl grew set_groups — the X25519/P-256/P-384
    curve preference list; older interpreters keep OpenSSL's default
    order (X25519-first anyway) rather than pinning wrong via the
    single-curve set_ecdh_curve."""

    def test_ssl_context_pins_reference_ciphers(self, tmp_path):
        import ssl
        import subprocess

        crt, key = tmp_path / "t.crt", tmp_path / "t.key"
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(key), "-out", str(crt), "-days", "1",
             "-subj", "/CN=localhost"],
            check=True, capture_output=True,
        )
        from imaginary_tpu.web.app import make_ssl_context

        o = ServerOptions(cert_file=str(crt), key_file=str(key))
        ctx = make_ssl_context(o)
        assert ctx is not None
        assert ctx.minimum_version == ssl.TLSVersion.TLSv1_2
        names = {c["name"] for c in ctx.get_ciphers()}
        # every pinned TLS1.2 suite is ECDHE with AEAD; no CBC/RSA-kex leaks
        tls12 = {n for n in names if not n.startswith("TLS_")}
        assert tls12 == {
            "ECDHE-ECDSA-AES256-GCM-SHA384", "ECDHE-RSA-AES256-GCM-SHA384",
            "ECDHE-ECDSA-AES128-GCM-SHA256", "ECDHE-RSA-AES128-GCM-SHA256",
            "ECDHE-ECDSA-CHACHA20-POLY1305", "ECDHE-RSA-CHACHA20-POLY1305",
        }

    def test_no_tls_without_both_files(self):
        from imaginary_tpu.web.app import make_ssl_context

        assert make_ssl_context(ServerOptions(cert_file="/tmp/x.crt")) is None

    def test_group_pinning_on_py313(self):
        """On >= 3.13 the context pins the reference's curve list via
        set_groups; this interpreter may be older, so the helper is
        proven against stand-ins on both sides of the version gate."""
        import ssl as ssl_mod
        import sys

        from imaginary_tpu.web.app import _pin_groups

        calls = []

        class WithGroups:  # the >= 3.13 surface
            def set_groups(self, groups):
                calls.append(groups)

        class WithoutGroups:  # pre-3.13 surface
            pass

        assert _pin_groups(WithGroups()) is True
        assert calls == ["x25519:prime256v1:secp384r1"]
        assert _pin_groups(WithoutGroups()) is False
        # and the real context takes whichever branch this interpreter has
        ctx = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
        assert _pin_groups(ctx) is (sys.version_info >= (3, 13))


class TestMultipartFieldOverride:
    """?field= selects the multipart form field name — documented by the
    reference (README.md:597, default `file`) though its fork hard-codes
    `file` (source_body.go:12); this build follows the docs."""

    def test_custom_field_name_accepted(self):
        async def fn(client, _):
            form = FormData()
            form.add_field("photo", fixture_bytes("imaginary.jpg"),
                           filename="p.jpg", content_type="image/jpeg")
            r = await client.post("/resize?width=100&field=photo", data=form)
            assert r.status == 200
            assert oracle_size(await r.read())[0] == 100

        run(ServerOptions(), fn)

    def test_default_field_still_file(self):
        async def fn(client, _):
            r = await client.post("/resize?width=100", data=multipart_jpg())
            assert r.status == 200

        run(ServerOptions(), fn)

    def test_wrong_field_is_missing_file_error(self):
        async def fn(client, _):
            form = FormData()
            form.add_field("photo", fixture_bytes("imaginary.jpg"),
                           filename="p.jpg", content_type="image/jpeg")
            # no ?field= -> the `photo` part is invisible, like the ref
            r = await client.post("/resize?width=100", data=form)
            assert r.status == 400

        run(ServerOptions(), fn)


class TestBootDeviceGate:
    """The CLI initializes the backend in-process at boot, logs what it
    found, and --require-device refuses (exit 2) when that is the CPU.
    Nothing re-pins the server to another backend behind the operator."""

    @pytest.mark.parametrize("pin", [
        {},                                   # no pin: jax's own default
        {"JAX_PLATFORMS": "cpu"},             # the operator's jax pin
        {"IMAGINARY_TPU_PLATFORM": "cpu"},    # the repo's own pin
    ])
    def test_require_device_refuses_cpu_backend(self, monkeypatch, capsys,
                                                pin):
        from imaginary_tpu import cli
        from imaginary_tpu.web import app as app_mod

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("IMAGINARY_TPU_PLATFORM", raising=False)
        for k, v in pin.items():
            monkeypatch.setenv(k, v)

        async def must_not_serve(o, mrelease=30):
            raise AssertionError("served on the CPU despite --require-device")

        monkeypatch.setattr(app_mod, "serve", must_not_serve)
        assert cli.main(["--require-device", "--port", "0"]) == 2
        assert "refusing to start" in capsys.readouterr().err

    def test_boot_line_names_backend_kind_and_count(self, monkeypatch,
                                                    capsys):
        import jax

        from imaginary_tpu import cli, prewarm
        from imaginary_tpu.web import app as app_mod

        served = {}

        async def fake_serve(o, mrelease=30):
            served["yes"] = True

        monkeypatch.setattr(app_mod, "serve", fake_serve)
        monkeypatch.setattr(prewarm, "enable_persistent_cache", lambda: "")
        assert cli.main(["--port", "0"]) == 0
        assert served
        devs = jax.devices()
        line = [ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("imaginary-tpu: backend")]
        assert line == [f"imaginary-tpu: backend {devs[0].platform} "
                        f"({devs[0].device_kind}), {len(devs)} device(s)"]


class TestQueueDepthAdmission:
    """--max-queue-ms sheds load with a 503 when the estimated queueing
    delay (host backlog + executor owed-work ledger) exceeds the bound —
    GCRA caps the RATE, this caps the DEPTH an overload can pile up
    (r4 weak: closed-loop p99 reached 450+ ms unbounded)."""

    def test_overloaded_queue_sheds_with_503(self):
        async def fn(client, _):
            svc = client.app["service"]
            svc._service_ewma_ms = 10_000.0  # simulate a saturated pool...
            svc._inflight = svc._pool_workers + 50  # ...with deep backlog
            resp = await client.post(
                "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
            assert resp.status == 503
            body = await resp.json()
            assert body["message"] == "Server queue is full, retry later"
            # the shed carries a backoff hint like the rate-limit 503 (r8)
            assert int(resp.headers["Retry-After"]) >= 1

        run(ServerOptions(max_queue_ms=200.0), fn)

    def test_quiet_queue_admits(self):
        async def fn(client, _):
            resp = await client.post(
                "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
            assert resp.status == 200

        run(ServerOptions(max_queue_ms=200.0), fn)

    def test_disabled_by_default(self):
        async def fn(client, _):
            svc = client.app["service"]
            svc._service_ewma_ms = 10_000.0
            svc._inflight = svc._pool_workers + 50
            resp = await client.post(
                "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
            assert resp.status == 200  # 0 = no depth gate (r4 behavior)
            svc._inflight = 0

        run(ServerOptions(), fn)

    def test_shutdown_drain_sheds_with_retry_after(self):
        """During the shutdown grace window new image work 503s fast with
        a Retry-After (another instance takes the retry); /health stays
        live so the balancer can see the drain."""
        async def fn(client, _):
            client.app["draining"] = True
            resp = await client.post(
                "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
            assert resp.status == 503
            assert resp.headers["Retry-After"] == "2"
            health = await client.get("/health")
            assert health.status == 200

        run(ServerOptions(), fn)

    def test_estimate_combines_host_and_device(self):
        async def fn(client, _):
            svc = client.app["service"]
            base = svc.estimated_queue_ms()
            svc._inflight = svc._pool_workers + svc._pool_workers  # backlog = workers
            bumped = svc.estimated_queue_ms()
            assert bumped >= base + svc._service_ewma_ms * 0.9
            svc._inflight = 0

        run(ServerOptions(), fn)

    def test_gate_recovers_when_queue_drains(self):
        """Regression: the estimate must exclude the link's fixed drain
        floor — on a slow backend (CPU-fallback floor ~670 ms) counting
        it latched the gate shut FOREVER after one burst (an idle server
        reading as permanently backlogged)."""
        async def fn(client, _):
            svc = client.app["service"]
            # a slow link's fixed floor, far above the bound
            svc.executor._drain_floor_ms = 700.0
            assert svc.executor.estimated_wait_ms() == 0.0  # floor excluded
            resp = await client.post(
                "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
            assert resp.status == 200  # idle server admits despite floor

        run(ServerOptions(max_queue_ms=150.0), fn)



class TestInflightLedgerOnCancellation:
    """Regression (the --max-queue-ms latch-shut leak): a request
    cancelled while its pool task is still QUEUED never runs
    _process_sync (whose finally normally decrements _inflight). The
    submit + done-callback path must balance the ledger for exactly the
    cancelled-while-queued outcome — and only that one."""

    def test_cancelled_queued_request_releases_inflight(self):
        import threading

        from aiohttp.test_utils import make_mocked_request

        async def fn(client, _):
            svc = client.app["service"]
            release = threading.Event()
            started = threading.Event()

            def blocker():
                started.set()
                release.wait(15)

            # saturate every pool worker so the next request sits queued
            blockers = [svc.pool.submit(blocker)
                        for _ in range(svc._pool_workers)]
            assert started.wait(5)
            base = svc._inflight
            # drive the real handler coroutine and cancel it the way a
            # disconnect-cancelled request would be (aiohttp's default
            # config doesn't cancel handlers, but middleware timeouts and
            # handler_cancellation deployments do — the ledger must
            # survive either way)
            req = make_mocked_request("POST", "/resize?width=100")
            task = asyncio.ensure_future(
                svc._process_and_respond(req, "resize",
                                         fixture_bytes("imaginary.jpg")))
            # wait for the handler to increment the ledger and enqueue its
            # pool task (it can never START: all workers are blocked)
            for _ in range(500):
                if svc._inflight > base:
                    break
                await asyncio.sleep(0.01)
            assert svc._inflight == base + 1
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            # the done-callback fires when the cancelled pool task is
            # discarded; give it a beat
            for _ in range(500):
                if svc._inflight == base:
                    break
                await asyncio.sleep(0.01)
            assert svc._inflight == base, "cancelled-while-queued leaked"
            release.set()
            for b in blockers:
                b.result(timeout=10)

        run(ServerOptions(cpus=1), fn)

    def test_completed_request_never_double_decrements(self):
        async def fn(client, _):
            svc = client.app["service"]
            for _ in range(3):
                resp = await client.post(
                    "/resize?width=100", data=fixture_bytes("imaginary.jpg"))
                assert resp.status == 200
            # ran-to-completion futures are not cancelled(), so only
            # _process_sync's finally decrements: the ledger sits at zero,
            # not negative
            assert svc._inflight == 0

        run(ServerOptions(cpus=2), fn)


class TestMetricsEndpoint:
    """Prometheus /metrics (above-reference: SURVEY 5.5 notes the
    reference has no Prometheus surface). Same numbers as /health in
    exposition format; public like /health."""

    def test_metrics_shape(self):
        async def fn(client, _):
            # process one image so executor counters are live
            await client.post("/resize?width=100", data=multipart_jpg())
            res = await client.get("/metrics")
            assert res.status == 200
            assert res.headers["Content-Type"].startswith("text/plain")
            text = await res.text()
            lines = dict(
                ln.rsplit(" ", 1) for ln in text.strip().splitlines()
                if " " in ln and not ln.startswith("#")
            )
            assert float(lines["imaginary_tpu_uptime"]) >= 0
            assert "imaginary_tpu_pid" in lines
            assert float(lines["imaginary_tpu_executor_items"]) >= 0
            assert float(lines["imaginary_tpu_estimated_queue_ms"]) >= 0
            assert any(k.startswith('imaginary_tpu_backend_info{backend=')
                       for k in lines)
            # per-stage latency gauges carry stage/quantile labels
            assert any(k.startswith('imaginary_tpu_stage_ms{stage="')
                       for k in lines)

        run(ServerOptions(), fn)

    def test_metrics_gated_like_health(self):
        """Exactly /health's auth posture: the reference wires ALL routes
        through the API-key middleware (server.go:73-76), so a scraper
        needs the key when one is set."""
        async def fn(client, _):
            res = await client.get("/metrics")
            assert res.status == 401
            res = await client.get("/metrics", headers={"API-Key": "sekrit"})
            assert res.status == 200

        run(ServerOptions(api_key="sekrit"), fn)


class TestShouldRestrictOriginMatrix:
    """The reference's full allowed-origins matrix, ported verbatim
    (source_http_test.go:300-443): wildcard subdomains, path prefixes,
    double slashes, trailing-slash normalization, bucket pairs, and the
    trailing-* path wildcard (parseOrigins strips it to a raw prefix,
    imaginary.go:314-321 — r5 fix: our parse previously kept both the
    `*` and the missing-slash laxness, so `/assets` wrongly admitted
    `/assetsevil/..`)."""

    def _restricted(self, url, origins_csv):
        from urllib.parse import urlparse as up

        from imaginary_tpu.web.sources import should_restrict_origin

        return should_restrict_origin(up(url), parse_origins(origins_csv))

    PLAIN = "https://example.org"
    WILD = ("https://localhost,https://*.example.org,"
            "https://some.s3.bucket.on.aws.org,https://*.s3.bucket.on.aws.org")
    WITH_PATH = ("https://localhost/foo/bar/,https://*.example.org/foo/,"
                 "https://some.s3.bucket.on.aws.org/my/bucket/,"
                 "https://*.s3.bucket.on.aws.org/my/bucket/,"
                 "https://no-leading-path-slash.example.org/assets")
    TWO_BUCKETS = ("https://some.s3.bucket.on.aws.org/my/bucket1/,"
                   "https://some.s3.bucket.on.aws.org/my/bucket2/")
    PATH_WILDCARD = "https://some.s3.bucket.on.aws.org/my-bucket-name*"

    @pytest.mark.parametrize("url,origins,allowed", [
        # plain origin
        ("https://example.org/logo.jpg", PLAIN, True),
        # wildcard origin, plain / sub / sub-sub domain URLs
        ("https://example.org/logo.jpg", WILD, True),
        ("https://node-42.example.org/logo.jpg", WILD, True),
        ("https://n.s3.bucket.on.aws.org/our/bucket/logo.jpg", WILD, True),
        # incorrect domain: restricted under both configs
        ("https://myexample.org/logo.jpg", PLAIN, False),
        ("https://myexample.org/logo.jpg", WILD, False),
        # loopback origin with path
        ("https://localhost/foo/bar/logo.png", WITH_PATH, True),
        ("https://localhost/wrong/logo.png", WITH_PATH, False),
        # wildcard origin with (partial) path
        ("https://our.company.s3.bucket.on.aws.org/my/bucket/logo.gif",
         WITH_PATH, True),
        ("https://our.company.s3.bucket.on.aws.org/my/bucket/a/b/c/d/e/logo.gif",
         WITH_PATH, True),
        # double slashes inside the URL path
        ("https://static.example.org/foo//a//b//c/d/e/logo.webp",
         WITH_PATH, True),
        # origin path missing its trailing slash still matches its subtree
        ("https://no-leading-path-slash.example.org/assets/logo.webp",
         "https://*.example.org/assets", True),
        # ...but must NOT leak prefix-sibling paths (normalization adds /)
        ("https://no-leading-path-slash.example.org/assetsevil/logo.webp",
         "https://*.example.org/assets", False),
        # two buckets on one host
        ("https://some.s3.bucket.on.aws.org/my/bucket1/logo.jpg", TWO_BUCKETS, True),
        ("https://some.s3.bucket.on.aws.org/my/bucket2/logo.jpg", TWO_BUCKETS, True),
        # trailing-* path wildcard: raw prefix
        ("https://some.s3.bucket.on.aws.org/my-bucket-name/logo.jpg",
         PATH_WILDCARD, True),
        ("https://some.s3.bucket.on.aws.org/my-other-bucket-name/logo.jpg",
         PATH_WILDCARD, False),
    ])
    def test_matrix(self, url, origins, allowed):
        assert self._restricted(url, origins) is (not allowed)


class TestAccessLogContract:
    """log_test.go ported: info level logs a 200 line carrying method,
    HTTP version and status; error level emits NOTHING for a 200
    (log.go:88-99). Plus the level gates the reference implies but never
    tests: warning catches 4xx, error catches 5xx."""

    def _capture(self, level, fn_inner):
        stream = io.StringIO()

        async def runner():
            app = create_app(ServerOptions(log_level=level), log_stream=stream)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                await fn_inner(client)
            finally:
                await client.close()

        asyncio.run(runner())
        return stream.getvalue()

    def test_info_logs_full_line(self):
        async def fn(client):
            await client.get("/health")

        line = self._capture("info", fn)
        assert "GET" in line and "HTTP/1.1" in line and " 200 " in line
        # Apache-ish shape with 4-decimal latency (log.go:12,31), a
        # timezone-offset timestamp, and the trailing request id
        import re

        assert re.search(r'" 200 \d+ \d+\.\d{4} [0-9a-f]{32}\n', line)
        assert re.search(r'\[\d{2}/\w{3}/\d{4}:\d{2}:\d{2}:\d{2} [+-]\d{4}\]', line)

    def test_error_level_silent_on_200(self):
        async def fn(client):
            await client.get("/health")

        assert self._capture("error", fn) == ""

    def test_warning_catches_4xx_not_2xx(self):
        async def fn(client):
            await client.get("/health")          # 200: silent
            await client.get("/bogus-route")     # 404: logged

        line = self._capture("warning", fn)
        assert " 200 " not in line and " 404 " in line


class TestMaxAllowedSize:
    """source_http_test.go:270-298 ported: a remote image larger than
    -max-allowed-size must be refused via the HEAD Content-Length
    pre-check (source_http.go:83-87,105-124), exercised with the
    1024-byte fixture against a 1023-byte cap."""

    def test_oversized_remote_rejected(self):
        from aiohttp import web

        blob = fixture_bytes("1024bytes")

        async def origin(request):
            return web.Response(body=blob,
                                content_type="application/octet-stream")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=100")
            # 413 to match the GET-side streaming cap (r8; was 400)
            assert res.status == 413
            body = await res.json()
            assert "exceeds maximum allowed" in body["message"]

        run(ServerOptions(enable_url_source=True, max_allowed_size=1023),
            fn, origin_handler=origin)

    def test_within_cap_fetches(self):
        from aiohttp import web

        blob = fixture_bytes("imaginary.jpg")

        async def origin(request):
            return web.Response(body=blob, content_type="image/jpeg")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=100")
            assert res.status == 200

        run(ServerOptions(enable_url_source=True,
                          max_allowed_size=len(blob) + 100),
            fn, origin_handler=origin)

    def test_head_failure_degrades_to_capped_get(self):
        """The HEAD pre-check is advisory (r8): an origin that errors the
        HEAD (many CDNs 403 it) degrades to the size-capped GET instead of
        failing a request the GET path can serve."""
        from aiohttp import web

        async def origin(request):
            if request.method == "HEAD":
                return web.Response(status=403)
            return web.Response(body=fixture_bytes("imaginary.jpg"),
                                content_type="image/jpeg")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=100")
            assert res.status == 200

        run(ServerOptions(enable_url_source=True, max_allowed_size=10_000_000),
            fn, origin_handler=origin)

    def test_head_oversize_still_capped_by_get(self):
        """A lying/failed HEAD cannot bypass the size budget: the GET-side
        streaming cap still rejects an oversize body with 413."""
        from aiohttp import web

        blob = fixture_bytes("1024bytes")

        async def origin(request):
            if request.method == "HEAD":
                return web.Response(status=500)
            return web.Response(body=blob,
                                content_type="application/octet-stream")

        async def fn(client, origin_url):
            res = await client.get(f"/resize?url={origin_url}/img.jpg&width=100")
            assert res.status == 413

        run(ServerOptions(enable_url_source=True, max_allowed_size=1023),
            fn, origin_handler=origin)
