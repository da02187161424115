"""chip_smoke.py off the chip: its request and check phase against a
CPU-pinned server, and its refusal to report success on the CPU."""

import os

import pytest

import chip_smoke


@pytest.fixture
def out_dir(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    return tmp_path


def test_request_and_health_phases_pass_on_cpu_server(out_dir):
    """Every case's status, backend header, MIME, dims and PSNR check
    passes against the real server (CPU backend, small inputs), and the
    health phase accepts it once told the platform is the CPU."""
    inputs = chip_smoke.make_inputs({"1080p": (960, 540), "4k": (1600, 900)})
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with chip_smoke.Server("cpu", ["--host-spill", "off"], env=env) as srv:
        srv.wait_healthy(timeout_s=120)
        res = chip_smoke.request_phase(srv.base, inputs, reps=1)
        h = chip_smoke.health_phase(srv.base, len(res), platform="cpu")
        with pytest.raises(chip_smoke.SmokeFailure, match="backend 'cpu'"):
            chip_smoke.health_phase(srv.base, len(res))
    assert set(res) == {c[0] for c in chip_smoke.ONE_CHIP_CASES}
    assert all(r["psnr_db"] >= chip_smoke.PSNR_FLOOR for r in res.values())
    assert h["executor"]["items"] >= len(res)


def test_main_fails_without_an_accelerator(out_dir, monkeypatch, capsys):
    """On the CPU the --require-device server refuses to start; main()
    exits nonzero and prints no result line."""
    monkeypatch.setattr(chip_smoke, "build_native", lambda: 0.0)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "refusing to start" in captured.err
