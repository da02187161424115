"""Test harness configuration.

All tests run on CPU with 8 virtual XLA devices so the multi-chip sharding
paths compile and execute without TPU hardware (SURVEY.md section 4.6). This
must run before the first `import jax` anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the config too, in case jax was imported before this file ran (the
# env var is read at import). Safe then as well: backends init lazily. The
# TPU compile tests (tests/test_tpu_compile.py) describe a chip without
# opening one, so the suite never holds an accelerator.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Make the repo root importable when pytest is run from anywhere.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import pytest  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")


_NATIVE = (("_imaginary_codecs", "codecs.cpp"), ("_imaginary_entropy", "entropy.cpp"))


def _build_native_if_stale():
    """Build the native codecs once, before any test process imports them,
    when a module is missing or older than its source. The codec backend
    is picked once per process, at first use: a build that lands midway
    through a run (test_native_codecs builds on demand) would leave every
    worker that started earlier on the cv2/PIL fallback, and the tests of
    the native paths would pass or fail by the order they ran in. Where
    the toolchain cannot build, those tests skip or fail as before."""
    import sysconfig

    native = os.path.join(_ROOT, "imaginary_tpu", "native")
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

    def stale(mod, src):
        so = os.path.join(native, mod + suffix)
        return (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(os.path.join(native, src)))

    if any(stale(mod, src) for mod, src in _NATIVE):
        from imaginary_tpu.native.build import build_any

        try:
            build_any(verbose=False)
        except Exception as e:  # any build failure leaves the fallback codecs
            print(f"native codec build failed: {e}", file=sys.stderr)


def pytest_configure(config):
    # the xdist controller builds before it starts any worker; a worker
    # (config.workerinput) finds the modules in place
    if not hasattr(config, "workerinput"):
        _build_native_if_stale()
    # tier-1 runs -m 'not slow'; register the marker so strict runs and
    # warning-free output both hold
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the tier-1 gate")
    # pytest resets the warnings machinery per test, which would undo the
    # narrow module-level filter ops/chain.py installs for XLA's expected
    # could-not-alias donation notice (output bucket != input bucket);
    # mirror it here so suite output stays readable
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")


@pytest.fixture(autouse=True)
def _isolate_link_seed(monkeypatch):
    """prewarm_common_chains installs a process-global link-rate seed that
    every later Executor consumes; a machine-timing-dependent seed leaking
    across test files would flip placement decisions (device vs host)
    non-deterministically. Every test starts unseeded; monkeypatch
    restores whatever was there before."""
    from imaginary_tpu.engine import executor as executor_mod

    monkeypatch.setattr(executor_mod, "_LINK_SEED", None)


@pytest.fixture(scope="session")
def testdata():
    """Path to the generated fixture directory (see tests/gen_fixtures.py)."""
    if not os.path.isdir(FIXTURES) or not os.listdir(FIXTURES):
        from tests.gen_fixtures import generate_all

        generate_all(FIXTURES)
    return FIXTURES


def fixture_bytes(name: str) -> bytes:
    path = os.path.join(FIXTURES, name)
    if not os.path.exists(path):
        from tests.gen_fixtures import generate_all

        generate_all(FIXTURES)
    with open(path, "rb") as f:
        return f.read()


def psnr(a, b) -> float:
    """Shared PSNR helper (single definition for every grading suite)."""
    import numpy as np

    mse = np.mean((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)
    if mse == 0:
        return 99.0
    return float(10.0 * np.log10(255.0 * 255.0 / mse))


def free_port() -> int:
    """Ephemeral TCP port for tests that boot real listeners."""
    from bench_util import free_port as _fp

    return _fp()
