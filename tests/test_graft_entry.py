"""Driver-contract tests: entry() must jit-compile and dryrun_multichip must
execute a sharded step on the 8-device CPU mesh."""

import importlib.util
import os

import jax


def _load():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("graft_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_entry_jits():
    mod = _load()
    fn, args = mod.entry()
    out, h, w = jax.jit(fn)(*args)
    assert out.shape[0] == args[0].shape[0]
    assert out.dtype.name == "uint8"


def test_dryrun_child_env():
    """Unit-level coverage of the child-env construction (seconds, not the
    ~2 min subprocess dryruns below): the child must run on exactly
    n_devices virtual CPU devices whatever the caller's env says."""
    mod = _load()
    base = {
        "XLA_FLAGS": "--foo=1 --xla_force_host_platform_device_count=8 --bar=2",
        "JAX_PLATFORMS": "tpu,cpu",
        "PATH": "/usr/bin",
    }
    env = mod._dryrun_child_env(4, base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["_ITPU_DRYRUN_CHILD"] == "1"
    # the stale count flag is REPLACED, not appended after
    assert env["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--foo=1" in env["XLA_FLAGS"] and "--bar=2" in env["XLA_FLAGS"]
    assert env["PATH"] == "/usr/bin"  # everything else passes through
    assert base["JAX_PLATFORMS"] == "tpu,cpu"  # caller env untouched
    # no pre-existing XLA_FLAGS at all
    env2 = mod._dryrun_child_env(8, {})
    assert env2["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"


def test_dryrun_multichip_8():
    mod = _load()
    mod.dryrun_multichip(8)


def test_dryrun_multichip_odd():
    mod = _load()
    mod.dryrun_multichip(1)
