"""Multi-host initialization hook (SURVEY.md section 5.8; VERDICT r1 next #9).

jax.distributed.initialize is process-global and incompatible with the
already-initialized test backend, so the test drives the real code path in a
pinned subprocess: a 1-process "fleet" whose coordinator is itself — the
same call shape a TPU pod worker uses, minus auto-discovery.
"""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import jax
jax.config.update("jax_platforms", "cpu")
from imaginary_tpu.parallel.mesh import get_mesh, init_distributed

init_distributed(coordinator_address="127.0.0.1:{port}",
                 num_processes=1, process_id=0)
init_distributed()  # idempotent: second call must be a no-op
assert jax.process_count() == 1
mesh = get_mesh()
print("DIST_OK", jax.process_count(), dict(zip(mesh.axis_names, mesh.devices.shape)))
"""


def test_init_distributed_single_process_fleet():
    from tests.conftest import free_port

    port = free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(port=port)],
        capture_output=True, text=True, timeout=240, cwd=_ROOT, env=env,
    )
    if r.returncode != 0 and "distributed" in (r.stderr or "").lower():
        pytest.skip(f"jax.distributed unavailable here: {r.stderr[-200:]}")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DIST_OK 1" in r.stdout


_WORKER = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
# cross-process collectives on the CPU backend run over gloo
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from jax.sharding import PartitionSpec as P
from jax import shard_map
from imaginary_tpu.parallel.mesh import batch_sharding, get_mesh, init_distributed

PID = {pid}
init_distributed(coordinator_address="127.0.0.1:{port}",
                 num_processes=2, process_id=PID)
assert jax.process_count() == 2, jax.process_count()
mesh = get_mesh()  # one GLOBAL mesh spanning both processes' devices

# 1) one collective across the fleet: psum over the batch axis rides the
#    cross-process (DCN-analogue) link
sharding = batch_sharding(mesh)
n_local = len(jax.local_devices())
n_global = mesh.devices.shape[0] * mesh.devices.shape[1]
x = jax.make_array_from_process_local_data(
    sharding, np.full((n_local,), float(PID + 1), np.float32), (n_global,))
f = jax.jit(shard_map(lambda v: jax.lax.psum(v, "batch"),
                      mesh=mesh, in_specs=P("batch"), out_specs=P()))
total = float(np.asarray(f(x).addressable_shards[0].data).ravel()[0])
expect = n_local * (1.0 + 2.0)  # each process contributes n_local shards
assert total == expect, (total, expect)
print("PSUM_OK", total == expect)

# 2) one dp-sharded chain step: each process contributes its local images;
#    the jitted chain runs once over the global mesh
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.plan import plan_operation

h_in, w_in = 32, 48
plan = plan_operation("resize", ImageOptions(width=16, height=12, force=True),
                      h_in, w_in, 0, 3)
imgs = [np.random.default_rng(1000 * PID + j).integers(
            0, 256, (h_in, w_in, 3), dtype=np.uint8)
        for j in range(n_local)]
padded = np.stack([chain_mod.pad_to_bucket(a) for a in imgs])
gx = jax.make_array_from_process_local_data(sharding, padded,
                                            (n_global,) + padded.shape[1:])
params, wide, layout = chain_mod.pack_operands([plan] * n_local, h_in, w_in)
gparams, *gwide = (
    jax.make_array_from_process_local_data(
        sharding, v, (n_global,) + v.shape[1:])
    for v in (params, *wide))
fn = jax.jit(chain_mod._run_chain, static_argnums=(0, 4))
y, _, _ = fn(plan.spec_key(), gx, gparams, tuple(gwide), layout)
for s in y.addressable_shards:
    local_idx = s.index[0].start - PID * n_local
    mine = np.asarray(s.data)[0, :plan.out_h, :plan.out_w]
    ref = chain_mod.run_single(imgs[local_idx], plan)  # single-device oracle
    assert np.array_equal(mine, ref), "sharded chain output diverged"
print("CHAIN_OK", (plan.out_h, plan.out_w))
"""


def test_two_process_fleet_psum_and_sharded_chain():
    """A REAL 2-process fleet (coordinator + worker subprocesses): global
    mesh, one cross-process psum, one dp-sharded chain step whose shards
    are bit-identical to the single-device oracle (SURVEY.md section 5.8;
    VERDICT r2 next #5)."""
    from tests.conftest import free_port

    port = free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER.format(pid=i, port=port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=_ROOT, env=env,
        )
        for i in range(2)
    ]
    # Poll both: if one worker dies early its peer blocks in
    # init_distributed until the timeout — report the dead worker's real
    # stderr instead of burning 5 minutes on a bare TimeoutExpired.
    import time

    outs = [None, None]
    deadline = time.monotonic() + 300
    try:
        while any(o is None for o in outs) and time.monotonic() < deadline:
            for i, p in enumerate(procs):
                if outs[i] is None and p.poll() is not None:
                    out, err = p.communicate()
                    outs[i] = (p.returncode, out, err)
            if any(o is not None and o[0] != 0 for o in outs):
                break  # a worker failed: don't wait out its blocked peer
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, p in enumerate(procs):
        if outs[i] is None:
            out, err = p.communicate()
            outs[i] = (p.returncode, out, err)

    fails = [(rc, out, err) for rc, out, err in outs if rc != 0]
    if any("distributed" in (err or "").lower() for _, _, err in fails):
        pytest.skip(f"jax.distributed unavailable here: {fails[0][2][-200:]}")
    assert not fails, "\n--- worker stderr ---\n".join(err[-2000:] for _, _, err in fails)
    for rc, out, err in outs:
        assert "PSUM_OK True" in out
        assert "CHAIN_OK" in out


_EXEC_WORKER = r"""
import threading
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from imaginary_tpu.parallel.mesh import init_distributed

PID = {pid}
init_distributed(coordinator_address="127.0.0.1:{port}",
                 num_processes=2, process_id=PID)
assert jax.process_count() == 2

# the SERVING executor inside a live fleet: micro-batch queue -> mesh
# dispatch on this process's local chips (get_mesh(local=True)), while the
# global 2-process backend stays up around it
from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation

ex = Executor(ExecutorConfig(max_form_ms=2.0, max_batch=8, use_mesh=True,
                             host_spill=False))
h_in, w_in = 32, 48
plan = plan_operation("resize", ImageOptions(width=16, height=12, force=True),
                      h_in, w_in, 0, 3)
rng = np.random.default_rng(77 + PID)
imgs = [rng.integers(0, 256, (h_in, w_in, 3), dtype=np.uint8) for _ in range(24)]
oracle = [chain_mod.run_single(a, plan) for a in imgs]

results = [None] * len(imgs)
def client(k):
    for j in range(k, len(imgs), 6):
        results[j] = ex.process(imgs[j], plan)

threads = [threading.Thread(target=client, args=(k,)) for k in range(6)]
for t in threads: t.start()
for t in threads: t.join()
ex.shutdown()
for got, want in zip(results, oracle):
    assert got is not None and np.array_equal(got, want), "fleet executor output diverged"
assert ex.stats.items == len(imgs)
print("EXEC_FLEET_OK", ex.stats.items, ex.stats.batches)
"""


def test_two_process_fleet_serving_executors():
    """Both fleet processes run the SERVING executor concurrently —
    micro-batch queue, batch formation, mesh dispatch — against the
    single-device oracle (VERDICT r4 next #7: test_distributed proved
    init/psum/sharded-chain but never the Executor across processes)."""
    import time

    from tests.conftest import free_port

    port = free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _EXEC_WORKER.format(pid=i, port=port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=_ROOT, env=env,
        )
        for i in range(2)
    ]
    outs = [None, None]
    deadline = time.monotonic() + 300
    try:
        while any(o is None for o in outs) and time.monotonic() < deadline:
            for i, p in enumerate(procs):
                if outs[i] is None and p.poll() is not None:
                    out, err = p.communicate()
                    outs[i] = (p.returncode, out, err)
            if any(o is not None and o[0] != 0 for o in outs):
                break
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, p in enumerate(procs):
        if outs[i] is None:
            out, err = p.communicate()
            outs[i] = (p.returncode, out, err)
    fails = [(rc, out, err) for rc, out, err in outs if rc != 0]
    if any("distributed" in (err or "").lower() for _, _, err in fails):
        pytest.skip(f"jax.distributed unavailable here: {fails[0][2][-200:]}")
    assert not fails, "\n--- worker stderr ---\n".join(err[-2000:] for _, _, err in fails)
    for rc, out, err in outs:
        assert "EXEC_FLEET_OK 24" in out


_MESH_CHAIN_WORKER = r"""
import threading
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from imaginary_tpu.parallel.mesh import init_distributed

PID = {pid}
init_distributed(coordinator_address="127.0.0.1:{port}",
                 num_processes=2, process_id=PID)
assert jax.process_count() == 2
# XLA_FLAGS forced 2 host devices per process: the serving executor's
# local mesh is (batch=2, spatial=1), so formed micro-batches genuinely
# SHARD across devices instead of degenerating to a 1-chip mesh
assert len(jax.local_devices()) == 2, jax.local_devices()

from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.options import ImageOptions
from imaginary_tpu.ops.plan import plan_operation

ex = Executor(ExecutorConfig(max_form_ms=4.0, max_batch=8, use_mesh=True,
                             host_spill=False))
assert ex._mesh_batch == 2, ex._mesh_batch  # batch axis spans both chips
h_in, w_in = 32, 48
plan = plan_operation("resize", ImageOptions(width=16, height=12, force=True),
                      h_in, w_in, 0, 3)
rng = np.random.default_rng(900 + PID)
imgs = [rng.integers(0, 256, (h_in, w_in, 3), dtype=np.uint8) for _ in range(24)]
oracle = [chain_mod.run_single(a, plan) for a in imgs]

results = [None] * len(imgs)
def client(k):
    for j in range(k, len(imgs), 6):
        results[j] = ex.process(imgs[j], plan)

threads = [threading.Thread(target=client, args=(k,)) for k in range(6)]
for t in threads: t.start()
for t in threads: t.join()
ex.shutdown()
for got, want in zip(results, oracle):
    assert got is not None and np.array_equal(got, want), "sharded serving chain diverged"
assert ex.stats.items == len(imgs)
assert ex.stats.batches < len(imgs)  # batching actually formed groups
print("MESH_CHAIN_OK", ex._mesh_batch, ex.stats.batches)
"""


def _run_fleet_pair(worker_src, port, extra_env=None, budget_s=300):
    """Launch two pinned fleet subprocesses and poll both (a dead worker
    would otherwise wedge its peer inside init_distributed)."""
    import time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", worker_src.format(pid=i, port=port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=_ROOT, env=env,
        )
        for i in range(2)
    ]
    outs = [None, None]
    deadline = time.monotonic() + budget_s
    try:
        while any(o is None for o in outs) and time.monotonic() < deadline:
            for i, p in enumerate(procs):
                if outs[i] is None and p.poll() is not None:
                    out, err = p.communicate()
                    outs[i] = (p.returncode, out, err)
            if any(o is not None and o[0] != 0 for o in outs):
                break
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, p in enumerate(procs):
        if outs[i] is None:
            out, err = p.communicate()
            outs[i] = (p.returncode, out, err)
    fails = [(rc, out, err) for rc, out, err in outs if rc != 0]
    if any("distributed" in (err or "").lower() for _, _, err in fails):
        pytest.skip(f"jax.distributed unavailable here: {fails[0][2][-200:]}")
    assert not fails, "\n--- worker stderr ---\n".join(
        err[-2000:] for _, _, err in fails)
    return outs


def test_two_process_fleet_sharded_serving_chain():
    """ISSUE 20: the 2-process gloo fleet runs one SHARDED chain through
    the serving Executor mesh path — 2 forced host devices per process,
    use_mesh batch-shards every formed micro-batch across them, outputs
    bit-identical to the single-device oracle."""
    from tests.conftest import free_port

    outs = _run_fleet_pair(
        _MESH_CHAIN_WORKER, free_port(),
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    for rc, out, err in outs:
        assert "MESH_CHAIN_OK 2" in out


def test_cli_flags_thread_through():
    from imaginary_tpu.cli import build_parser, options_from_args

    args = build_parser().parse_args([
        "--distributed", "--coordinator-address", "10.0.0.1:1234",
        "--num-processes", "4", "--process-id", "2",
    ])
    o = options_from_args(args)
    assert o.distributed
    assert o.coordinator_address == "10.0.0.1:1234"
    assert o.num_processes == 4
    assert o.process_id == 2


def test_mesh_hosts_flags_thread_through():
    from imaginary_tpu.cli import build_parser, options_from_args

    args = build_parser().parse_args([
        "--mesh-hosts", "2", "--coordinator-address", "10.0.0.1:1234",
        "--process-id", "1", "--workers", "1",
    ])
    o = options_from_args(args)
    assert o.mesh_hosts == 2
    assert o.process_id == 1

    # a serving mesh needs a coordinator, a pinned process id, and one
    # serving process per host (that process owns the host's chips)
    with pytest.raises(SystemExit):
        options_from_args(build_parser().parse_args(
            ["--mesh-hosts", "2", "--process-id", "0", "--workers", "1"]))
    with pytest.raises(SystemExit):
        options_from_args(build_parser().parse_args(
            ["--mesh-hosts", "2", "--coordinator-address", "10.0.0.1:1",
             "--workers", "1"]))
    with pytest.raises(SystemExit):
        options_from_args(build_parser().parse_args(
            ["--mesh-hosts", "2", "--coordinator-address", "10.0.0.1:1",
             "--process-id", "0", "--workers", "2"]))


def test_mesh_hosts_serving_boot_two_hosts():
    """Tentpole (e): `--mesh-hosts` wires init_distributed into serving
    boot. Two real `python -m imaginary_tpu.cli` processes rendezvous as
    a 2-host jax.distributed fleet, then each serves a real resize over
    HTTP — proving the global backend and the HTTP plane coexist."""
    import json
    import time
    import urllib.request

    from tests.conftest import fixture_bytes, free_port

    coord = free_port()
    p0, p1 = free_port(), free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "imaginary_tpu.cli", "--workers", "1",
             "--mesh-hosts", "2",
             "--coordinator-address", f"127.0.0.1:{coord}",
             "--process-id", str(i), "--port", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=_ROOT, env=env,
        )
        for i, port in enumerate((p0, p1))
    ]
    try:
        body = fixture_bytes("imaginary.jpg")
        deadline = time.monotonic() + 240
        answers = {}
        while time.monotonic() < deadline and len(answers) < 2:
            for port in (p0, p1):
                if port in answers:
                    continue
                if any(p.poll() is not None for p in procs):
                    break  # a host died: fail fast with its stderr
                try:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/resize?width=64",
                        data=body, method="POST",
                        headers={"Content-Type": "image/jpeg"})
                    with urllib.request.urlopen(req, timeout=30.0) as r:
                        assert r.status == 200
                        answers[port] = r.read()
                except (urllib.error.URLError, ConnectionError, OSError):
                    time.sleep(0.5)
            if any(p.poll() is not None for p in procs):
                break
        dead = [p for p in procs if p.poll() is not None]
        if dead:
            err = dead[0].communicate()[1]
            if "distributed" in (err or "").lower():
                pytest.skip(f"jax.distributed unavailable: {err[-200:]}")
            raise AssertionError("mesh host died:\n" + err[-2000:])
        assert len(answers) == 2
        # identical pipeline on both hosts: byte-identical answers
        assert answers[p0] == answers[p1]
    finally:
        import signal as _signal

        for p in procs:
            if p.poll() is None:
                p.send_signal(_signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
