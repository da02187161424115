"""`/health` stats (ref: health.go:17-63).

The reference reports Go runtime memory/GC stats; the meaningful analogues
here are process RSS, thread count, the jit compile cache, the micro-batch
executor counters, and the device inventory — the things an operator of THIS
runtime needs (SURVEY.md section 5.5's guidance: keep the shape, add
batch-occupancy and device utilization).
"""

from __future__ import annotations

import os
import threading
import time

_START = time.time()


def _rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 2)
    except OSError:
        pass
    return 0.0


def get_health_stats(executor=None, qos=None, pressure=None,
                     slo=None, cost=None) -> dict:
    import gc

    stats = {
        "uptime": round(time.time() - _START, 2),
        "allocatedMemoryMb": _rss_mb(),
        "threads": threading.active_count(),
        "cpus": os.cpu_count() or 1,
        "gcCollections": sum(s["collections"] for s in gc.get_stats()),
        # which serving process answered: under --workers N each worker
        # has its own executor/caches, so an operator debugging a skewed
        # fleet needs to attribute /health samples to processes
        "pid": os.getpid(),
    }
    from imaginary_tpu.web.workers import worker_epoch, worker_index

    stats["worker"] = worker_index()
    # the supervisor-stamped fencing generation (web/workers.py): the
    # rolling-restart harness asserts these are monotonic per index, and
    # the roll's ready-gate matches on (worker, epoch) since SO_REUSEPORT
    # makes the old and new holder of an index indistinguishable by port
    stats["epoch"] = worker_epoch()
    # the host-level incarnation (fleet/multihost.py): present only when
    # the multi-host plane stamped an identity into the env — absent =
    # single-host parity, same presence-is-the-signal discipline as the
    # blocks below
    from imaginary_tpu.fleet import multihost

    if multihost.host_id():
        stats["host"] = {"id": multihost.host_id(),
                         "epoch": multihost.host_epoch()}
    import jax

    # a backend-init failure propagates (the request fails loudly)
    # rather than reading as a host with zero devices
    devs = jax.devices()
    stats["devices"] = len(devs)
    stats["backend"] = devs[0].platform
    stats["device_kind"] = devs[0].device_kind
    # per local device, where the backend reports it (the CPU does not)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    if any(p is not None for p in peaks):
        stats["peak_bytes_in_use"] = peaks
    if executor is not None:
        stats["executor"] = executor.stats.to_dict()
        # per-device fault domains (engine/devhealth.py): state, breaker
        # counters, error/latency EWMAs, probe/readmission history for
        # every chip — one quarantined device must be visible here long
        # before it becomes a fleet-wide outage. /metrics renders the
        # same block as imaginary_tpu_device_state so the two surfaces
        # cannot drift.
        stats["deviceHealth"] = executor.devhealth.snapshot()
        integ = getattr(executor, "integrity", None)
        if integ is not None:
            # output-integrity defense (engine/integrity.py): sampled
            # cross-verification counters + poison quarantine occupancy;
            # /metrics renders the same block as imaginary_tpu_integrity_*
            # so the two surfaces cannot drift. Absent with --integrity
            # off — the block's presence IS the armed/parity signal.
            stats["integrity"] = integ.snapshot()
    from imaginary_tpu import codecs

    # host codecs: decodes served, and those that read only an /extract's
    # window of the frame
    stats["codecs"] = codecs.decode_counts()
    if qos is not None:
        # per-class qos counters + live queue depths (qos/shed.py
        # QosStats); /metrics renders the same block as
        # imaginary_tpu_qos_* so the two surfaces cannot drift
        stats["qos"] = qos.stats.to_dict()
    if pressure is not None:
        # memory-pressure governor (engine/pressure.py): current rung,
        # the sampled RSS/occupancy signals, per-rung transition counters
        # and ladder-action counts; /metrics renders the same block as
        # imaginary_tpu_pressure_* so the two surfaces cannot drift
        stats["pressure"] = pressure.snapshot()
    if slo is not None:
        # per-route burn rates over 5m/1h windows (obs/slo.py); /metrics
        # renders the same block as imaginary_tpu_slo_* so the two
        # surfaces cannot drift. Absent with --slo-config unset — the
        # block's presence IS the armed/parity signal.
        stats["slo"] = slo.snapshot()
    if cost is not None:
        # cost attribution + capacity plane (obs/cost.py): per-tenant
        # cost windows, utilization timelines, live bound_by verdict;
        # /metrics renders the same block as imaginary_tpu_cost_* /
        # imaginary_tpu_utilization_* so the two surfaces cannot drift.
        # Absent with --cost-attribution unset — the block's presence IS
        # the armed/parity signal.
        stats["capacity"] = cost.snapshot()
    from imaginary_tpu.engine.timing import TIMES

    stage_times = TIMES.snapshot()
    if stage_times:
        stats["stageTimesMs"] = stage_times
    return stats
