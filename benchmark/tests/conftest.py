import argparse
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture
def small_cell(monkeypatch):
    """ref-trio.paced with two inputs, rungs 1 and 2, at 20 req/s, on the CPU."""
    from harness import registry

    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    real_config, real_traffic = registry.config, registry.traffic

    def config(spec, name, root=registry.ROOT):
        c = real_config(spec, name, root)
        return dict(c, inputs=dict(c["inputs"], count=2), warm_rungs=[1, 2])

    def traffic(name):
        return dict(real_traffic(name), rate_rps=20)

    monkeypatch.setattr(registry, "config", config)
    monkeypatch.setattr(registry, "traffic", traffic)
    return argparse.Namespace(workload="ref-trio.paced", seed=2147483647 + 99, seconds=2.0, trace=0)
