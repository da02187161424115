"""Finding a cell's parts by name, so that a later PR adds a cell, a
configuration, a traffic mix, a generator, an oracle or a metric by adding
files and entries, and edits none.

- BENCHMARK.json at the repo root: the cells, configurations and metrics;
- a configuration: the file its BENCHMARK.json entry names;
- a traffic mix: benchmark/traffic/<traffic>.json;
- an input generator: benchmark/inputs/<generator>.py, `generate(params, seed)`;
- an oracle: benchmark/oracles/<operation>.py, `expected(src, query, ops)`;
- a metric: benchmark/metrics/<name>.py, `read(ctx)`, returning a number or
  None where it finds nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

_modules: dict = {}


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    key = (kind, name)
    if key not in _modules:
        path = os.path.join(BENCH, kind, name + ".py")
        if not os.path.exists(path):
            raise KeyError(f"no {kind[:-1]} named {name!r}: {path} is missing")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[key] = mod
    return _modules[key]


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(spec: dict, name: str, root: str = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration named {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    path = os.path.join(BENCH, "traffic", name + ".json")
    if not os.path.exists(path):
        raise KeyError(f"no traffic mix named {name!r}: {path} is missing")
    with open(path) as f:
        return json.load(f)


def generator(name: str):
    return _load_module("inputs", name).generate


def oracle(name: str):
    return _load_module("oracles", name).expected


def metric(name: str):
    return _load_module("metrics", name).read


def cell_metrics(spec: dict, cell_name: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries this cell reports. A metric
    without `workloads` is reported by every cell that reports the
    end-to-end metric it moves (for an end-to-end metric: by every cell)."""
    e2e = [m for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m else m["moves"] in names)]
