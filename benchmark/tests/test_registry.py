"""A cell, a traffic mix, a configuration and a metric are added by adding
files and BENCHMARK.json entries: the harness finds them by name."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH

ROOT = os.path.dirname(BENCH)


def copy_bench(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def snapshot(root):
    out = {}
    for d, _, files in os.walk(root / "benchmark"):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_new_cell_traffic_config_and_metric_are_found(tmp_path):
    root = copy_bench(tmp_path)
    before = snapshot(root)
    b = root / "benchmark"
    (b / "traffic" / "trio.bursty.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 10, "routes": [
            {"name": "resize", "path": "/resize?width=300", "oracle": "resize",
             "format": "JPEG", "quality": 80, "limit": 0.1}]}))
    (b / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "server_args": [], "warm_rungs": [1],
         "inputs": {"generator": "photo_jpeg", "count": 1, "width": 64, "height": 48,
                    "quality": 90, "roughness": 0.8, "contrast": 30}}))
    (b / "metrics" / "new_thing_ms.paced.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx.records else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "https://example.org/tiny",
                            "file": "benchmark/configs/tiny.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.bursty", "config": "tiny", "traffic": "trio.bursty",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new_thing_ms.paced", "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "device", "moves": "p50_ms",
                              "workloads": ["tiny.bursty"]})
    spec["end_to_end"][0]["workloads"].append("tiny.bursty")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = r"""
import json, sys
sys.path.insert(0, "benchmark")
from harness import registry
spec = registry.load_spec()
cell = registry.cell(spec, "tiny.bursty")
cfg = registry.config(spec, cell["config"])
tr = registry.traffic(cell["traffic"])
inputs = registry.generator(cfg["inputs"]["generator"])(cfg["inputs"], 7)
per_layer = [m["name"] for m in registry.cell_metrics(spec, "tiny.bursty", "per_layer")]
e2e = [m["name"] for m in registry.cell_metrics(spec, "tiny.bursty", "end_to_end")]
class C: records = [1]
print(json.dumps({"per_layer": per_layer, "e2e": e2e, "rate": tr["rate_rps"],
                  "n": len(inputs), "value": registry.metric("new_thing_ms.paced")(C()),
                  "oracle": callable(registry.oracle(tr["routes"][0]["oracle"]))}))
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["per_layer"] == ["new_thing_ms.paced"]
    assert got["e2e"] == ["p50_ms", "setup_s"]
    assert got["rate"] == 10 and got["n"] == 1 and got["value"] == 42.0 and got["oracle"]
    after = snapshot(root)
    changed = [k for k in before if after.get(k) != before[k]]
    assert changed == [], changed


def test_metric_without_workloads_follows_the_metric_it_moves():
    from harness import registry

    spec = {"end_to_end": [{"name": "served_rps", "workloads": ["a"]}, {"name": "setup_s"}],
            "per_layer": [{"name": "x", "moves": "served_rps"},
                          {"name": "y", "moves": "setup_s"},
                          {"name": "z", "moves": "served_rps", "workloads": ["b"]}]}
    assert [m["name"] for m in registry.cell_metrics(spec, "a", "per_layer")] == ["x", "y"]
    assert [m["name"] for m in registry.cell_metrics(spec, "b", "per_layer")] == ["y", "z"]
