"""No accelerator and no BENCH_PLATFORM=cpu: a nonzero exit and no result."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH

ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "ref-trio.paced", "--seed", "2147483659", "--seconds", "1", "--trace", "0"]


def env_without_chip():
    env = dict(os.environ)
    env.pop("BENCH_PLATFORM", None)
    env["JAX_PLATFORMS"] = "cpu"  # what JAX finds here: the CPU alone
    return env


def no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in stdout.splitlines())


def test_no_accelerator_exits_nonzero_without_a_result():
    r = subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=ROOT,
                       env=env_without_chip(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert no_result(r.stdout), r.stdout
    assert "FAILED" in r.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = env_without_chip()
    env["BENCH_PLATFORM"] = "cpu"
    r = subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert no_result(r.stdout), r.stdout
