"""A whole run on the CPU (BENCH_PLATFORM=cpu skips the look for a chip),
with the timed path broken underneath: the program's chain program is
patched in the server process, and `correct` must come out false. The
faults a serving cell can have: an answer altered where it is produced (the
top rows of every output zeroed), and half of the batch left out (its items
returned as zeros). A stateless server has no state to leave unchanged and
the lanes exchange nothing between chips, so those two do not apply. The
same run unpatched must come out correct."""

import asyncio  # noqa: F401  (run.py's loop)

import pytest

import run
from harness.server import Server

PATCH = {
    "altered": "x = x.at[:, :16].set(0)",
    "half_batch": "x = x.at[x.shape[0] // 2:].set(0)",
}

LAUNCH = """
import runpy, sys
import imaginary_tpu.ops.chain as chain
_orig = chain._run_chain
def _faulty(specs, x, h, w, dyns):
    x, h, w = _orig(specs, x, h, w, dyns)
    {fault}
    return x, h, w
chain._run_chain = _faulty
runpy.run_module("imaginary_tpu", run_name="__main__", alter_sys=True)
"""


def test_sound_program_is_correct(small_cell):
    result = run.run(small_cell)
    assert result["correct"], result["checks"]
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(PATCH))
def test_fault_makes_the_run_incorrect(small_cell, monkeypatch, fault):
    monkeypatch.setattr(Server, "launcher", ("-c", LAUNCH.format(fault=PATCH[fault])))
    result = run.run(small_cell)
    assert not result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
