"""Every cell end to end on the CPU at its tiny rehearsal size (the mesh
cell on four virtual devices): paths, arguments and control flow, and the
last line is the contract's object.  No time, rate or share is printed from
a CPU run.  About a minute in all."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _bench(cell, trace, *more, **env):
    """``benchmarks/run.py`` as a child, as the driver starts it."""
    child_env = dict(os.environ, **env)
    child_env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "11", "--seconds", "1",
         "--trace", str(trace), *more],
        env=child_env, cwd=ROOT, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_and_prints_the_contracts_object(cell, trace):
    res = _bench(cell, trace, "--rehearse-cpu")
    assert res.returncode == 0, res.stderr[-2000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    # counts only: a CPU run never gives a time, a rate or a share
    assert set(last["metrics"]) <= {"window_compiles"}
    lines = [json.loads(l) for l in res.stdout.splitlines()
             if l.startswith("{")]
    assert any("inputs" in l and "reference_MBps" in l["inputs"]
               for l in lines)
    assert any("window" in l and "job_walls_s" in l["window"]
               for l in lines)


def test_without_an_accelerator_there_is_no_result():
    """Here JAX has only the CPU: the command must fail and print no
    result line."""
    res = _bench("stream-wc-heaps", 0, JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert '"metrics"' not in res.stdout


def test_unknown_cell_is_an_error():
    res = _bench("no-such-cell", 0)
    assert res.returncode != 0 and "no cell" in res.stderr
