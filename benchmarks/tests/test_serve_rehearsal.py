"""``serve-grep-fb12`` end to end on the CPU at its rehearsal size (32 files
of 64 KiB, rows of 4 KiB: the wave's 12 jobs keep their shape in rows),
through ``benchmarks/run.py`` as the driver starts it: the daemon as a
child, the wave through the client library, the comparison with the
reference, and no ``mrserve`` process left when the run is over, whether it
ended with a result or without one."""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bench(*more, **env):
    child_env = dict(os.environ, **env)
    child_env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "serve-grep-fb12", "--seed", "2147483659",
         "--seconds", "1", *more],
        env=child_env, cwd=ROOT, capture_output=True, text=True, timeout=600)


def _daemons_of(checkout: str) -> list:
    """Command lines of live ``mrserve`` processes whose spool lies under
    ``checkout``'s work directory."""
    mark = os.path.join(checkout, ".bench_cache", "work", "serve-grep-fb12")
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if "dsi_tpu.cli.mrserve" in cmd and mark in cmd:
            found.append(cmd)
    return found


def test_a_rehearsed_wave_is_correct_evicts_and_leaves_no_daemon():
    res = _bench("--trace", "1", "--rehearse-cpu")
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [json.loads(l) for l in res.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) <= {"window_compiles"}   # counts only
    waves = [l["job"] for l in lines if "job" in l]
    assert waves and all(w["rc"] == 0 for w in waves)
    for wave in waves:
        counts = wave["counts"]
        assert counts["jobs_done"] == 12
        assert counts["packed_steps"] * 4096 >= 32 * 65536
        assert counts["evictions"] >= 1 and counts["resumes"] >= 1
        assert counts["ckpt_saves"] >= counts["evictions"]
        jobs = wave["serve"]["jobs"]
        assert len(jobs) == 12 and {j["state"] for j in jobs} == {"done"}
        assert len({j["tenant"] for j in jobs}) == 8
        assert not any(j["stats"]["hostpath"] for j in jobs)
    warm = [l["warm_up"] for l in lines if "warm_up" in l]
    assert warm and warm[0]["jobs_done"] == 12
    assert any("daemon" in l and l["daemon"]["rc"] == 0 for l in lines)
    time.sleep(0.2)
    assert _daemons_of(ROOT) == []


def test_without_an_accelerator_no_result_and_no_daemon_left():
    """Here JAX has only the CPU and the run does not ask for it by name:
    ``mrserve`` refuses to start, the run ends at once without a result,
    and nothing is left behind."""
    t0 = time.monotonic()
    res = _bench("--trace", "0", JAX_PLATFORMS="")
    assert res.returncode != 0
    assert '"metrics"' not in res.stdout
    assert "mrserve" in res.stderr
    assert time.monotonic() - t0 < 120
    time.sleep(0.2)
    assert _daemons_of(ROOT) == []
