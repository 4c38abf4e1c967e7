"""End-to-end resilience: coordinator crash/resume, and the TCP control
plane — real processes, full wire path.

Journal resume was unit-tested only
(tests/test_journal.py) and TCP+HMAC was exercised only at the RPC layer
(tests/test_rpc.py).  These tests close both gaps at the process level:

* SIGKILL the coordinator mid-job, restart it with the same ``--journal``,
  and require completion with oracle parity — the capability the reference
  lacks entirely (its coordinator state is process-local,
  ``mr/coordinator.go:17,21``; death loses the job).
* Run the whole job over ``DSI_MR_SOCKET=tcp:127.0.0.1:0`` with a shared
  ``DSI_MR_SECRET``: the coordinator announces its kernel-assigned port,
  workers join over authenticated TCP — the reference's intended
  multi-host variant (``mr/coordinator.go:124``, ``mr/worker.go:173``).
"""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from dsi_tpu.utils.corpus import ensure_corpus
from tests.harness import merged_output, oracle_output

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(args, cwd, env, **kw):
    kw.setdefault("stdout", subprocess.DEVNULL)
    kw.setdefault("stderr", subprocess.DEVNULL)
    return subprocess.Popen([sys.executable, "-m", *args], cwd=cwd, env=env,
                            **kw)


def _base_env(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DSI_MR_SOCKET"] = str(tmp_path / "mr.sock")
    return env


def _journaled_maps(jpath: str) -> int:
    """Completed-map records currently in the journal (0 if absent)."""
    if not os.path.exists(jpath):
        return 0
    n = 0
    with open(jpath, "rb") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except (json.JSONDecodeError, UnicodeDecodeError):
                break
            if isinstance(rec, dict) and rec.get("kind") == "map":
                n += 1
    return n


@pytest.mark.slow
def test_coordinator_crash_resume_e2e(tmp_path):
    """SIGKILL the coordinator after >=1 journaled map completion but
    before the job ends; a restarted coordinator on the same journal plus
    fresh workers must finish with oracle parity."""
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=12,
                          file_size=60_000)
    wd = str(tmp_path)
    want = oracle_output("wc", files, wd)
    env = _base_env(tmp_path)
    jpath = str(tmp_path / "journal")
    coord_args = ["dsi_tpu.cli.mrcoordinator", "--journal", jpath,
                  "--task-timeout", "2.0", *files]

    coord = _spawn(coord_args, wd, env)
    workers = []
    try:
        time.sleep(0.5)  # socket-creation grace (test-mr.sh:39-40)
        workers = [_spawn(["dsi_tpu.cli.mrworker", "wc"], wd, env)
                   for _ in range(2)]
        deadline = time.time() + 60
        while _journaled_maps(jpath) < 1:
            if time.time() > deadline:
                pytest.fail("no map completion journaled in 60s")
            if coord.poll() is not None:
                pytest.fail("job finished before the crash could be "
                            "injected; enlarge the corpus")
            time.sleep(0.02)
        coord.kill()  # SIGKILL mid-job: no cleanup, journal is all that survives
        coord.wait(timeout=10)
        assert _journaled_maps(jpath) < len(files), \
            "crash landed after all maps finished; enlarge the corpus"
        # Orphaned workers exit on their own once the socket is gone
        # (worker.go:173 semantics: unreachable coordinator = job over).
        for w in workers:
            w.wait(timeout=30)

        coord = _spawn(coord_args, wd, env)
        time.sleep(0.5)
        workers = [_spawn(["dsi_tpu.cli.mrworker", "wc"], wd, env)
                   for _ in range(2)]
        assert coord.wait(timeout=90) == 0
        for w in workers:
            w.wait(timeout=30)
    finally:
        for p in (coord, *workers):
            if p.poll() is None:
                p.kill()
    assert merged_output(wd) == want
    assert len(want) > 1000


@pytest.mark.slow
def test_tcp_control_plane_e2e(tmp_path):
    """Full job over authenticated TCP: coordinator on tcp:127.0.0.1:0
    announces its kernel-assigned address; 3 workers join over it."""
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=5,
                          file_size=50_000)
    wd = str(tmp_path)
    want = oracle_output("wc", files, wd)
    env = _base_env(tmp_path)
    env["DSI_MR_SOCKET"] = "tcp:127.0.0.1:0"
    env["DSI_MR_SECRET"] = "e2e-shared-secret"

    errpath = tmp_path / "coord.err"
    with open(errpath, "w") as errf:
        coord = _spawn(["dsi_tpu.cli.mrcoordinator", *files], wd, env,
                       stderr=errf)
    workers = []
    try:
        addr = None
        deadline = time.time() + 30
        while addr is None:
            if time.time() > deadline:
                pytest.fail("coordinator never announced its TCP address")
            m = re.search(r"listening on (tcp:\S+)",
                          errpath.read_text(errors="replace"))
            if m:
                addr = m.group(1)
            else:
                time.sleep(0.05)
        wenv = dict(env)
        wenv["DSI_MR_SOCKET"] = addr
        workers = [_spawn(["dsi_tpu.cli.mrworker", "wc"], wd, wenv)
                   for _ in range(3)]
        assert coord.wait(timeout=90) == 0
        for w in workers:
            w.wait(timeout=30)
    finally:
        for p in (coord, *workers):
            if p.poll() is None:
                p.kill()
    assert merged_output(wd) == want
