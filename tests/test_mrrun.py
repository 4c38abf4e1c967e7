"""The one-command job runner (cli/mrrun.py): real child processes,
oracle-checked — the scripted form of the reference's manual
coordinator+workers choreography (main/test-mr.sh:36-45)."""

import os
import subprocess
import sys

from dsi_tpu.utils.corpus import ensure_corpus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=180, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "dsi_tpu.cli.mrrun"] + args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_mrrun_wc_parity(tmp_path):
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=3,
                          file_size=30_000)
    wd = tmp_path / "job"
    p = _run(["--workers", "2", "--workdir", str(wd), "--check", "wc"]
             + files)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr
    outs = [f for f in os.listdir(wd) if f.startswith("mr-out-")]
    assert len(outs) == 10


def test_mrrun_crash_app_respawns_and_finishes(tmp_path):
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=3,
                          file_size=8_000)
    wd = tmp_path / "job"
    p = _run(["--workers", "2", "--task-timeout", "2.0",
              "--workdir", str(wd), "--check", "crash"] + files,
             env_extra={"DSI_CRASH_EXIT_PROB": "0.3"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr


def test_mrrun_bad_app_fails_fast_without_respawn_storm(tmp_path):
    import time

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=4_000)
    wd = tmp_path / "job"
    t0 = time.monotonic()
    p = _run(["--workers", "2", "--workdir", str(wd), "--timeout", "120",
              "no_such_app"] + files)
    elapsed = time.monotonic() - t0
    assert p.returncode != 0
    assert "failing repeatedly" in p.stderr
    # The instant-death streak detector (same exit code, zero tasks
    # completed) must abort after a handful of respawn rounds — seconds
    # of interpreter startups, not the old ~26-respawn budget that ran
    # the clock toward the 90 s wall.
    assert "consecutive instant deaths" in p.stderr
    assert elapsed < 45


def test_mrrun_journal_resume_keeps_committed_outputs(tmp_path):
    # Resume semantics: with an existing journal, committed mr-out-* files
    # ARE the checkpoint — the resumed coordinator marks journaled tasks
    # done and never regenerates them, so mrrun must NOT sweep them (the
    # no-journal sweep is tested by test_mrrun_reports_coordinator_failure).
    # Re-execution of the *unjournaled* remainder is covered at the
    # coordinator level by tests/test_journal.py.
    from dsi_tpu.mr.journal import Journal

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=10_000)
    wd = tmp_path / "job"
    wd.mkdir()
    jpath = str(wd / "ckpt.journal")

    # A complete run provides the committed outputs of the "crashed" job.
    p = _run(["--workers", "2", "--workdir", str(wd), "--check", "wc"]
             + files)
    assert p.returncode == 0
    committed = {r: (wd / f"mr-out-{r}").read_text() for r in range(10)}

    j = Journal(jpath, [os.path.abspath(f) for f in files], 10)
    j.open()
    for m in range(len(files)):
        j.record("map", m)
    for r in range(10):
        j.record("reduce", r)
    j.close()

    p = _run(["--workers", "2", "--workdir", str(wd),
              "--journal", jpath, "--check", "wc"] + files)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr
    for r in range(10):
        assert (wd / f"mr-out-{r}").read_text() == committed[r]


def test_mrrun_tpu_backend_parity(tmp_path):
    # --backend tpu plumbing end-to-end (kernels pinned to host CPU, the
    # same route scripts/test_mr.sh tpu_wc tpu exercises).
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=20_000)
    wd = tmp_path / "job"
    p = _run(["--workers", "2", "--workdir", str(wd), "--backend", "tpu",
              "--check", "tpu_wc"] + files,
             env_extra={"DSI_JAX_PLATFORM": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr


def test_mrrun_reports_coordinator_failure(tmp_path):
    # A coordinator that cannot start (unauthenticated non-loopback TCP is
    # refused, mr/rpc.py) must surface as a non-zero mrrun exit — never a
    # silent success (and never a stale-output parity pass).
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=4_000)
    wd = tmp_path / "job"
    wd.mkdir()
    (wd / "mr-out-0").write_text("stale 1\n")  # must not survive the run
    p = _run(["--workers", "1", "--workdir", str(wd), "--check", "wc"]
             + files,
             env_extra={"DSI_MR_SOCKET": "tcp:0.0.0.0:0"})
    assert p.returncode != 0
    assert "coordinator exited" in p.stderr
    assert not (wd / "mr-out-0").exists()
