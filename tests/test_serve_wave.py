"""A wave of the benchmark's ``fb12-grep`` shape through a real daemon, at a
small size on the CPU: 12 grep jobs from 8 tenants, submitted by eight
client threads through ``dsi_tpu.serve.client``, against fewer resident
slots than jobs.

Every job's ``grep.json`` must equal, byte for byte once rendered into
lines, what ``benchmarks/reference_servegrep.py`` computes over that job's
own files (a file that imports nothing of the program; loaded here by
path), whatever was evicted to a checkpoint chain and resumed on the way.
The daemon's spans, counters, per-job ``queue_wait_s`` / ``service_s`` and
the ``Status`` ``stats`` section are pinned here too.
"""

import ast
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from dsi_tpu import obs
from dsi_tpu.serve import client
from dsi_tpu.serve.daemon import ServeDaemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")


def _load(name):
    """A benchmark module by path, under its own flat name (they import
    each other that way)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault(name, mod)
    spec.loader.exec_module(mod)
    return mod


corpus = _load("corpus")
reference_grepstats = _load("reference_grepstats")
reference_servegrep = _load("reference_servegrep")

with open(os.path.join(BENCH, "traffic", "fb12-grep.json")) as _f:
    MIX = json.load(_f)

CHUNK = 1 << 10
PARAMS = MIX["reference_params"]


def short_sock() -> str:
    # AF_UNIX paths cap at ~108 bytes; pytest tmp dirs can exceed it.
    return os.path.join(tempfile.mkdtemp(prefix="dsi-sw-"), "s.sock")


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """The cell's 32 files as the benchmark draws them, 8 KiB each: a
    one-file job is 9 rows of 1 KiB, the 12-file job about 100."""
    made = corpus.ensure(str(tmp_path_factory.mktemp("bench_cache")),
                         corpus.effective({"files": 32, "file_bytes": 8192,
                                           "vocab_per_file": 500}, {}), 32)
    return made["files"]


def _render(result):
    """``grep.json`` as the lines ``grepstream`` commits."""
    return ([f"lines {result['lines']}", f"matched {result['matched']}",
             f"occurrences {result['occurrences']}"]
            + [f"hist {b} {n}" for b, n in enumerate(result["hist"])]
            + [f"top {rank} {line_no} {occ}"
               for rank, (line_no, occ) in enumerate(result["topk"])])


def run_wave(sock, files):
    """One wave as the benchmark's driver submits it: the tenants start
    together, each submits its jobs back to back, then waits for them.
    Returns the jobs with their final records and the wave's lines."""
    jobs = reference_servegrep.deal(PARAMS["tenants"], len(files))
    by_tenant = {}
    for job in jobs:
        by_tenant.setdefault(job["tenant"], []).append(job)
    gate = threading.Barrier(len(by_tenant))
    errors = []

    def tenant(name, mine):
        try:
            gate.wait()
            for job in mine:
                rep = client.submit(sock, name,
                                    [files[i] for i in job["files"]],
                                    app="grep", pattern=job["pattern"])
                job.update(rep)
            final = client.wait(sock, [j["job_id"] for j in mine],
                                timeout=240)
            for job in mine:
                job["final"] = final[job["job_id"]]
        except Exception as e:  # noqa: BLE001
            errors.append(f"{name}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=tenant, args=item)
               for item in by_tenant.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    lines = []
    for job in jobs:
        assert job["final"]["state"] == "done", job["final"]
        with open(os.path.join(job["out_dir"], "grep.json")) as f:
            lines += reference_servegrep.job_lines(
                job["tenant"], job["k"], _render(json.load(f)))
    return jobs, sorted(lines)


def test_wave_evicts_resumes_and_every_job_equals_the_reference(
        tmp_path, files):
    """12 jobs against 2 resident slots and a quota of 2 steps: jobs are
    parked on their chains and resumed many times over, and every one of
    them still answers as if its tenant had run alone."""
    want = reference_servegrep.lines(files, PARAMS)
    tracer = obs.configure_tracing(enabled=True)
    since = tracer.mark()
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    devices=1, chunk_bytes=CHUNK, max_resident=2,
                    quota_steps=2, warm=False).start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        before = client.status(d.socket_path)["stats"]
        jobs, got = run_wave(d.socket_path, files)
        reply = client.status(d.socket_path)
    finally:
        d.close()
        obs.configure_tracing(enabled=False)
    assert got == want
    assert len(jobs) == 12 and len({j["tenant"] for j in jobs}) == 8

    # the Status stats section: three scopes, counted from the start
    assert set(before) == {"daemon", "serve", "serve_grep"}
    assert before["daemon"]["submits"] == 0
    stats = reply["stats"]
    daemon, grep = stats["daemon"], stats["serve_grep"]
    assert daemon["submits"] == 12 and daemon["jobs_done"] == 12
    assert daemon["evictions"] >= 1 and daemon["resumes"] >= 1
    assert daemon["evictions"] == \
        daemon["evict_p99"] + daemon["evict_quota"]
    assert daemon["evictions"] == sum(
        t["evictions"] for t in reply["tenants"].values())
    assert daemon["shed"] == 0 and daemon["rate_limited"] == 0
    # a snapshot every 8 confirmed steps and one at every eviction
    assert daemon["ckpt_saves"] >= daemon["evictions"]
    for key in ("submit_s", "admit_s", "evict_s", "finish_s", "ckpt_s"):
        assert daemon[key] > 0.0, key
    assert daemon["ckpt_s"] >= daemon["ckpt_commit_s"] > 0.0
    total = sum(os.path.getsize(f) for f in files)
    assert grep["packed_steps"] * CHUNK >= total
    assert grep["packed_rows"] == grep["packed_steps"]   # one lane
    assert grep["host_fallbacks"] == 0
    for key in ("take_s", "upload_s", "kernel_s", "pull_s", "merge_s"):
        assert grep[key] > 0.0, key
    assert stats["serve"]["packed_steps"] == 0

    # per job: waited for its first row, then was served
    for job in jobs:
        js = job["final"]["stats"]
        assert js["hostpath"] is False
        assert js["queue_wait_s"] >= 0.0 and js["service_s"] >= 0.0
        assert js["queue_wait_s"] + js["service_s"] <= \
            job["final"]["done_ts"] - job["final"]["submitted_ts"] + 0.01

    # the spans, by name, with their fields, and the counters
    events = tracer._events[since:]
    spans = {}
    for ph, name, _lane, _ts, _dur, _depth, fields, *_ in events:
        if ph == "X":
            spans.setdefault(name, []).append(fields or {})
    assert len(spans["submit"]) == 12
    assert {f["tenant"] for f in spans["submit"]} == \
        {t["tenant"] for t in PARAMS["tenants"]}
    assert {f["job"] for f in spans["submit"]} == \
        {j["job_id"] for j in jobs}
    assert len(spans["finish"]) == 12
    assert len(spans["admit"]) == 12 + daemon["resumes"]
    assert sum(1 for f in spans["admit"] if f["resumed"]) == \
        daemon["resumes"]
    assert len(spans["evict"]) == daemon["evictions"]
    assert {f["how"] for f in spans["evict"]} <= {"p99", "quota"}
    assert len(spans["ckpt"]) == daemon["ckpt_saves"]
    assert all(f["bytes"] > 0 for f in spans["ckpt"])
    took = [f for f in spans["take_row"] if f["bytes"]]
    assert len(took) == grep["packed_rows"]
    assert sum(f["bytes"] for f in took) == total + (32 - 12)  # joins
    for name in ("upload", "kernel", "pull", "merge"):
        assert len(spans[name]) == grep["packed_steps"], name
        assert all(f == {"rows": 1, "tenants": 1} for f in spans[name])
    counters = tracer.counters_snapshot()
    for name, scope, key in (("packed_steps", grep, "packed_steps"),
                             ("packed_rows", grep, "packed_rows"),
                             ("evictions", daemon, "evictions"),
                             ("resumes", daemon, "resumes"),
                             ("ckpt_saves", daemon, "ckpt_saves")):
        assert counters[name] == scope[key], name


def test_restarted_daemon_keeps_done_jobs_done_and_takes_the_next_wave(
        tmp_path, files):
    """Nothing is killed: the daemon is stopped between two waves and a
    new one started on the same spool.  The first wave's jobs stay
    ``done`` from the journal and are not run again; the second wave gets
    fresh job ids and the same answers."""
    want = reference_servegrep.lines(files, PARAMS)
    spool = str(tmp_path / "spool")
    kw = dict(devices=1, chunk_bytes=CHUNK, max_resident=8, warm=False)
    d = ServeDaemon(spool, socket_path=short_sock(), **kw).start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        first, got = run_wave(d.socket_path, files)
    finally:
        d.close()
    assert got == want
    outputs = {j["job_id"]: os.stat(os.path.join(
        j["out_dir"], "grep.json")).st_mtime_ns for j in first}

    d = ServeDaemon(spool, socket_path=short_sock(), **kw).start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        time.sleep(0.3)   # a re-run of an old job would start here
        reply = client.status(d.socket_path)
        old = {j["job_id"]: j for j in reply["jobs"]}
        assert set(old) == set(outputs)
        for job in first:
            assert old[job["job_id"]]["state"] == "done"
            assert old[job["job_id"]]["done_ts"] == job["final"]["done_ts"]
        assert reply["stats"]["serve_grep"]["packed_steps"] == 0
        assert reply["stats"]["daemon"]["jobs_done"] == 0
        second, got = run_wave(d.socket_path, files)
        stats = client.status(d.socket_path)["stats"]
    finally:
        d.close()
    assert got == want
    assert not {j["job_id"] for j in second} & set(outputs)
    assert stats["daemon"]["jobs_done"] == 12
    assert stats["serve_grep"]["packed_rows"] == sum(
        j["final"]["stats"]["rows"] for j in second)
    for jid, mtime in outputs.items():   # the old outputs: untouched
        assert os.stat(os.path.join(spool, "out", jid,
                                    "grep.json")).st_mtime_ns == mtime


def test_mrserve_and_mrsubmit_clis_serve_a_job_and_print_the_stats(
        tmp_path, files):
    """The two commands of the deployment, as processes: ``mrserve`` with
    ``--trace-dir``, one job through ``mrsubmit --wait``, ``mrsubmit
    --status`` for the ``stats`` section, ``mrsubmit --shutdown``.  The job
    equals the reference, the daemon prints ``mrserve:
    pipeline_stats={...}`` on its way out and leaves its spans behind."""
    spool, spans = str(tmp_path / "spool"), str(tmp_path / "spans")
    sock = short_sock()
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    daemon = subprocess.Popen(
        [sys.executable, "-m", "dsi_tpu.cli.mrserve", "--spool", spool,
         "--socket", sock, "--devices", "1", "--nreduce", "10",
         "--chunk-bytes", str(CHUNK), "--trace-dir", spans, "--no-warm"],
        env=env, cwd=REPO, stderr=subprocess.PIPE, text=True)
    try:
        client.wait_ready(sock, timeout=120)
        sub = subprocess.run(
            [sys.executable, "-m", "dsi_tpu.cli.mrsubmit", "--socket", sock,
             "--tenant", "t3", "--app", "grep", "--pattern", "ion",
             "--wait", *files[25:27]],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=240)
        assert sub.returncode == 0, sub.stderr[-2000:]
        rep = json.loads(sub.stdout.splitlines()[0])
        with open(os.path.join(rep["out_dir"], "grep.json")) as f:
            got = sorted(_render(json.load(f)))
        assert got == reference_grepstats.lines(
            files[25:27], {"pattern": "ion", "bins": 8, "topk": 16})
        status = subprocess.run(
            [sys.executable, "-m", "dsi_tpu.cli.mrsubmit", "--socket", sock,
             "--status"], env=env, cwd=REPO, capture_output=True, text=True,
            timeout=60)
        stats = json.loads(status.stdout)["stats"]
        assert stats["daemon"]["jobs_done"] == 1
        assert stats["serve_grep"]["packed_steps"] >= 16
        subprocess.run(
            [sys.executable, "-m", "dsi_tpu.cli.mrsubmit", "--socket", sock,
             "--shutdown"], env=env, cwd=REPO, check=True, timeout=60)
        _out, err = daemon.communicate(timeout=120)
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.communicate()
    assert daemon.returncode == 0, err[-2000:]
    line = [l for l in err.splitlines()
            if l.startswith("mrserve: pipeline_stats=")]
    assert len(line) == 1
    printed = ast.literal_eval(line[0].split("=", 1)[1])
    assert printed["serve_grep"]["packed_steps"] == \
        stats["serve_grep"]["packed_steps"]
    assert printed["daemon"]["submits"] == 1
    with open(os.path.join(spans, "trace.jsonl")) as f:
        head = json.loads(f.readline())
        names = {json.loads(l)["name"] for l in f}
    assert {"submit", "admit", "take_row", "ckpt", "finish", "upload",
            "kernel", "pull", "merge"} <= names
    assert head["counters"]["packed_steps"] == \
        stats["serve_grep"]["packed_steps"]


# ── one packed step in flight (PR 54) ──────────────────────────────────
# The scheduler alone, one chip, two lanes that alternate: a call of
# ``step`` dispatches a row and confirms the one the call before
# dispatched.  Nobody who holds a lane learns of the lag.


def _lane(tmp_path, files, name, tag="", every=2):
    """Tenant ``a``'s or ``b``'s lane (one pattern length, so one group)
    over two of the cell's files, 16 KiB in rows of 1 KiB; it resumes the
    chain its checkpoint directory holds, if any."""
    from dsi_tpu.serve.pack import GrepLane

    fs, pattern = {"a": (files[0:2], "the"), "b": (files[2:4], "and")}[name]
    job = {"tenant": name, "pattern": pattern, "files": list(fs)}
    return GrepLane(job, CHUNK, str(tmp_path / (name + tag)),
                    checkpoint_every=every)


def _pair(tmp_path, files, tag="", every=2):
    return (_lane(tmp_path, files, "a", tag, every),
            _lane(tmp_path, files, "b", tag, every))


def _sched():
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.serve.pack import PackedGrepScheduler

    return PackedGrepScheduler(mesh=default_mesh(1), chunk_bytes=CHUNK)


def _oracle(lane):
    from dsi_tpu.parallel.grepstream import grep_host_oracle
    from dsi_tpu.parallel.streaming import stream_files

    return grep_host_oracle(stream_files(lane.job["files"]), lane.pattern)


def _alternate(sched, a, b, calls=None):
    """``step`` with the two lanes' order swapped call by call, so that
    with one row a step they take turns; to the end of both inputs, or
    for ``calls`` calls."""
    n = 0
    while (a.runnable or b.runnable) and (calls is None or n < calls):
        sched.step([a, b] if n % 2 == 0 else [b, a])
        n += 1
    return n


def _taken_end(lane):
    """The stream offset just past the last row the lane has taken."""
    return lane.start_offset + lane.offsets[lane.rows_taken - 1]


def test_a_lane_suspended_with_a_row_in_flight_stands_at_that_row(
        tmp_path, files):
    sched = _sched()
    a, b = _pair(tmp_path, files, "-through")
    _alternate(sched, a, b)
    through = a.finalize(), b.finalize()
    assert through == (_oracle(a), _oracle(b))

    a, b = _pair(tmp_path, files)
    _alternate(sched, a, b, calls=7)     # a: rows 0 2 4 6, b: 1 3 5
    assert sched.in_flight and a.rows_taken == 4
    assert a.confirmed_rows == 3 and a.cursor < _taken_end(a)
    settles = sched.stats["settles"]
    a.suspend()                          # the row in flight is a's
    assert not sched.in_flight and sched.stats["settles"] == settles + 1
    assert a.confirmed_rows == 4 and a.cursor == _taken_end(a)
    b.suspend()                          # nothing of b's is in flight
    assert sched.stats["settles"] == settles + 1
    assert b.confirmed_rows == b.rows_taken == 3
    a2, b2 = _pair(tmp_path, files)      # the same chains, resumed
    assert (a2.start_offset, b2.start_offset) == (a.cursor, b.cursor)
    assert a2.lines == a.lines and a2.confirmed_rows == 4
    _alternate(sched, a2, b2)
    assert (a2.finalize(), b2.finalize()) == through


def test_finalize_with_a_row_in_flight_returns_the_whole_result(
        tmp_path, files):
    sched = _sched()
    a, b = _pair(tmp_path, files)
    while a.runnable:                    # a alone, to the end of its input
        sched.step([a])
    # the call that found the input at its end had no row to dispatch and
    # confirmed the last one: nothing is left in flight
    rows = a.rows_taken
    assert not sched.in_flight and a.confirmed_rows == rows >= 16
    sched.step([b])
    assert sched.in_flight and b.confirmed_rows == 0
    assert a.finalize() == _oracle(a)    # not a's row: nothing settles
    assert sched.in_flight and sched.stats["settles"] == 0
    b.suspend()
    assert not sched.in_flight and sched.stats["settles"] == 1

    c = _lane(tmp_path, files, "a", "-c")   # a's input, call by call
    for _ in range(rows):
        sched.step([c])
    # every row taken, the last one in flight, the end not yet looked for
    assert sched.in_flight and c.runnable
    assert (c.rows_taken, c.confirmed_rows) == (rows, rows - 1)
    assert c.finalize() == _oracle(c)
    assert c.confirmed_rows == rows and not sched.in_flight
    assert sched.stats["settles"] == 2
    assert sched.stats["packed_rows"] == 2 * rows + 1


def test_every_dispatched_step_is_confirmed_once_and_the_counters_say_so(
        tmp_path, files):
    sched = _sched()
    a, b = _pair(tmp_path, files)
    early = 0
    n = 0
    while a.runnable or b.runnable:
        sched.step([a, b] if n % 2 == 0 else [b, a])
        n += 1
        if n in (3, 8) and sched.in_flight:
            # evict and resume whichever lane has the row in flight
            victim = a if a.confirmed_rows < a.rows_taken else b
            assert (a.confirmed_rows < a.rows_taken) != \
                (b.confirmed_rows < b.rows_taken)
            victim.suspend()
            early += 1
            fresh = _lane(tmp_path, files, victim.tenant)
            if victim is a:
                a = fresh
            else:
                b = fresh
    results = a.finalize(), b.finalize()
    assert early == 2
    assert results == (_oracle(a), _oracle(b))
    st = sched.stats
    rows = a.confirmed_rows + b.confirmed_rows
    assert rows >= 32                    # 2 x 16 KiB in rows of 1 KiB
    assert st["packed_rows"] == st["packed_steps"] == rows
    assert 0 <= st["results_ready"] <= st["packed_steps"]
    assert early <= st["settles"] <= early + 1   # a last row, finalized
    assert not sched.in_flight
    assert st["max_tenants_per_step"] == 1 and st["host_fallbacks"] == 0


def test_a_crash_after_a_dispatch_replays_the_rows_that_were_not_confirmed(
        tmp_path, files, monkeypatch):
    from dsi_tpu.ckpt import FaultInjected, reset_faults

    sched = _sched()
    a, b = _pair(tmp_path, files, "-through", every=1)
    _alternate(sched, a, b)
    through = a.finalize(), b.finalize()

    a, b = _pair(tmp_path, files, every=1)
    reset_faults()
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", "post-dispatch")
    monkeypatch.setenv("DSI_FAULT_STEP", "4")
    with pytest.raises(FaultInjected):
        _alternate(sched, a, b)
    for k in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
        monkeypatch.delenv(k)
    # four rows dispatched (a b a b), two confirmed and snapshotted, the
    # third in flight, the fourth's record lost with the "process"
    assert (a.rows_taken, a.confirmed_rows) == (2, 1)
    assert (b.rows_taken, b.confirmed_rows) == (2, 1)
    assert sched.in_flight
    a2, b2 = _pair(tmp_path, files, every=1)   # the chains, as on disk
    assert a2.confirmed_rows == 1 and a2.start_offset == a.cursor > 0
    assert b2.confirmed_rows == 1 and b2.start_offset == b.cursor > 0
    fresh = _sched()                     # a new process has a new packer
    _alternate(fresh, a2, b2)
    assert (a2.finalize(), b2.finalize()) == through


class _Unreadable:
    """A device array whose copy to the host fails."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device lost (injected)")


def test_an_error_at_the_lagged_read_fails_the_jobs_of_that_step_alone(
        tmp_path, files, monkeypatch):
    from dsi_tpu.serve.pack import PackedGrepScheduler

    real = PackedGrepScheduler._dispatch
    dispatched = []

    def dispatch(self, *args):
        outs = real(self, *args)
        dispatched.append(1)
        if len(dispatched) == 3:          # the first job's third row
            return (_Unreadable(),) + tuple(outs[1:])
        return outs

    monkeypatch.setattr(PackedGrepScheduler, "_dispatch", dispatch)
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    devices=1, chunk_bytes=CHUNK, max_resident=2,
                    warm=False)
    reps = [d._rpc_submit({"tenant": t, "app": "grep", "files": fs,
                           "pattern": p})
            for t, fs, p in (("t0", files[0:2], "the"),
                             ("t1", files[2:4], "and"))]
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        final = client.wait(d.socket_path, [r["job_id"] for r in reps],
                            timeout=240)
        stats = client.status(d.socket_path)["stats"]
    finally:
        d.close()
    first, second = (final[r["job_id"]] for r in reps)
    assert first["state"] == "failed"
    assert "packed grep step" in first["error"]
    assert "device lost (injected)" in first["error"]
    assert second["state"] == "done" and second["error"] is None
    with open(os.path.join(reps[1]["out_dir"], "grep.json")) as f:
        got = sorted(_render(json.load(f)))
    assert got == reference_grepstats.lines(
        files[2:4], {"pattern": "and", "bins": 8, "topk": 16})
    # the lost step is counted nowhere.  The second job's rows are, the
    # first job's two confirmed ones, and its fourth: dispatched by the
    # call that lost the third, read in the next one, folded nowhere
    assert stats["serve_grep"]["packed_rows"] == \
        second["stats"]["rows"] + 2 + 1
    assert stats["daemon"]["jobs_done"] == 1


def test_the_scheduler_never_sleeps_on_a_step_in_flight(tmp_path, files):
    """A call that dispatched and confirmed nothing, the first of a
    burst, is work: the daemon's idle wait (0.2 s) may begin only with
    nothing in flight."""
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    devices=1, chunk_bytes=CHUNK, max_resident=2,
                    warm=False)
    slept_on = []
    idle_wait = d._wake.wait

    def wait(timeout=None):
        slept_on.append(d.grep_packer is not None
                        and d.grep_packer.in_flight)
        return idle_wait(timeout)

    d._wake.wait = wait
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        time.sleep(0.3)                  # idle: it sleeps, nothing queued
        for k in range(3):               # three bursts of one small job
            rep = client.submit(d.socket_path, "t0", files[k:k + 1],
                                app="grep", pattern="the")
            final = client.wait(d.socket_path, [rep["job_id"]], timeout=120)
            assert final[rep["job_id"]]["state"] == "done"
            time.sleep(0.25)
        steps = client.status(d.socket_path)["stats"]["serve_grep"]
    finally:
        d.close()
    assert steps["packed_steps"] >= 3 * 8 and steps["settles"] == 0
    assert len(slept_on) >= 3 and not any(slept_on)
