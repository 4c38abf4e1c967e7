"""Driver: the deployment is one resident ``python -m dsi_tpu.cli.mrserve``
child that holds the chip for the whole run; a job of the harness is one
wave of the traffic mix's jobs, submitted by eight client threads.

The harness is the tenants: it never imports JAX (the chip is the
daemon's, as it is the device worker's in the batch cells) and talks to
the daemon only through the program's own client library
(``dsi_tpu.serve.client``, which ``cli/mrsubmit.py`` wraps; loaded here by
name).  What it knows about the device comes from the daemon itself,
through ``hooks/sitecustomize.py``: device kind, memory peak, compile
counts, and in a traced run one profiler trace from the daemon's
backend-up for at most ``trace_seconds``.

A run: ``claim_device`` starts the daemon and waits until it answers.  A
daemon that does not answer, or whose ``Status`` reports no ``stats``
section (a program from before the served path had one), ends the run at
once with no result.  ``warm_up`` waits for ``mrserve: ready`` (the boot
warm) and runs one whole wave, in which the grep step program loads or
compiles.  Then the window: waves back to back, fresh job ids and outputs
each, the daemon and its spool kept across waves as a service keeps them.
A traced run stops the first daemon once it has answered and starts a
fresh one when the inputs are ready, so that the hook's window holds the
boot warm, the warm-up wave and at least one whole measured wave.

One wave: the mix's tenants start together, one thread each; a thread
submits its tenant's jobs back to back without waiting, then waits until
all of them are ``done`` (the client's own ``wait``).  ``t_start`` is the
first submit, ``t_end`` the last ``done`` seen.  ``Status`` without a job id
is read before and after; the difference is the wave's ``stats``.  Each
job's ``grep.json`` is rendered into the lines ``grepstream`` commits,
prefixed ``<tenant>/<k>``, as one ``mr-out-*`` file of the wave's work
directory, which ``run.py`` compares with ``reference_servegrep.py``.

The daemon's process group is killed on every way out of this process
(``finish``, a failure, an exception, a signal, ``atexit``), and the
kernel kills the daemon if this process dies without a word: a daemon left
holding the chip would hang every cell that runs after it.

Importing this file registers the plain reference of kind ``servegrep``.
"""

from __future__ import annotations

import atexit
import ctypes
import glob
import importlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import reference
import reference_servegrep
from drivers._common import adopt_trace
from drivers.mrrun_child import _child_env

reference.KINDS.setdefault("servegrep", reference_servegrep.lines)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Every daemon this process has started and not yet seen exit.
_LIVE: list = []
_ARMED = False


def _bench_error(message: str) -> Exception:
    """``run.py``'s ``BenchError`` (the command is ``__main__``), so that the
    run ends as every run without a result does."""
    cls = getattr(sys.modules.get("__main__"), "BenchError", RuntimeError)
    return cls(message)


def _kill_all() -> None:
    while _LIVE:
        d = _LIVE.pop()
        try:
            os.killpg(d["proc"].pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        d["proc"].wait()


def _arm() -> None:
    """Whatever ends this process ends the daemon."""
    global _ARMED
    if _ARMED:
        return
    _ARMED = True
    atexit.register(_kill_all)

    def on_signal(sig, _frame):
        _kill_all()
        sys.exit(128 + sig)

    for sig in (signal.SIGTERM, signal.SIGHUP):
        signal.signal(sig, on_signal)


def _die_with_parent() -> None:
    """In the child, before exec: SIGKILL when the harness dies, even if it
    is killed outright (``PR_SET_PDEATHSIG``)."""
    ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGKILL)


def _client(cell):
    if cell.root not in sys.path:
        sys.path.insert(0, cell.root)
    return importlib.import_module("dsi_tpu.serve.client")


def _start(cell, traced: bool) -> dict:
    """One ``mrserve`` child in a session of its own."""
    _arm()
    home = os.path.join(cell.workroot, f"daemon-{int(traced)}")
    spool = os.path.join(home, "spool")
    os.makedirs(home)
    flags = dict(cell.config["mrserve"])
    if cell.rehearsal:
        flags["chunk_bytes"] = cell.config["rehearsal"].get(
            "chunk_bytes", flags["chunk_bytes"])
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrserve", "--spool", spool,
           "--devices", str(flags["devices"]),
           "--nreduce", str(flags["nreduce"]),
           "--chunk-bytes", str(flags["chunk_bytes"]),
           "--trace-dir", os.path.join(home, "spans")]
    sock = os.path.join(spool, "mrserve.sock")
    if len(sock) >= 96:   # a Unix socket path may not exceed ~100 bytes
        sock = os.path.join(tempfile.gettempdir(),
                            f"bm-{os.getpid()}-{int(traced)}.sock")
        cmd += ["--socket", sock]
    # the hook's environment, as the batch cells' device worker gets it
    env = _child_env(cell, os.path.join(home, "hook"), traced)
    log_path = os.path.join(home, "mrserve.log")
    with open(log_path, "w") as out:
        proc = subprocess.Popen(cmd, env=env, cwd=cell.root, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True,
                                preexec_fn=_die_with_parent)
    d = {"proc": proc, "home": home, "sock": sock, "log": log_path,
         "hook": env["BENCH_HOOK_OUT"], "traced": traced,
         "chunk_bytes": int(flags["chunk_bytes"]),
         "t_spawn": time.monotonic()}
    _LIVE.append(d)
    return d


def _log_tail(d: dict, n: int = 3000) -> str:
    try:
        with open(d["log"], errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def _stop(d: dict, grace_s: float = 60.0) -> int:
    """Ask the daemon to stop (it parks what is resident, prints its
    statistics and flushes its trace), wait, then make sure of the whole
    group.  Returns its exit code."""
    proc = d["proc"]
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    rc = proc.wait()
    if d in _LIVE:
        _LIVE.remove(d)
    if d["sock"].startswith(tempfile.gettempdir()):
        try:
            os.remove(d["sock"])
        except OSError:
            pass
    return rc


def _fail(cell, d: dict, why: str) -> Exception:
    """Stop the daemon, show what it said, and end the run."""
    _stop(d, grace_s=5.0)
    sys.stderr.write(_log_tail(d))
    return _bench_error(f"cell {cell.name}: {why}")


def _poll(cell, d: dict, what: str, timeout_s: float, ask):
    """``ask()`` until it returns something, while the daemon lives."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if d["proc"].poll() is not None:
            raise _fail(cell, d, f"mrserve exited {d['proc'].returncode} "
                                 f"before {what}")
        got = ask()
        if got:
            return got
        time.sleep(0.05)
    raise _fail(cell, d, f"mrserve: no {what} within {timeout_s:.0f} s")


def _hook_reports(d: dict) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(d["hook"], "device-*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def _answer(cell, d: dict) -> None:
    """Wait until the daemon answers on its socket, and hold it to the
    statistics this cell reads; adopt the device it reports."""
    client = _client(cell)

    def ping():
        try:
            return client.ping(d["sock"], timeout=2.0)
        except Exception:  # noqa: BLE001 (not up yet)
            return None

    _poll(cell, d, "answer on its socket", 120.0, ping)
    try:
        stats = client.status(d["sock"]).get("stats")
    except Exception as e:  # noqa: BLE001
        raise _fail(cell, d, f"mrserve: Status failed: {e}")
    if not isinstance(stats, dict) or "daemon" not in stats:
        raise _fail(cell, d, "mrserve's Status reports no scheduler "
                             "statistics (no 'stats' section): this "
                             "program cannot run the cell")
    report = _poll(cell, d, "device report from the hook", 30.0,
                   lambda: [h for h in _hook_reports(d) if "platform" in h])
    first = report[0]
    cell.device = {"platform": first["platform"], "kind": first["kind"],
                   "count": first["count"]}


def claim_device(cell) -> None:
    """The chip is the daemon's: start it, and learn from it what it holds."""
    cell.obs["daemon"] = d = _start(cell, traced=False)
    _answer(cell, d)


def _ready(cell, d: dict) -> float:
    """Seconds from the daemon's spawn to ``ready`` (the boot warm done)."""
    client = _client(cell)

    def ready():
        try:
            return client.ping(d["sock"], timeout=5.0).get("ready")
        except Exception:  # noqa: BLE001
            return None

    _poll(cell, d, "'ready' (the boot warm)",
          float(cell.config.get("boot_timeout_s", 900)), ready)
    return time.monotonic() - d["t_spawn"]


def _render(result: dict) -> list:
    """A job's ``grep.json`` as the lines ``grepstream`` commits."""
    return ([f"lines {result['lines']}", f"matched {result['matched']}",
             f"occurrences {result['occurrences']}"]
            + [f"hist {b} {n}" for b, n in enumerate(result["hist"])]
            + [f"top {rank} {line_no} {occ}"
               for rank, (line_no, occ) in enumerate(result["topk"])])


def _diff(before, after):
    """``after - before`` through the scopes, numbers only."""
    if isinstance(after, dict):
        return {k: _diff((before or {}).get(k), v) for k, v in after.items()
                if isinstance(v, (dict, int, float))
                and not isinstance(v, bool)}
    return after - (before or 0)


def _wave(cell, d: dict, workdir: str) -> dict:
    """One wave through the client library; its record."""
    client = _client(cell)
    sock = d["sock"]
    os.makedirs(workdir)
    params = cell.traffic["reference_params"]
    jobs = reference_servegrep.deal(params["tenants"], len(cell.files))
    by_tenant: dict = {}
    for job in jobs:
        by_tenant.setdefault(job["tenant"], []).append(job)
    gate = threading.Barrier(len(by_tenant))
    timeout = float(cell.config.get("wave_timeout_s", 600))
    marks, errors = [], []

    def tenant(name: str, mine: list) -> None:
        try:
            gate.wait()
            t0 = time.monotonic()
            for job in mine:
                rep = client.submit(
                    sock, name, [cell.files[i] for i in job["files"]],
                    app=cell.traffic["app"], pattern=job["pattern"])
                job["job_id"], job["out_dir"] = rep["job_id"], rep["out_dir"]
            final = client.wait(sock, [j["job_id"] for j in mine],
                                timeout=timeout)
            t1 = time.monotonic()
            for job in mine:
                job["final"] = final[job["job_id"]]
            marks.append((t0, t1))
        except Exception as e:  # noqa: BLE001 (the wave fails, says why)
            errors.append(f"tenant {name}: {type(e).__name__}: {e}")

    before = client.status(sock)["stats"]
    wall_start = time.time()
    threads = [threading.Thread(target=tenant, args=(n, m), name=f"tenant-{n}")
               for n, m in by_tenant.items()]
    t_fallback = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_end = time.time()
    after = client.status(sock)["stats"]
    t_start = min((m[0] for m in marks), default=t_fallback)
    t_end = max((m[1] for m in marks), default=time.monotonic())

    records = []
    for n, job in enumerate(jobs):
        final = job.get("final") or {}
        rec = {"tenant": job["tenant"], "k": job["k"],
               "job_id": job.get("job_id"), "files": len(job["files"]),
               "state": final.get("state"), "error": final.get("error"),
               "stats": final.get("stats") or {}}
        records.append(rec)
        if rec["state"] != "done":
            continue
        try:
            with open(os.path.join(job["out_dir"], "grep.json")) as f:
                rendered = _render(json.load(f))
        except (OSError, ValueError, KeyError) as e:
            errors.append(f"job {rec['job_id']}: no readable grep.json: {e}")
            continue
        with open(os.path.join(workdir, f"mr-out-{n}"), "w") as f:
            f.write("\n".join(reference_servegrep.job_lines(
                job["tenant"], job["k"], rendered)) + "\n")
    return {"rc": 1 if errors else 0, "t_start": t_start, "t_end": t_end,
            "wall_s": round(t_end - t_start, 4), "workdir": workdir,
            "log_text": "\n".join(errors), "compiles": 0,
            "serve": {"wall_s": t_end - t_start, "wall_start": wall_start,
                      "wall_end": wall_end, "chunk_bytes": d["chunk_bytes"],
                      "stats": _diff(before, after), "jobs": records,
                      "errors": errors}}


def _counts(wave: dict) -> dict:
    """What a wave did, in counts (for the log)."""
    stats = wave["serve"]["stats"]
    grep, daemon = stats.get("serve_grep", {}), stats.get("daemon", {})
    return {"packed_steps": grep.get("packed_steps"),
            "packed_rows": grep.get("packed_rows"),
            "evictions": daemon.get("evictions"),
            "resumes": daemon.get("resumes"),
            "ckpt_saves": daemon.get("ckpt_saves"),
            "jobs_done": daemon.get("jobs_done")}


def warm_up(cell) -> None:
    """Wait for the boot warm, then one whole wave: the grep step program
    of the mix's pattern length compiles (first run in a checkout) or
    loads from the compile cache (every later run) inside it, and not in
    the window's first wave."""
    d = cell.obs["daemon"]
    if cell.trace and not cell.rehearsal:
        _stop(d)
        cell.obs["daemon"] = d = _start(cell, traced=True)
        _answer(cell, d)
    boot_s = _ready(cell, d)
    wave = _wave(cell, d, os.path.join(cell.workroot, "warm"))
    print(json.dumps({"warm_up": {
        "boot_to_ready_s": round(boot_s, 3), "wall_s": wave["wall_s"],
        "rc": wave["rc"], **_counts(wave)}}), flush=True)
    cell.obs["warm_up"] = wave
    bad = [j for j in wave["serve"]["jobs"] if j["state"] != "done"]
    if wave["rc"] != 0 or bad:
        sys.stderr.write(wave["log_text"] + "\n")
        raise _fail(cell, d, "the warm-up wave did not complete: "
                             f"{[(j['job_id'], j['state'], j['error']) for j in bad]}")


def run_job(cell, i: int) -> dict:
    d = cell.obs["daemon"]
    wave = _wave(cell, d, os.path.join(cell.workroot, f"job-{i}"))
    wave.update({"i": i, "bytes": cell.job_bytes, "traced": d["traced"],
                 "counts": _counts(wave)})
    if wave["rc"] != 0:
        sys.stderr.write(wave["log_text"] + "\n" + _log_tail(d))
    return wave


def job_problems(cell, job: dict) -> list:
    """Every job of the wave done on the device, nothing shed, and at
    least as many packed steps as the wave's bytes need."""
    problems = list(job["serve"]["errors"])
    records = job["serve"]["jobs"]
    stats = job["serve"]["stats"]
    daemon, grep = stats.get("daemon", {}), stats.get("serve_grep", {})
    done = [j for j in records if j["state"] == "done"]
    if len(done) != len(records):
        problems.append(
            f"{len(done)} of {len(records)} jobs done: "
            f"{[(j['job_id'], j['state'], j['error']) for j in records if j['state'] != 'done']}")
    hostpath = [j["job_id"] for j in done if j["stats"].get("hostpath")]
    if hostpath or grep.get("host_fallbacks"):
        problems.append(f"jobs on the host path: {hostpath} "
                        f"(host_fallbacks {grep.get('host_fallbacks')})")
    if daemon.get("shed") or daemon.get("rate_limited"):
        problems.append(f"shed {daemon.get('shed')}, rate_limited "
                        f"{daemon.get('rate_limited')}")
    chunk = job["serve"]["chunk_bytes"]
    if (grep.get("packed_steps") or 0) * chunk < cell.job_bytes:
        problems.append(f"packed_steps {grep.get('packed_steps')} of "
                        f"{chunk} B cannot hold the wave's "
                        f"{cell.job_bytes} B")
    want = "cpu" if cell.rehearsal else "tpu"
    if cell.device.get("platform") != want:
        problems.append(f"the daemon is on {cell.device.get('platform')!r},"
                        f" want {want!r}")
    return problems


def _spans(d: dict) -> list:
    """The daemon's own spans (``--trace-dir``, written at its shutdown),
    each with its start on the epoch clock."""
    path = os.path.join(d["home"], "spans", "trace.jsonl")
    try:
        with open(path) as f:
            head = json.loads(f.readline())
            wall0 = head["wall0_ns"] / 1e9
            return [dict(ev, wall=wall0 + ev["ts"])
                    for ev in map(json.loads, f) if ev.get("ph") == "X"]
    except (OSError, ValueError, KeyError):
        return []


def finish(cell, jobs: list) -> None:
    """Stop the daemon; then what it left: the device as the hook reports
    it, its compile-cache misses, its spans dealt to the waves they fell
    in, and the traced run's profile reduced (in a child: this process
    stays off JAX)."""
    d = cell.obs.pop("daemon")
    rc = _stop(d)
    reports = [h for h in _hook_reports(d) if "platform" in h]
    if reports:
        last = reports[-1]
        cell.device = {"platform": last["platform"], "kind": last["kind"],
                       "count": last["count"],
                       "memory_peak_bytes": last.get("memory_peak_bytes", 0)}
        # The hook counts from the daemon's start to its exit: the boot
        # warm and the warm-up wave are in it, so a first run in a checkout
        # reads what it compiled, and every later run has to read 0.
        jobs[0]["compiles"] = last.get("jax", {}).get("cache_misses", 0)
    cell.obs["hook_reports"] = reports
    spans = _spans(d)
    for job in jobs:
        lo, hi = job["serve"]["wall_start"], job["serve"]["wall_end"]
        mine = [s for s in spans if lo <= s["wall"] <= hi]
        job["serve"]["spans"] = {
            name + "_ms": [1e3 * s["dur"] for s in mine
                           if s["name"] == name]
            for name in ("submit", "finish")}
    print(json.dumps({"daemon": {
        "rc": rc, "spans": len(spans),
        "jax": (reports[-1].get("jax") if reports else None),
        "programs": (reports[-1].get("programs") if reports else None)}}),
        flush=True)
    if not d["traced"]:
        return
    cell.obs["traced_job"] = jobs[0]
    pbs = glob.glob(os.path.join(d["hook"], "profile", "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        print(json.dumps({"trace": "the daemon wrote no profile"}),
              flush=True)
        return
    out = os.path.join(d["home"], "reduced.json")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "tracereduce.py"), pbs[0], out],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    if res.returncode != 0:
        print(json.dumps({"trace": "reduction failed",
                          "stderr": res.stderr[-2000:]}), flush=True)
        return
    with open(out) as f:
        reduced = json.load(f)
    adopt_trace(cell, pbs[0], reduced)
