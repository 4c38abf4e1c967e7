"""Driver: a batch job is ``python -m dsi_tpu.cli.mrrun ...`` as a child.

The harness is the submitter: it starts ``mrrun`` (which starts the
coordinator, the one device worker per chip and the reduce-only host
helpers) and waits for it to exit with the job's ``mr-out-*`` committed.
This process never imports JAX: a parent that holds the chip starves the
worker.  What it knows about the device comes from the worker itself, through
``hooks/sitecustomize.py``.

What throughput counts of a job is the time the deployment is in service:
from the first device worker's backend being up (the hook's report, a moment
before it asks for its first task) to the last ``mr-out-*`` committed (its
modification time).  The device worker's start before that moment (12-16 s;
a probe child came before it until PR 25) and the TPU runtime's teardown
after it (3-9 s) differ by seconds from one job to the next on one machine:
no window the contract allows averages that out, so they are reported per
layer (``launch_s``) and, for a run's first job, inside ``setup_s``.

The configuration's ``mrrun`` block gives the deployment's flags, the
traffic mix gives ``app`` and ``env``.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

from drivers._common import adopt_trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOOKS = os.path.join(HERE, "hooks")


def claim_device(cell) -> None:
    """Nothing: the chip is the worker's."""


def _child_env(cell, hook_out: str, traced: bool) -> dict:
    env = dict(os.environ)
    path = [HOOKS, cell.root]
    if env.get("PYTHONPATH"):
        path.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(path)
    env["JAX_COMPILATION_CACHE_DIR"] = cell.jax_cache
    env["BENCH_HOOK_OUT"] = hook_out
    env["BENCH_HOOK_MATCH"] = cell.config["device_process_match"]
    env["BENCH_HOOK_TRACE_S"] = (
        str(cell.config.get("trace_seconds", 15)) if traced else "0")
    if cell.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(cell.traffic.get("env", {}))
    return env


def _socket_path(workdir: str) -> str:
    """A short address for the job's RPC plane: a Unix socket path may not
    exceed ~100 bytes, and a checkout can sit anywhere."""
    short = os.path.join(tempfile.gettempdir(),
                         f"bm-{os.getpid()}-{os.path.basename(workdir)}.sock")
    return short if len(short) < 96 else os.path.join(workdir, "mr.sock")


def _run_mrrun(cell, workdir: str, files: list, traced: bool) -> dict:
    """One ``mrrun`` child over ``files``; returns the raw job record."""
    os.makedirs(workdir)
    hook_out = os.path.join(workdir, "hook")
    flags = cell.config["mrrun"]
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", str(flags["workers"]),
           "--nreduce", str(flags["nreduce"]),
           "--backend", flags["backend"],
           "--task-timeout", str(flags["task_timeout_s"]),
           "--timeout", str(flags["job_timeout_s"]),
           "--workdir", workdir]
    if traced:
        cmd += ["--trace-dir", os.path.join(workdir, "trace")]
    cmd += [cell.traffic["app"]] + files
    env = _child_env(cell, hook_out, traced)
    env["DSI_MR_SOCKET"] = _socket_path(workdir)
    log_path = os.path.join(workdir, "job.log")
    with open(log_path, "w") as out:
        t_start, spawn_wall = time.monotonic(), time.time()
        proc = subprocess.Popen(cmd, env=env, cwd=cell.root, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=flags["job_timeout_s"] + 60)
        except subprocess.TimeoutExpired:
            rc = -9
        finally:
            # mrrun stops its own children; make sure of the whole group
            # whatever happened, and wait until it is gone.
            try:
                os.killpg(proc.pid, signal.SIGKILL if rc == -9
                          else signal.SIGTERM)
            except ProcessLookupError:
                pass
            proc.wait()
        t_end = time.monotonic()
    try:
        os.remove(env["DSI_MR_SOCKET"])
    except OSError:
        pass
    with open(log_path, errors="replace") as f:
        text = f.read()
    workers = [dict(kv.split("=") for kv in m.split())
               for m in re.findall(r"^mrworker: pid=\d+ (backend=tpu .*)$",
                                   text, re.M)]
    hook = []
    for path in sorted(glob.glob(os.path.join(hook_out, "device-*.json"))):
        with open(path) as f:
            hook.append(json.load(f))
    return {**_in_service(rc, t_start, spawn_wall, hook, workdir),
            "rc": rc, "t_start": t_start, "t_end": t_end,
            "wall_s": round(t_end - t_start, 4), "spawn_wall": spawn_wall,
            "workdir": workdir, "log_text": text, "traced": traced,
            "device_workers": workers, "hook": hook,
            "device_maps": sum(int(w["device_maps"]) for w in workers),
            "host_maps": sum(int(w["host_maps"]) for w in workers),
            "compiles": sum(h.get("jax", {}).get("cache_misses", 0)
                            for h in hook),
            "cache_load_s": sum(h.get("jax", {}).get("cache_load_s", 0.0)
                                for h in hook),
            "compile_lines": re.findall(
                r"^\[compile\] (\S+): compiled in ([0-9.]+)s", text, re.M)}


def _in_service(rc: int, t_start: float, spawn_wall: float, hook: list,
                workdir: str) -> dict:
    """The job's measured span on the harness's monotonic clock, from two
    readings of the machine's wall clock: the first device worker's backend
    up, and the newest ``mr-out-*`` (written to a temporary name and renamed,
    so its modification time is its commit).  Nothing when either is
    missing: the job then counts whole, and as failed."""
    ups = [h["backend_up_wall"] for h in hook if "backend_up_wall" in h]
    outs = [os.stat(p).st_mtime_ns / 1e9
            for p in glob.glob(os.path.join(workdir, "mr-out-*"))]
    if rc != 0 or not ups or not outs:
        return {}
    return {"in_service_wall": min(ups), "committed_wall": max(outs),
            "t_measured_start": t_start + (min(ups) - spawn_wall),
            "t_measured_end": t_start + (max(outs) - spawn_wall)}


def warm_up(cell) -> None:
    """Compile the cell's programs into this checkout's compile cache: one
    whole job (some program shapes follow the data, so one split is not
    enough), once per checkout and cache (a marker says it has been done).
    Every later job, in this run and in later ones, loads them."""
    key = hashlib.sha1(json.dumps(
        [cell.jax_cache, cell.traffic["app"], cell.traffic.get("env", {}),
         cell.corpus_params["file_bytes"], cell.rehearsal],
        sort_keys=True).encode()).hexdigest()[:12]
    marker = os.path.join(cell.cache_root, "warm", f"{cell.config['name']}-"
                          f"{key}")
    cache_live = os.path.isdir(cell.jax_cache) and os.listdir(cell.jax_cache)
    if os.path.exists(marker) and cache_live:
        print(json.dumps({"warm_up": "skipped: this checkout's cache was "
                          "warmed by an earlier run"}), flush=True)
        return
    job = _run_mrrun(cell, os.path.join(cell.workroot, "warm"),
                     cell.files, traced=False)
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"],
        "compiles": job["compiles"],
        "compile_lines": job["compile_lines"]}}), flush=True)
    if job["rc"] == 0:
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, "w") as f:
            f.write(cell.jax_cache + "\n")
    else:
        sys.stderr.write(job["log_text"][-3000:])


def run_job(cell, i: int) -> dict:
    traced = cell.trace and i == 0
    job = _run_mrrun(cell, os.path.join(cell.workroot, f"job-{i}"),
                     cell.files, traced)
    job.update({"i": i, "bytes": cell.job_bytes})
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])
    if traced:
        job["spans"] = _read_spans(os.path.join(job["workdir"], "trace"))
    return job


def job_problems(cell, job: dict) -> list:
    """The deployment's own conditions: every map on the device, the
    device worker on the right platform."""
    want = "cpu" if cell.rehearsal else "tpu"
    problems = []
    n_files = len(cell.files)
    if job["device_maps"] < n_files or job["host_maps"]:
        problems.append(f"{job['device_maps']} device maps and "
                        f"{job['host_maps']} host maps of {n_files}")
    platforms = sorted({w["platform"] for w in job["device_workers"]})
    if platforms != [want]:
        problems.append(f"device workers on {platforms}, want [{want!r}]")
    if "needs the host path" in job["log_text"]:
        problems.append("a task took the host path")
    if job["rc"] == 0 and not (
            job["t_start"] < job.get("t_measured_start", 0.0)
            < job.get("t_measured_end", 0.0) <= job["t_end"]):
        problems.append("no in-service span: the device worker's report or "
                        "the outputs' commit times are missing or out of "
                        "order")
    return problems


def _read_spans(trace_dir: str) -> dict:
    """The program's own task trace (``mrrun --trace-dir``), every event on
    the epoch clock: ``wall0`` of its file plus its ``ts``."""
    events, counters = [], {}
    for path in glob.glob(os.path.join(trace_dir, "trace-*.jsonl")):
        with open(path) as f:
            head = json.loads(f.readline())
            for k, v in head.get("counters", {}).items():
                counters[k] = counters.get(k, 0) + v
            for line in f:
                ev = json.loads(line)
                ev["wall"] = head["wall0"] + ev["ts"]
                ev["pid"] = head["pid"]
                events.append(ev)
    return {"events": events, "counters": counters}


def finish(cell, jobs: list) -> None:
    """The device as the workers reported it, and the traced job's
    profile reduced (in a child: this process stays off JAX)."""
    reports = [h for j in jobs for h in j["hook"] if "platform" in h]
    if reports:
        first = reports[0]
        cell.device = {
            "platform": first["platform"], "kind": first["kind"],
            "count": first["count"],
            "memory_peak_bytes": max(h.get("memory_peak_bytes", 0)
                                     for h in reports)}
    cell.obs["hook_reports"] = reports
    traced = [j for j in jobs if j["traced"]]
    if not traced or cell.rehearsal:
        return
    job = traced[0]
    cell.obs["traced_job"] = job
    pbs = glob.glob(os.path.join(job["workdir"], "hook", "profile", "**",
                                 "*.xplane.pb"), recursive=True)
    if not pbs:
        print(json.dumps({"trace": "the device worker wrote no profile"}),
              flush=True)
        return
    out = os.path.join(job["workdir"], "reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable,
                          os.path.join(HERE, "tracereduce.py"), pbs[0], out],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    if res.returncode != 0:
        print(json.dumps({"trace": "reduction failed",
                          "stderr": res.stderr[-2000:]}), flush=True)
        return
    with open(out) as f:
        reduced = json.load(f)
    adopt_trace(cell, pbs[0], reduced)
