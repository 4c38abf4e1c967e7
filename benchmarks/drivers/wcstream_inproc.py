"""Driver: a stream job is one call of ``dsi_tpu.cli.wcstream.main`` here.

The harness process holds the chip(s) for the whole run, as a user's
``wcstream`` process does for its job; a job is one call of the program's
entry point over the corpus, with ``--stats`` so that it prints its own
``pipeline_stats`` on stderr, which is captured around the call.  The
configuration's ``wcstream`` block gives the flags (devices, reduce
partitions); the traffic mix gives ``passes`` and may add ``extra_args``.

In a traced run a side thread takes one ``jax.profiler`` trace of a few
seconds inside the first job (``trace_after_s`` after it starts, for
``trace_seconds``), since the program pins no step boundary a caller
could wrap.
"""

from __future__ import annotations

import ast
import contextlib
import glob
import io
import json
import os
import re
import sys
import threading
import time

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import tracereduce
from drivers._common import adopt_trace


def claim_device(cell) -> None:
    """Initialise JAX in this process and check the chips are there."""
    if cell.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={cell.chips}"
        if cell.chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cell.jax_cache)
    if cell.root not in sys.path:
        sys.path.insert(0, cell.root)
    import jax

    jaxwatch.install()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        devices = []
        print(f"benchmarks: JAX found no backend: {e}", file=sys.stderr)
    cell.device = {"platform": devices[0].platform if devices else "none",
                   "kind": devices[0].device_kind if devices else "none",
                   "count": len(devices)}


def _call_main(cell, files: list, workdir: str) -> dict:
    """One ``wcstream.main`` call; stderr captured and parsed."""
    from dsi_tpu.cli import wcstream

    flags = cell.config["wcstream"]
    argv = ["--nreduce", str(flags["nreduce"]),
            "--devices", str(flags["devices"]), "--stats",
            "--workdir", workdir,
            *[str(a) for a in cell.traffic.get("extra_args", [])], *files]
    err = io.StringIO()
    before = jaxwatch.snapshot()
    t_start = time.monotonic()
    with contextlib.redirect_stderr(err):
        try:
            rc = wcstream.main(argv)
        except SystemExit as e:   # argparse, or the program's device gate
            rc = e.code if isinstance(e.code, int) else 1
            err.write(f"\nSystemExit: {e.code}\n")
    t_end = time.monotonic()
    text = err.getvalue()
    jax_delta = jaxwatch.delta(before, jaxwatch.snapshot())
    return {"rc": rc, "t_start": t_start, "t_end": t_end,
            "wall_s": round(t_end - t_start, 4), "workdir": workdir,
            "log_text": text, "jax": jax_delta,
            "compiles": jax_delta["cache_misses"],
            "pipeline_stats": _stats(text, "pipeline_stats"),
            "device_line": _stats(text, "device")}


def _stats(text: str, tag: str):
    m = re.search(rf"^wcstream: {tag}=(\{{.*\}})$", text, re.M)
    return ast.literal_eval(m.group(1)) if m else None


def warm_up(cell) -> None:
    """The program's entry point over the corpus's first file: the step
    rungs the corpus reaches and the pack program compile (first run in a
    checkout) or load from the compile cache (every later run)."""
    job = _call_main(cell, cell.files[:1],
                     os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "steps": ps.get("steps"), "replays": ps.get("replays")}}),
        flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])


class _SideTrace:
    """One bounded profiler trace from a side thread."""

    def __init__(self, out_dir: str, after_s: float, for_s: float) -> None:
        self.out_dir, self.after_s, self.for_s = out_dir, after_s, for_s
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._run, name="bench-trace")

    def _run(self) -> None:
        import jax

        if self.stop.wait(self.after_s):
            return  # the job ended before the trace was due
        jax.profiler.start_trace(self.out_dir)
        self.stop.wait(self.for_s)
        jax.profiler.stop_trace()

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop.set()
        self.thread.join()


def run_job(cell, i: int) -> dict:
    workdir = os.path.join(cell.workroot, f"job-{i}")
    traced = cell.trace and i == 0 and not cell.rehearsal
    if traced:
        prof = os.path.join(cell.workroot, "profile")
        with _SideTrace(prof, cell.config.get("trace_after_s", 3),
                        cell.config.get("trace_seconds", 4)):
            job = _call_main(cell, cell.files, workdir)
    else:
        job = _call_main(cell, cell.files, workdir)
    job.update({"i": i, "bytes": cell.job_bytes, "traced": traced})
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])
    return job


def job_problems(cell, job: dict) -> list:
    """Every step on the device, on every device the layout names."""
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("the stream took the host path")
    ps = job["pipeline_stats"]
    want = int(cell.config["wcstream"]["devices"])
    if not ps:
        problems.append("wcstream printed no pipeline_stats")
    elif len(ps.get("device_rows", [])) != want \
            or min(ps["device_rows"]) <= 0:
        problems.append(f"device_rows {ps.get('device_rows')}: not every "
                        f"one of {want} devices held a shard")
    return problems


def finish(cell, jobs: list) -> None:
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()]
    cell.device["memory_peak_bytes"] = max(peaks)
    pbs = glob.glob(os.path.join(cell.workroot, "profile", "**",
                                 "*.xplane.pb"), recursive=True)
    if not pbs:
        return
    reduced = tracereduce.reduce_file(pbs[0])
    if reduced:
        cell.obs["traced_job"] = jobs[0]
        adopt_trace(cell, pbs[0], reduced)
