"""Driver: a stream job is one call of the entry point the configuration
names, in the harness process.

The harness process holds the chip(s) for the whole run, as a user's stream
process does for its job.  For any stream command of the program that
commits ``mr-out-*`` under ``--workdir`` and prints ``<stats_tag>:
pipeline_stats={...}`` on stderr with ``--stats`` (``wcstream``,
``grepstream``).  The configuration gives the entry point as data
(``"entry": "<module>"``, loaded here by name, so this file holds no import
statement of the program), the tag, the flags (``"argv"``, in which
``{workdir}`` stands for the job's work directory) and the layout the
checks hold a job to (``devices``, ``chunk_bytes``); the traffic mix may
add ``extra_args``; the corpus files come last.

Importing this file also registers the plain reference of kind
``grepstats`` (``reference_grepstats.py``).  ``run.py`` imports a
configuration's driver before it looks the reference up in
``reference.KINDS``, and that table cannot name a reference that lives in
another file; registering from here is the one route a new kind has until a
configuration can name its reference (PERF.md, Open questions).

The trace of a traced run is anchored to the job, not to the wall clock:
the profiler starts immediately before the traced job's call of ``main``
and stops after ``trace_seconds`` or when the call returns, whichever is
first.  So the trace holds the job's first steps however fast they get, and
a job that returns in a tenth of a second is traced whole.
"""

from __future__ import annotations

import ast
import contextlib
import importlib
import io
import json
import os
import re
import sys
import threading
import time

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import reference
import reference_grepstats
from drivers._common import claim_device, finish  # noqa: F401

reference.KINDS.setdefault("grepstats", reference_grepstats.lines)


def _call_main(cell, files: list, workdir: str) -> dict:
    """One call of the entry point's ``main``; stderr captured and parsed."""
    entry = importlib.import_module(cell.config["entry"])
    argv = [str(a).replace("{workdir}", workdir)
            for a in cell.config["argv"]]
    argv += [str(a) for a in cell.traffic.get("extra_args", [])] + files
    err = io.StringIO()
    before = jaxwatch.snapshot()
    t_start = time.monotonic()
    # The entry point also prints its result on stdout: kept off the
    # harness's own lines, the committed mr-out-* is what is compared.
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        try:
            rc = entry.main(argv)
        except SystemExit as e:   # argparse, or the program's device gate
            rc = e.code if isinstance(e.code, int) else 1
            err.write(f"\nSystemExit: {e.code}\n")
    t_end = time.monotonic()
    text = err.getvalue()
    jax_delta = jaxwatch.delta(before, jaxwatch.snapshot())
    m = re.search(rf"^{re.escape(cell.config['stats_tag'])}: "
                  r"pipeline_stats=(\{.*\})$", text, re.M)
    return {"rc": rc, "t_start": t_start, "t_end": t_end,
            "wall_s": round(t_end - t_start, 4), "workdir": workdir,
            "log_text": text, "jax": jax_delta,
            "compiles": jax_delta["cache_misses"],
            "pipeline_stats": ast.literal_eval(m.group(1)) if m else None}


def warm_up(cell) -> None:
    """The entry point over the corpus's first file: the step program of
    the rung this corpus stays on compiles (first run in a checkout) or
    loads from the compile cache (every later run).  A program that cannot
    run the configuration at all ends the run here, with no result."""
    job = _call_main(cell, cell.files[:1],
                     os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "steps": ps.get("steps"), "replays": ps.get("replays"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}")


class _JobTrace:
    """One profiler trace from just before a job's start, for at most
    ``for_s`` seconds of it."""

    def __init__(self, out_dir: str, for_s: float) -> None:
        self.out_dir = out_dir
        self.timer = threading.Timer(for_s, self._stop)
        self.lock = threading.Lock()
        self.stopped = False

    def _stop(self) -> None:
        import jax

        with self.lock:
            if not self.stopped:
                self.stopped = True
                jax.profiler.stop_trace()

    def __enter__(self):
        import jax

        jax.profiler.start_trace(self.out_dir)
        self.timer.start()
        return self

    def __exit__(self, *exc) -> None:
        self.timer.cancel()
        self._stop()


def run_job(cell, i: int) -> dict:
    workdir = os.path.join(cell.workroot, f"job-{i}")
    traced = cell.trace and i == 0 and not cell.rehearsal
    if traced:
        with _JobTrace(os.path.join(cell.workroot, "profile"),
                       cell.config.get("trace_seconds", 4)):
            job = _call_main(cell, cell.files, workdir)
    else:
        job = _call_main(cell, cell.files, workdir)
    job.update({"i": i, "bytes": cell.job_bytes, "traced": traced})
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])
    return job


def job_problems(cell, job: dict) -> list:
    """Every byte through a device step, on every device of the layout."""
    problems = []
    if re.search(r"need(s|ed) the host path", job["log_text"]):
        problems.append("the stream took the host path")
    ps = job["pipeline_stats"]
    want, chunk = int(cell.config["devices"]), int(cell.config["chunk_bytes"])
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    if ps.get("steps", 0) * want * chunk < cell.job_bytes:
        problems.append(f"steps {ps.get('steps')} of {want} x {chunk} B "
                        f"cannot hold the job's {cell.job_bytes} B")
    rows = ps.get("device_rows", [])
    if len(rows) != want or min(rows) <= 0:
        problems.append(f"device_rows {rows}: not every one of {want} "
                        "devices took lines")
    return problems
