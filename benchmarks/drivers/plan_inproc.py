"""Driver: a plan job is one call of ``dsi_tpu.cli.planrun.main`` here.

As ``stream_inproc`` (the configuration gives ``entry``, ``stats_tag`` and
``argv``, the traffic mix ``extra_args``; the harness process holds the
chip; the trace of a traced run is anchored to the job), for an entry
point that runs several engines in a row and reports per stage:

    planrun: pipeline_stats={'stages': {<stage>: {<the engine's own
        pipeline_stats>}, ...}, 'plan': {<the plan_* and relay_* keys>},
        'write_s': <s>}

A program that does not declare per-stage stats in its registry schema
cannot run such a cell: the run ends at once, with no result and a
non-zero exit.

In the traced run the job also gets ``--trace-dir``: the program's tracer
is then on, and its spans are ``dsi:<name>`` annotations in the profiler's
trace, so that an idle gap can be named by the program's own span.

Importing this file registers the plain reference of kind ``grepwc``
(``reference_grepwc.py``), by the one route a new kind has
(``stream_inproc``'s module text).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import reference
import reference_grepwc
from drivers import stream_inproc
from drivers.stream_inproc import _JobTrace, _call_main, finish  # noqa: F401

reference.KINDS.setdefault("grepwc", reference_grepwc.lines)


def claim_device(cell) -> None:
    stream_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "stage_stats" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no stage_stats, "
                 "so it reports nothing per stage")


def _stage_counts(ps, key: str) -> dict:
    return {name: s.get(key) for name, s in ((ps or {}).get("stages")
                                             or {}).items()}


def warm_up(cell) -> None:
    """The entry point over the corpus's first ``warm_files`` files: as
    many as it takes for what passes to fill a relay buffer, so that stage
    2 widens to the table rung a whole job settles on.  The ``emit`` step,
    the relay's pack program and that rung compile (first run in a
    checkout) or load from the compile cache (every later run) here, and
    not in the window's first job."""
    job = _call_main(cell, cell.files[:int(cell.config["warm_files"])],
                     os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"]
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "steps": _stage_counts(ps, "steps"),
        "replays": _stage_counts(ps, "replays"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))


def run_job(cell, i: int) -> dict:
    workdir = os.path.join(cell.workroot, f"job-{i}")
    traced = cell.trace and i == 0 and not cell.rehearsal
    if traced:
        argv = cell.config["argv"]
        cell.config["argv"] = argv + [
            "--trace-dir", os.path.join(cell.workroot, "spans")]
        try:
            with _JobTrace(os.path.join(cell.workroot, "profile"),
                           cell.config.get("trace_seconds", 10)):
                job = _call_main(cell, cell.files, workdir)
        finally:
            cell.config["argv"] = argv
            # the tracer is the process's: off again for the other jobs
            importlib.import_module("dsi_tpu.obs").configure_tracing(
                enabled=False)
    else:
        job = _call_main(cell, cell.files, workdir)
    job.update({"i": i, "bytes": cell.job_bytes, "traced": traced})
    if job["rc"] != 0:
        sys.stderr.write(job["log_text"][-3000:])
    return job


def job_problems(cell, job: dict) -> list:
    """Every byte through a device step of stage 1, every relay buffer
    through one of stage 2, on every device of the layout, and the
    intermediate never off the device."""
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("a stage took the host path")
    ps = job["pipeline_stats"]
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    want, chunk = int(cell.config["devices"]), int(cell.config["chunk_bytes"])
    stages, plan = ps.get("stages", {}), ps.get("plan", {})
    for name in ("grep", "wc"):
        rows = stages.get(name, {}).get("device_rows", [])
        if len(rows) != want or min(rows) <= 0:
            problems.append(f"stage {name}: device_rows {rows}: not every "
                            f"one of {want} devices took part")
    steps = stages.get("grep", {}).get("steps", 0)
    if steps * want * chunk < cell.job_bytes:
        problems.append(f"stage grep: steps {steps} of {want} x {chunk} B "
                        f"cannot hold the job's {cell.job_bytes} B")
    if plan.get("plan_handoff") != "device" \
            or plan.get("plan_intermediate_bytes") != 0 \
            or plan.get("plan_spilled_bytes") != 0:
        problems.append(
            "the handoff left the device: plan_handoff "
            f"{plan.get('plan_handoff')!r}, plan_intermediate_bytes "
            f"{plan.get('plan_intermediate_bytes')}, plan_spilled_bytes "
            f"{plan.get('plan_spilled_bytes')}")
    buffers = plan.get("plan_relay_buffers", 0)
    if stages.get("wc", {}).get("steps", 0) < max(1, buffers):
        problems.append(f"stage wc: steps {stages.get('wc', {}).get('steps')}"
                        f" for {buffers} relay buffers")
    return problems
