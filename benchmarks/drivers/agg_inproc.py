"""Driver: an aggregation job is one call of ``dsi_tpu.cli.planrun.main``
here, over files of ``UserVisits`` rows.

As ``plan_inproc`` (the configuration gives ``entry``, ``stats_tag`` and
``argv``; the harness process holds the chip; the traced job gets
``--trace-dir`` and its trace is anchored to the job), with three things
of its own:

* **The rows.**  The corpus's generated text files carry the seed and the
  size: ``uservisits.py`` writes, once a seed beside the corpus, one file
  of rows a text file, of as many whole rows as fit the text file's bytes,
  seeded by the CRC-32 of the first text file (the reference gets the
  files and no seed).  This driver hands the row files to the entry point
  in place of the text files.
* **The warm-up** is one whole job: its first step overflows the table the
  job starts with and runs again at the rung it settles on, and its last
  step, a short chunk, pulls a shorter prefix than the others; a whole job
  reaches all of these programs, a file's worth of steps not the last.
* **The conditions** (``job_problems``): no stage on the host path, steps
  that can hold the job, every row read (``agg_rows`` is the job's
  newlines), as many groups as the reference has lines, every partition
  committed, and the commit rendered from the merged table's arrays
  (``write_rows_dict`` 0).

A program whose registry does not know ``agg_rows`` has no aggregation
chain and cannot run such a cell: the run ends at once, before any input
is made, with no result and a non-zero exit.

Importing this file registers the plain reference of kind ``agg``
(``reference_agg.py`` over the row files), by the one route a new kind has
(``stream_inproc``'s module text).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import reference
import reference_agg
import uservisits
from drivers import stream_inproc
from drivers.plan_inproc import finish, run_job  # noqa: F401
from drivers.stream_inproc import _call_main

STAGE = "agg"


def _reference_lines(corpus_files: list, params: dict) -> list:
    return reference_agg.lines(uservisits.job_files(corpus_files), params)


reference.KINDS.setdefault("agg", _reference_lines)


def claim_device(cell) -> None:
    stream_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "agg_rows" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no agg_rows, so "
                 "it has no aggregation chain")


def count_rows(paths: list) -> int:
    """The newlines of the files (a row file ends in one)."""
    total = 0
    for path in paths:
        with open(path, "rb") as f:
            while block := f.read(1 << 24):
                total += block.count(b"\n")
    return total


def warm_up(cell) -> None:
    """One whole job over the row files: the step program at the table it
    starts with and at the rung it widens to, and the pull's pack program
    at a full step's prefix and at the last step's, compile (first run in
    a checkout) or load from the compile cache (every later run) here, and
    not in the window's first job.  From here on the job's input files are
    the row files."""
    cell.files = uservisits.job_files(cell.files)
    cell.job_bytes = sum(os.path.getsize(path) for path in cell.files)
    cell.obs["job_rows"] = count_rows(cell.files)
    job = _call_main(cell, cell.files, os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    agg = (ps.get("stages") or {}).get(STAGE, {})
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "row_files": len(cell.files), "job_bytes": cell.job_bytes,
        "job_rows": cell.obs["job_rows"], "steps": agg.get("steps"),
        "replays": agg.get("replays"), "groups": agg.get("agg_groups"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))


def job_problems(cell, job: dict) -> list:
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("a stage took the host path")
    ps = job["pipeline_stats"]
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    agg = ps.get("stages", {}).get(STAGE, {})
    chunk = int(cell.config["chunk_bytes"])
    if agg.get("steps", 0) * chunk < cell.job_bytes:
        problems.append(f"stage agg: steps {agg.get('steps')} of {chunk} B "
                        f"cannot hold the job's {cell.job_bytes} B")
    if agg.get("agg_rows") != cell.obs.get("job_rows"):
        problems.append(f"stage agg: agg_rows {agg.get('agg_rows')}, the "
                        f"job holds {cell.obs.get('job_rows')} rows")
    if agg.get("agg_groups") != len(cell.reference_lines):
        problems.append(f"stage agg: agg_groups {agg.get('agg_groups')}, "
                        f"the reference has {len(cell.reference_lines)} "
                        "lines")
    if ps.get("write_rows_dict", 0) > 0:
        problems.append(f"write_rows_dict {ps['write_rows_dict']}: the "
                        "commit went through Python objects")
    if job["rc"] == 0:
        missing = [r for r in range(int(cell.config["partitions"]))
                   if not os.path.exists(
                       os.path.join(job["workdir"], f"mr-out-{r}"))]
        if missing:
            problems.append(f"partitions {missing} were not committed")
    return problems
