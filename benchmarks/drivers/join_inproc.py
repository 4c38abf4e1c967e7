"""Driver: a join job is one call of ``dsi_tpu.cli.planrun.main`` here,
over a ``Rankings`` and a ``UserVisits`` table.

As ``plan_inproc`` (the configuration gives ``entry``, ``stats_tag`` and
``argv``, the traffic mix ``extra_args``: the window; the harness process
holds the chip; the traced job gets ``--trace-dir`` and its trace is
anchored to the job), with four things of its own:

* **The tables.**  The corpus's generated text files carry the seed and
  the size: ``rankvisits.py`` writes, once a seed beside the corpus, the
  rankings' files and one file of visits a text file, seeded by the CRC-32
  of the first text file (the reference gets the files and no seed).  This
  driver hands the visits' files to the entry point in place of the text
  files and the rankings' as ``--join-build`` flags, which it adds to the
  traffic mix's ``extra_args`` in memory; a job's bytes are both tables'.
* **The warm-up** is one whole job: the build's steps, its ordering at the
  table's capacity, the probe's steps and the pull's pack program compile
  (first run in a checkout) or load from the compile cache (every later
  run) there, and not in the window's first job.
* **The top row, rendered.**  The chain commits the table as ``mr-out-<r>``
  and writes its second statement's row as ``plan-top.json``.  After a job
  (outside its measured span) the driver renders that file into the
  reference's ``#top`` line as ``mr-out-top`` in the job's directory, so
  that the harness's ``read_output`` compares table and top row in one.
* **The conditions** (``job_problems``): no stage on the host path, every
  row of both tables read exactly once, as many rows inside the window and
  matched as the reference counts, as many groups as it has lines, steps
  that can hold the probe side, the table on the device and whole, the
  commit rendered from the merged table's arrays, every partition and the
  top row committed.

A program whose registry does not know ``join_probe_rows`` has no join
chain and cannot run such a cell: the run ends at once, before any input
is made, with no result and a non-zero exit.

Importing this file registers the plain reference of kind ``join``
(``reference_join.py`` over the two tables), by the one route a new kind
has (``stream_inproc``'s module text).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import rankvisits
import reference
import reference_join
from drivers import plan_inproc, stream_inproc
from drivers.agg_inproc import count_rows
from drivers.plan_inproc import finish  # noqa: F401
from drivers.stream_inproc import _call_main

STAGE = "join"


def _counts_path(corpus_files: list, dates: str) -> str:
    return os.path.join(os.path.dirname(corpus_files[0]), "visits",
                        f"counts-{dates.replace(':', '_')}.json")


def _reference(corpus_files: list, dates: str) -> tuple:
    """The reference's sums and counts over the two tables; the counts
    are kept beside the visits, for the runs that load the lines."""
    build, probe = rankvisits.job_files(corpus_files)
    total, counts = reference_join.sums(build, probe, dates)
    with open(_counts_path(corpus_files, dates), "w") as f:
        json.dump(counts, f)
    return total, counts


def _reference_lines(corpus_files: list, params: dict) -> list:
    return reference_join.lines_of(_reference(
        corpus_files, str(params.get("dates", reference_join.DATES)))[0])


reference.KINDS.setdefault("join", _reference_lines)


def claim_device(cell) -> None:
    stream_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "join_probe_rows" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no "
                 "join_probe_rows, so it has no join chain")


def warm_up(cell) -> None:
    """One whole job over the two tables (module docstring).  From here on
    the job's input files are the visits' files, its bytes both tables',
    and the rankings' files ride in the traffic mix's ``extra_args``."""
    corpus_files = cell.files
    dates = str(cell.traffic.get("reference_params", {}).get(
        "dates", reference_join.DATES))
    build, probe = rankvisits.job_files(corpus_files)
    try:
        with open(_counts_path(corpus_files, dates)) as f:
            counts = json.load(f)
    except FileNotFoundError:
        counts = _reference(corpus_files, dates)[1]
    cell.files = probe
    cell.job_bytes = sum(os.path.getsize(path) for path in build + probe)
    cell.obs.update(join_counts=counts, join_build_files=build,
                    join_file_rows={"build": count_rows(build),
                                    "probe": count_rows(probe)},
                    join_probe_bytes=sum(map(os.path.getsize, probe)))
    cell.traffic = dict(cell.traffic, extra_args=[
        *cell.traffic.get("extra_args", []),
        *(arg for path in build for arg in ("--join-build", path))])
    job = _call_main(cell, cell.files, os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    join = (ps.get("stages") or {}).get(STAGE, {})
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "build_files": len(build), "probe_files": len(probe),
        "job_bytes": cell.job_bytes, "file_rows": cell.obs["join_file_rows"],
        "reference_counts": counts, "build_steps":
        join.get("join_build_steps"), "steps": join.get("steps"),
        "groups": join.get("join_groups"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))


def _render_top(workdir: str) -> None:
    """``plan-top.json`` as the reference's ``#top`` line, in
    ``mr-out-top`` (empty where no row joined)."""
    with open(os.path.join(workdir, "plan-top.json")) as f:
        top = json.load(f)["top"]
    with open(os.path.join(workdir, "mr-out-top"), "w") as f:
        if top is not None:
            f.write(f"#top {top['sourceIP']} {top['totalRevenue']} "
                    f"{top['avgPageRank']}\n")


def run_job(cell, i: int) -> dict:
    job = plan_inproc.run_job(cell, i)
    if job["rc"] == 0:
        _render_top(job["workdir"])
    return job


def job_problems(cell, job: dict) -> list:
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("a stage took the host path")
    ps = job["pipeline_stats"]
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    join = ps.get("stages", {}).get(STAGE, {})
    counts, rows = cell.obs["join_counts"], cell.obs["join_file_rows"]
    want = {"join_build_rows": rows["build"], "join_probe_rows":
            rows["probe"], "join_window_rows": counts["window_rows"],
            "join_matched_rows": counts["matched_rows"],
            "join_groups": sum(1 for line in cell.reference_lines
                               if not line.startswith("#top "))}
    for key, value in want.items():
        if join.get(key) != value:
            problems.append(f"stage join: {key} {join.get(key)}, the files "
                            f"and the reference give {value}")
    chunk = int(cell.config["chunk_bytes"])
    if join.get("steps", 0) * chunk < cell.obs["join_probe_bytes"]:
        problems.append(f"stage join: steps {join.get('steps')} of {chunk} "
                        f"B cannot hold the probe side's "
                        f"{cell.obs['join_probe_bytes']} B")
    if join.get("join_table_bytes", 0) < 100 * rows["build"] // 2:
        problems.append(f"stage join: join_table_bytes "
                        f"{join.get('join_table_bytes')} cannot be "
                        f"{rows['build']} rows' table on the device")
    if ps.get("write_rows_dict", 0) > 0:
        problems.append(f"write_rows_dict {ps['write_rows_dict']}: the "
                        "commit went through Python objects")
    if job["rc"] == 0:
        missing = [name for name in [
            f"mr-out-{r}" for r in range(int(cell.config["partitions"]))]
            + ["plan-top.json"]
            if not os.path.exists(os.path.join(job["workdir"], name))]
        if missing:
            problems.append(f"{missing} were not committed")
    return problems
