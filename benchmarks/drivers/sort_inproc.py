"""Driver: a sort job is one call of ``dsi_tpu.cli.planrun.main`` here,
over files of ``gensort`` records.

As ``plan_inproc`` (the configuration gives ``entry``, ``stats_tag`` and
``argv``; the harness process holds the chip; the traced job gets
``--trace-dir`` and its trace is anchored to the job), with three things
of its own:

* **The records.**  The corpus's generated text files carry the seed and
  the size: ``gensort.py`` writes, once a seed beside the corpus, one file
  of ``gensort -a`` records a text file, of as many whole records as the
  text file has hundreds of bytes, seeded by the CRC-32 of the first text
  file (the reference gets the files and no seed).  This driver hands the
  record files to the entry point in place of the text files.
* **The warm-up** is one whole job: the resident store's shape follows the
  job's record count, so anything shorter would leave the ordering
  program to compile in the window.  It ends by writing and deleting
  the bytes the window's jobs will commit and two jobs' more
  (``_touch_disk``), so that a machine's first run, and a run's last
  job, commit at the price of every other.
* **The conditions** (``job_problems``): no stage on the host path, the
  handoff on the device with no intermediate or spilled bytes, steps that
  can hold the job, every record counted and resident, every partition
  committed, and the order itself: ``mr-out-0..`` read in partition order
  as raw bytes, every key greater than or equal to the one before, which
  the harness's comparison of sorted lines cannot see.

A program whose registry does not know ``sort_records`` has no sort chain
and cannot run such a cell: the run ends at once, before any input is
made, with no result and a non-zero exit.

Importing this file registers the plain reference of kind ``sort``
(``reference_sort.py`` over the record files), by the one route a new kind
has (``stream_inproc``'s module text).
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys

import numpy as np

import gensort
import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import reference
import reference_sort
from drivers import plan_inproc, stream_inproc
from drivers.plan_inproc import finish, run_job  # noqa: F401
from drivers.stream_inproc import _call_main


def _reference_lines(corpus_files: list, params: dict) -> list:
    """The plain reference over the job's record files, as the harness's
    ``read_output`` orders a job's lines: sorted as strings (for
    ``gensort`` records that is the reference's own order: a record's
    number follows its key and is its input ordinal)."""
    return sorted(reference_sort.lines(gensort.job_files(corpus_files),
                                       params))


reference.KINDS.setdefault("sort", _reference_lines)


def claim_device(cell) -> None:
    stream_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "sort_records" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no sort_records, "
                 "so it has no sort chain")


def warm_up(cell) -> None:
    """One whole job over the record files: the ingest step, the ordering
    program of the job's own capacity and the pull's block program compile
    (first run in a checkout) or load from the compile cache (every later
    run) here, and not in the window's first job.  From here on the job's
    input files are the record files."""
    cell.files = gensort.job_files(cell.files)
    cell.job_bytes = sum(os.path.getsize(path) for path in cell.files)
    job = _call_main(cell, cell.files, os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    sort = (ps.get("stages") or {}).get("sort", {})
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "record_files": len(cell.files), "job_bytes": cell.job_bytes,
        "steps": sort.get("steps"), "records": sort.get("sort_records"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))
    _touch_disk(cell)


def _touch_disk(cell) -> None:
    """Write, fsync and delete what the window's jobs will commit and two
    jobs' bytes more, so that they land on blocks the file system has
    written before.  On a machine's fresh disk the first write of a block
    costs more than a rewrite (a job's ten commits 0.26 s in the first
    run on a machine and 0.13-0.18 s in every later one, the rest of the
    job the same; with exactly the window's bytes touched the tenth job
    of a run still found fresh blocks, 0.43-0.51 s: my chip runs, PR 45),
    and a worker's disk in service is not fresh: the first touch is
    set-up's, as a program's first compile is."""
    scratch = os.path.join(cell.workroot, "disk-warm")
    os.makedirs(scratch)
    piece = bytes(1 << 24)
    for i in range(int(cell.traffic.get("max_jobs", 1)) + 2):
        with open(os.path.join(scratch, f"touch-{i}"), "wb") as f:
            for _ in range(-(-cell.job_bytes // len(piece))):
                f.write(piece)
            f.flush()
            os.fsync(f.fileno())
    shutil.rmtree(scratch)


def _flag(argv: list, name: str) -> str:
    return str(argv[argv.index(name) + 1])


def order_problems(workdir: str, n_reduce: int, job_bytes: int) -> list:
    """What is wrong with the committed partitions as an ordered whole:
    read in partition order as raw bytes, every key (bytes 0-9, unsigned)
    at least the one before, within a partition and from each to the
    next; whole records; the job's bytes in all."""
    problems, total = [], 0
    last = None   # the last key read: (bytes 0-7, bytes 8-9) as numbers
    for r in range(n_reduce):
        path = os.path.join(workdir, f"mr-out-{r}")
        if not os.path.exists(path):
            problems.append(f"partition {r} was not committed")
            continue
        data = np.fromfile(path, np.uint8)
        total += len(data)
        if len(data) % gensort.RECORD_BYTES:
            problems.append(f"partition {r}: {len(data)} bytes is not "
                            "whole records")
            continue
        if not len(data):
            continue
        keys = np.ascontiguousarray(data.reshape(
            -1, gensort.RECORD_BYTES)[:, :gensort.KEY_BYTES])
        hi = keys[:, :8].copy().view(">u8")[:, 0]
        lo = keys[:, 8:10].copy().view(">u2")[:, 0]
        if last is not None:
            hi = np.concatenate([[last[0]], hi])
            lo = np.concatenate([[last[1]], lo])
        falls = (hi[1:] < hi[:-1]) | ((hi[1:] == hi[:-1])
                                      & (lo[1:] < lo[:-1]))
        if falls.any():
            problems.append(f"partition {r}: {int(falls.sum())} keys are "
                            "less than the key before them")
        last = (hi[-1], lo[-1])
    if total != job_bytes:
        problems.append(f"the partitions hold {total} B, the job "
                        f"{job_bytes} B")
    return problems


def job_problems(cell, job: dict) -> list:
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("a stage took the host path")
    ps = job["pipeline_stats"]
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    plan = ps.get("plan", {})
    sort = ps.get("stages", {}).get("sort", {})
    if plan.get("plan_handoff") != "device" \
            or plan.get("plan_intermediate_bytes") != 0 \
            or plan.get("plan_spilled_bytes", 0) != 0:
        problems.append(
            "the records left the device: plan_handoff "
            f"{plan.get('plan_handoff')!r}, plan_intermediate_bytes "
            f"{plan.get('plan_intermediate_bytes')}, plan_spilled_bytes "
            f"{plan.get('plan_spilled_bytes', 0)}")
    chunk = int(cell.config["chunk_bytes"])
    if sort.get("steps", 0) * chunk < cell.job_bytes:
        problems.append(f"stage sort: steps {sort.get('steps')} of {chunk} "
                        f"B cannot hold the job's {cell.job_bytes} B")
    records = cell.job_bytes // gensort.RECORD_BYTES
    if sort.get("sort_records") != records:
        problems.append(f"stage sort: sort_records "
                        f"{sort.get('sort_records')}, the job holds "
                        f"{records}")
    if sort.get("sort_resident_bytes", 0) < cell.job_bytes:
        problems.append(f"stage sort: sort_resident_bytes "
                        f"{sort.get('sort_resident_bytes')} under the "
                        f"job's {cell.job_bytes} B: the store did not "
                        "hold the job")
    n_reduce = int(_flag(cell.config["argv"], "--nreduce"))
    if job["rc"] == 0:
        problems += order_problems(job["workdir"], n_reduce,
                                   cell.job_bytes)
    return problems
