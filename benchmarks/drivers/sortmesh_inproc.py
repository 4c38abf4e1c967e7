"""Driver: a sort job across a mesh is one call of
``dsi_tpu.cli.planrun.main`` here, with ``--devices`` in the
configuration's ``argv``, over files of ``gensort`` records.

As ``sort_inproc`` (the record files beside the corpus, the warm-up of one
whole job and its disk touch, the trace anchored to the job: all by
import), with one thing of its own, because that driver holds a job to
``steps x chunk_bytes >= job_bytes`` and to no device count:

* **The conditions** (``job_problems``): ``sort_inproc``'s (no stage on
  the host path, the handoff on the device with no intermediate or
  spilled bytes, every record counted, the stores together at least the
  job's bytes, every partition committed and the order itself, on the
  committed bytes), with steps of ``devices`` chunks that can hold the
  job in place of steps of one; ``device_rows`` one entry a device, each
  above 0, that sum to the job's records; more than half the records
  sent to another device than the one that read them (uniform keys:
  ``(devices - 1) / devices`` of them).

A program whose registry does not know ``sort_exchange_rows`` sorts on one
device only and cannot run such a cell: the run ends at once, before any
input is made, with no result and a non-zero exit.
"""

from __future__ import annotations

import importlib
import sys

import gensort  # benchmarks/ is on sys.path: run.py put it there
from drivers import sort_inproc
from drivers.sort_inproc import finish, run_job, warm_up  # noqa: F401


def claim_device(cell) -> None:
    sort_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "sort_exchange_rows" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no "
                 "sort_exchange_rows, so its sort chain runs on one device "
                 "and exchanges nothing")


def job_problems(cell, job: dict) -> list:
    """``sort_inproc``'s conditions (no stage on the host path, the
    handoff on the device, every record counted and resident, every
    partition committed, the order itself) less the one that holds a job
    to one chunk a step, and in its place: steps of a chunk a device,
    every device a part of the records, the records across the mesh."""
    problems = [p for p in sort_inproc.job_problems(cell, job)
                if "cannot hold" not in p]
    ps = job["pipeline_stats"]
    if not ps:
        return problems
    sort = ps.get("stages", {}).get("sort", {})
    want, chunk = int(cell.config["devices"]), int(cell.config["chunk_bytes"])
    if sort.get("steps", 0) * want * chunk < cell.job_bytes:
        problems.append(f"stage sort: steps {sort.get('steps')} of {want} x "
                        f"{chunk} B cannot hold the job's {cell.job_bytes} B")
    records = cell.job_bytes // gensort.RECORD_BYTES
    rows = sort.get("device_rows") or []
    if len(rows) != want or min(rows) <= 0 or sum(rows) != records:
        problems.append(f"stage sort: device_rows {rows}: not {want} "
                        f"devices that each hold a part of the job's "
                        f"{records} records and together all of them")
    crossed = sort.get("sort_exchange_rows", 0)
    if 2 * crossed <= records:
        problems.append(f"stage sort: sort_exchange_rows {crossed} of "
                        f"{records} records: the records did not cross "
                        "the mesh")
    return problems
