"""Driver: an index job over a collection of page-sized documents, packed
many to a wave: one call of ``dsi_tpu.cli.planrun.main`` here, with
``--pack-docs`` in the configuration's ``argv``.

As ``index_inproc`` (the documents cut from the shelves and written once a
seed, the join rendered beside the committed index, the trace anchored to
the job), with two things of its own, because that driver holds a job to
one document a device a wave:

* **The warm-up** walks the job's last waves.  The packed plan is a
  function of the documents' lengths alone and fills chunks in document
  order, so the documents from where the job's ``pipeline_depth`` + 2
  last chunks begin pack, alone, into those same chunks: full ones, which
  overflow the first capacity rung with ``pipeline_depth`` waves in
  flight and replay at the rung the whole job settles on, and the short
  last one, whose narrower pull of rows is a program of its own.  The
  plan is the program's (``plan_packed_waves``), asked for through
  ``importlib`` as the registry's schema is.
* **The conditions** (``job_problems``): every document handed over is in
  a wave, the packing engaged (``pack_docs`` true, and the padded bytes
  uploaded, less the short last wave, at most 1.25 times the job's), no
  stage on the host path, the
  handoff on the device, every partition committed.

A program whose registry does not know ``pack_docs`` cannot pack and
cannot run such a cell: the run ends at once, before any input is made,
with no result and a non-zero exit.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
from drivers import index_inproc
from drivers.index_inproc import (  # noqa: F401
    _call_main, _documents, _flag, finish, run_job)

#: Padded bytes uploaded over the job's bytes, the short last wave left
#: out: above it the walk did not fill its waves (a document a wave pads
#: these pages to ~1.4 times their bytes).
FILL_LIMIT = 1.25


def claim_device(cell) -> None:
    index_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "pack_docs" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no pack_docs, so "
                 "its indexer chain cannot pack documents into waves")


def _chunk_bytes(cell) -> int:
    argv = cell.config["argv"]
    return int(_flag(argv, "--chunk-bytes")) if "--chunk-bytes" in argv \
        else int(cell.config["chunk_bytes"])


def _last_waves(cell, sizes: list, count: int) -> int:
    """The ordinal of the first document of the job's ``count``-th wave
    from the end, by the program's own plan."""
    plan = importlib.import_module(
        "dsi_tpu.parallel.grepstream").plan_packed_waves
    waves = plan(sizes, int(cell.config["devices"]), _chunk_bytes(cell))
    firsts = [min(i for slot in slots for i in slot) for slots, _ in waves]
    return min(firsts[-count:]) if firsts else 0


def warm_up(cell) -> None:
    """The entry point over the documents of the job's last
    ``pipeline_depth`` + 2 waves (module docstring).  Every wave program
    and every pull of the job, and none it does not use, compiles (first
    run in a checkout) or loads from the compile cache (every later run)
    here, and not in the window's first job.  From here on the job's
    input files are the documents."""
    cell.files = _documents(cell)
    sizes = [os.path.getsize(path) for path in cell.files]
    first = _last_waves(cell, sizes, int(cell.config["pipeline_depth"]) + 2)
    job = _call_main(cell, cell.files[first:],
                     os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    walk = (ps.get("stages") or {}).get("indexer", {})
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "documents": len(cell.files),
        "warm_documents": len(cell.files) - first,
        "waves_by_size": walk.get("waves_by_size"),
        "docs_per_wave_max": walk.get("docs_per_wave_max"),
        "replays": walk.get("replays"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))


def job_problems(cell, job: dict) -> list:
    """``index_inproc``'s conditions (no stage on the host path, ``docs``
    the documents handed over, the handoff on the device, every
    partition committed) less the one that holds a job to a document a
    device a wave, and in its place: every document in a wave, the waves
    packed."""
    problems = [p for p in index_inproc.job_problems(cell, job)
                if "cannot hold" not in p]
    ps = job["pipeline_stats"]
    if not ps:
        return problems
    n_docs = len(cell.files)
    walk = ps.get("stages", {}).get("indexer", {})
    if walk.get("wave_docs", 0) < n_docs:
        problems.append(f"stage indexer: wave_docs {walk.get('wave_docs')}:"
                        f" not every one of {n_docs} documents was "
                        "dispatched in a wave")
    if walk.get("pack_docs") is not True:
        problems.append("stage indexer: the walk did not pack: pack_docs "
                        f"{walk.get('pack_docs')!r}")
    padded = walk.get("wave_chunk_bytes")
    last_wave = int(cell.config["devices"]) * _chunk_bytes(cell)
    if padded is None or padded - last_wave > FILL_LIMIT * cell.job_bytes:
        problems.append(f"stage indexer: wave_chunk_bytes {padded} for a "
                        f"job of {cell.job_bytes} B: over {FILL_LIMIT} "
                        "times the job's bytes and a wave more, the waves "
                        "were not filled")
    return problems
