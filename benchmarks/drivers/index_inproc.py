"""Driver: an index job is one call of ``dsi_tpu.cli.planrun.main`` here,
over the documents of a collection.

As ``plan_inproc`` (the configuration gives ``entry``, ``stats_tag`` and
``argv``; the harness process holds the chip; the traced job gets
``--trace-dir`` and its trace is anchored to the job), with three things
of its own:

* **The documents.**  The corpus's generated files are shelves;
  ``docs.py`` cuts them into the job's documents (the traffic mix's
  ``reference_params`` say how), and this driver writes them once a seed
  beside the corpus as ``d<5 digits>.txt`` and hands their paths to the
  entry point in place of the files.  The documents partition the files,
  so a job's bytes are the corpus's.
* **The join, rendered.**  The chain commits the index as ``mr-out-<r>``
  and writes its top-k and their postings as ``plan-join.json``.  After a
  job (outside its measured span) the driver renders that file into the
  reference's ``#top`` / ``#join`` lines as ``mr-out-join`` in the job's
  directory, so that the harness's ``read_output`` compares index and join
  in one.
* **The conditions** (``job_problems``): every document through a device
  wave, the handoff on the device, every partition committed.

A program whose registry does not know ``index_terms`` commits no index
and cannot run such a cell: the run ends at once, with no result and a
non-zero exit.

Importing this file registers the plain reference of kind ``index``
(``reference_index.py``), by the one route a new kind has
(``stream_inproc``'s module text).
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import sys

import corpus
import docs
import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import reference
import reference_index
from drivers import plan_inproc, stream_inproc
from drivers.stream_inproc import _call_main, finish  # noqa: F401

reference.KINDS.setdefault("index", reference_index.lines)


def claim_device(cell) -> None:
    stream_inproc.claim_device(cell)
    schema = importlib.import_module("dsi_tpu.obs.registry").SCHEMA_KEYS
    if "index_terms" not in schema:
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: the program's schema has no index_terms, "
                 "so its indexer chain commits no index")


def _documents(cell) -> list:
    """The job's documents as files beside the corpus, written once a
    seed; their paths in document order."""
    params = cell.traffic["reference_params"]
    directory = os.path.join(
        os.path.dirname(cell.files[0]),
        "docs-" + corpus.params_key({k: params[k] for k in (
            "doc_min_bytes", "doc_max_bytes")}))
    done = os.path.join(directory, "DONE")
    if not os.path.exists(done):
        os.makedirs(directory, exist_ok=True)
        names = []
        for name, data in docs.spans(cell.files, params):
            with open(os.path.join(directory, name), "wb") as f:
                f.write(data)
            names.append(name)
        with open(done, "w") as f:
            json.dump(names, f)
    with open(done) as f:
        return [os.path.join(directory, name) for name in json.load(f)]


def _chunk_bytes(n_bytes: int) -> int:
    """The padded size of a one-document wave (``tfidf.plan_waves``)."""
    return 1 << max(8, int(n_bytes).bit_length())


def warm_up(cell) -> None:
    """The entry point over the fewest documents that walk as the job
    walks: the job's ``pipeline_depth`` longest documents, which it has
    in flight at the first capacity rung before the first of them is
    retired and the walk climbs to the rung the whole job settles on,
    and of every smaller chunk size the shortest document, so that the
    narrowest pull of rows is there as well.  Every wave program of the
    job, and none it does not use, compiles (first run in a checkout)
    or loads from the compile cache (every later run) here, and not in
    the window's first job.  From here on the job's input files are the
    documents."""
    cell.files = _documents(cell)
    by_size = sorted((os.path.getsize(path), path) for path in cell.files)
    first = by_size[-int(cell.config["pipeline_depth"]):]
    picked = {_chunk_bytes(size): path for size, path in reversed(by_size)
              if _chunk_bytes(size) < _chunk_bytes(first[0][0])}
    job = _call_main(cell, [path for _, path in first]
                     + list(picked.values()),
                     os.path.join(cell.workroot, "warm"))
    ps = job["pipeline_stats"] or {}
    walk = (ps.get("stages") or {}).get("indexer", {})
    print(json.dumps({"warm_up": {
        "wall_s": job["wall_s"], "rc": job["rc"], "jax": job["jax"],
        "documents": len(cell.files),
        "waves_by_size": walk.get("waves_by_size"),
        "replays": walk.get("replays"),
        "programs": jaxwatch.programs()}}), flush=True)
    cell.obs["warm_up"] = job
    if job["rc"] != 0 or not ps:
        sys.stderr.write(job["log_text"][-3000:])
        sys.exit(f"benchmarks: {cell.config['entry']} cannot run cell "
                 f"{cell.name}: its warm-up job exited {job['rc']}"
                 + ("" if ps else " and printed no pipeline_stats"))


def _render_join(workdir: str, names: list) -> None:
    """``plan-join.json`` as the reference's ``#top`` / ``#join`` lines,
    in ``mr-out-join``: a document's ordinal is its place in argv."""
    with open(os.path.join(workdir, "plan-join.json")) as f:
        found = json.load(f)
    lines = [f"#top {rank} {df} {word}"
             for rank, (df, word) in enumerate(found["topk"], 1)]
    for word, entry in found["join"].items():
        held = sorted({names[d] for d in entry["docs"]})
        lines.append(f"#join {word} {len(held)} {','.join(held)}")
    with open(os.path.join(workdir, "mr-out-join"), "w") as f:
        f.write("".join(line + "\n" for line in lines))


def run_job(cell, i: int) -> dict:
    job = plan_inproc.run_job(cell, i)
    if job["rc"] == 0:
        _render_join(job["workdir"],
                     [os.path.basename(path) for path in cell.files])
    return job


def _flag(argv: list, name: str) -> str:
    return str(argv[argv.index(name) + 1])


def job_problems(cell, job: dict) -> list:
    """Every document through a device wave, the handoff on the device,
    and every partition of the index committed."""
    problems = []
    if "needs the host path" in job["log_text"]:
        problems.append("a stage took the host path")
    ps = job["pipeline_stats"]
    if not ps:
        problems.append(f"{cell.config['stats_tag']} printed no "
                        "pipeline_stats")
        return problems
    want, n_docs = int(cell.config["devices"]), len(cell.files)
    walk = ps.get("stages", {}).get("indexer", {})
    if walk.get("waves", 0) * want < n_docs:
        problems.append(f"stage indexer: {walk.get('waves')} waves of "
                        f"{want} device(s) cannot hold {n_docs} documents")
    if walk.get("docs") != n_docs:
        problems.append(f"stage indexer: docs {walk.get('docs')}, "
                        f"{n_docs} documents were handed over")
    handoff = ps.get("plan", {}).get("plan_handoff")
    if handoff != "device":
        problems.append(f"the handoff left the device: plan_handoff "
                        f"{handoff!r}")
    n_reduce = int(_flag(cell.config["argv"], "--nreduce"))
    parts = [p for p in glob.glob(os.path.join(job["workdir"], "mr-out-*"))
             if p.rsplit("-", 1)[1].isdigit()]
    if len(parts) < n_reduce:
        problems.append(f"{len(parts)} of {n_reduce} partitions of the "
                        "index were committed")
    return problems
