#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of stdout, one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``compared``: each number the
comparison with the plain reference held to a limit, beside that limit; the
same numbers are the last lines of stderr.  Everything else a reader needs
to recompute a metric goes on earlier lines.

A run is: set-up (corpus and plain reference from ``--seed``, warm-up of the
cell's own programs), then a closed loop of whole jobs, one submitter: a new
job starts while less than ``--seconds`` have passed since the first was
submitted (and while the traffic mix's ``max_jobs``, if it names one, is not
reached), the job in flight always finishes, only whole jobs count.
Throughput is the bytes of the whole jobs over the seconds in which a job
was being measured: a job's span from ``t_start`` to ``t_end``, or the
narrower one its driver gives (``t_measured_start``, ``t_measured_end``: a
deployment that starts processes per job is measured from the moment it is
in service).  Set-up ends where the first job's measured span begins.  After
the window every job's output is compared byte for byte with the plain
reference.  The rule is the same for every cell and lives only here.

This file knows no cell, configuration, traffic mix or metric by name: it
finds ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``layer_metrics/<metric>.py`` from the names in ``BENCHMARK.json``, and the
configuration names its driver in ``drivers/``.  See ``README.md``.

Exit code 0 only with a result.  No accelerator, fewer chips than the cell
asks for, a device kind missing from ``peaks.json``, or a directory that
holds only the benchmark and not the program: non-zero, and no result.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import shutil
import statistics
import sys
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import corpus  # noqa: E402  (the benchmark's own modules, beside this file)
import reference  # noqa: E402

class BenchError(Exception):
    """The run cannot produce a result (no chip, broken cell, bad data)."""


def log(msg: str) -> None:
    print(msg, flush=True)


class Cell:
    """Everything one run knows: the cell's entries and files, the run's
    arguments, where it may write, and what the driver has observed."""

    def __init__(self, bench: dict, args) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no cell {args.workload!r} in BENCHMARK.json; "
                             f"cells: {sorted(cells)}")
        self.bench = bench
        self.entry = cells[args.workload]
        self.name = self.entry["name"]
        self.chips = int(self.entry["chips"])
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearse_cpu)
        self.config = _load_json("configs", self.entry["config"])
        self.traffic = _load_json("traffic", self.entry["traffic"])
        self.root = ROOT
        self.cache_root = os.path.join(ROOT, ".bench_cache")
        self.workroot = os.path.join(self.cache_root, "work", self.name)
        self.jax_cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(ROOT, ".jaxcache"))
        scale = self.config.get("rehearsal", {}) if self.rehearsal else {}
        self.corpus_params = corpus.effective(
            {**self.config.get("corpus", {}), **scale.get("corpus", {})},
            self.traffic.get("corpus", {}))
        self.passes = int(self.traffic.get("passes", 1))
        self.files: list = []
        self.job_bytes = 0
        self.reference_lines: list = []
        self.obs: dict = {}        # what per-layer readers read
        self.device: dict = {}

    def metric_entries(self, group: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def _load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"BENCHMARK.json names {kind[:-1]} {name!r} but "
                         f"{os.path.relpath(path, ROOT)} is not there")


def load_peaks(kind: str) -> dict:
    """The device's published peaks; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in benchmarks/"
                         f"peaks.json (known: {sorted(table)}); add its "
                         "published peaks with their source")
    return table[kind]


def prepare_inputs(cell: Cell) -> None:
    """Corpus and plain reference from the seed, cached per seed."""
    t0 = time.monotonic()
    made = corpus.ensure(cell.cache_root, cell.corpus_params, cell.seed)
    cell.files = list(made["files"]) * cell.passes
    cell.job_bytes = sum(os.path.getsize(f) for f in made["files"]) \
        * cell.passes
    t1 = time.monotonic()
    ref_params = dict(cell.traffic.get("reference_params", {}),
                      passes=cell.passes)
    ref_key = corpus.params_key({"kind": cell.traffic["reference"],
                                 **ref_params})
    ref_path = os.path.join(made["dir"], f"reference-{ref_key}.json")
    if os.path.exists(ref_path):
        with open(ref_path) as f:
            saved = json.load(f)
        cell.reference_lines = saved["lines"]
        ref_mbps, how = saved["reference_MBps"], "loaded"
    else:
        fn = reference.KINDS[cell.traffic["reference"]]
        cell.reference_lines = fn(made["files"], ref_params)
        ref_s = time.monotonic() - t1
        ref_mbps = cell.job_bytes / cell.passes / 1e6 / ref_s
        how = f"computed in {ref_s:.2f}s"
        with open(ref_path + ".tmp", "w") as f:
            json.dump({"lines": cell.reference_lines,
                       "reference_MBps": ref_mbps}, f)
        os.replace(ref_path + ".tmp", ref_path)
    log(json.dumps({
        "inputs": {"files": len(cell.files), "job_bytes": cell.job_bytes,
                   "corpus": cell.corpus_params, "seed": cell.seed,
                   "generated": made["generated"],
                   "corpus_s": round(t1 - t0, 3),
                   "reference": how, "reference_lines":
                   len(cell.reference_lines),
                   "reference_MBps": round(ref_mbps, 3)}}))


def measured_span(job: dict) -> tuple:
    """``(start, end)`` of the part of a job that throughput counts, on this
    process's monotonic clock: the whole job unless its driver says
    narrower."""
    return (job.get("t_measured_start", job["t_start"]),
            job.get("t_measured_end", job["t_end"]))


def run_window(cell: Cell, driver) -> list:
    """The closed loop.  Returns the job records, each with ``t_start`` and
    ``t_end`` on this process's monotonic clock."""
    jobs = []
    most = int(cell.traffic.get("max_jobs", 0)) or None
    window_start = time.monotonic()
    while not jobs or (time.monotonic() - window_start < cell.seconds
                       and (most is None or len(jobs) < most)):
        job = driver.run_job(cell, len(jobs))
        jobs.append(job)
        log(json.dumps({"job": {k: v for k, v in job.items()
                                if k not in ("log_text",)}},
                       default=str))
        if job["rc"] != 0:
            break  # a failing deployment: do not spin on it
    return jobs


def verify(cell: Cell, driver, jobs: list) -> dict:
    """After the window: every job's merged, sorted output against the
    plain reference, byte for byte; plus the driver's own conditions (every
    map or step on the device, the right platform).  Returns the numbers
    compared, each an exact comparison with the limit 0: jobs that exited
    non-zero, output lines that differ from the reference (missing or
    surplus, as multisets, over all jobs), conditions of the driver that a
    job broke."""
    bad_exits = differing = broken = 0
    for job in jobs:
        problems = []
        if job["rc"] != 0:
            problems.append(f"exit code {job['rc']}")
            bad_exits += 1
        else:
            got = reference.read_output(job["workdir"])
            if got != cell.reference_lines:
                want_n = collections.Counter(cell.reference_lines)
                got_n = collections.Counter(got)
                off = sum(((want_n - got_n) + (got_n - want_n)).values())
                problems.append(
                    f"output differs from the plain reference: {len(got)} "
                    f"lines, want {len(cell.reference_lines)}; {off} lines "
                    "missing or surplus")
                differing += off
        own = list(job.get("problems", [])) + driver.job_problems(cell, job)
        broken += len(own)
        problems += own
        job["problems"] = problems
        if problems:
            log(json.dumps({"job_failed": {"i": job["i"],
                                           "problems": problems}}))
    return {"jobs_exited_nonzero": {"value": bad_exits, "limit": 0},
            "output_lines_differing": {"value": differing, "limit": 0},
            "driver_conditions_broken": {"value": broken, "limit": 0}}


def read_layer_metrics(cell: Cell) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing returns None and the metric is left out.  A quantity
    whose cells report different end-to-end metrics is split by a suffix
    (``map_task_s`` moves one, ``map_task_s.grep`` the other): the reader
    is the file of the name before the first dot."""
    out = {}
    for m in cell.metric_entries("per_layer"):
        if cell.rehearsal and m["source"] != "program_counter":
            continue  # a CPU run gives no time, rate or share
        reader = m["name"].split(".")[0]
        try:
            mod = importlib.import_module(f"layer_metrics.{reader}")
        except ModuleNotFoundError:
            raise BenchError(f"per-layer metric {m['name']!r} has no reader "
                             f"benchmarks/layer_metrics/{reader}.py")
        value = mod.read(cell.obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_device(cell: Cell) -> None:
    """No accelerator, or fewer chips than the cell asks for: no result."""
    want, dev = "cpu" if cell.rehearsal else "tpu", cell.device
    if dev.get("platform") != want or dev.get("count", 0) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} {want} "
                         f"device(s); the device process reported {dev}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="run the cell at its configuration's tiny "
                        "'rehearsal' size on the CPU, to check paths and "
                        "control flow; prints counts only, never a time, "
                        "rate or share")
    args = p.parse_args(argv)
    try:
        return _run(args)
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2


def _run(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "dsi_tpu")):
        raise BenchError("the dsi_tpu package is not beside benchmarks/: "
                         "this directory holds the benchmark and not the "
                         "program")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = Cell(bench, args)
    driver = importlib.import_module(f"drivers.{cell.config['driver']}")
    shutil.rmtree(cell.workroot, ignore_errors=True)
    os.makedirs(cell.workroot)
    log(json.dumps({"cell": cell.name, "config": cell.entry["config"],
                    "traffic": cell.entry["traffic"], "chips": cell.chips,
                    "seed": cell.seed, "seconds": cell.seconds,
                    "trace": int(cell.trace), "rehearsal": cell.rehearsal}))
    try:
        driver.claim_device(cell)   # stream: this process; batch: nothing
        if cell.device:
            check_device(cell)      # before any work
        prepare_inputs(cell)
        driver.warm_up(cell)
        jobs = run_window(cell, driver)
        setup_s = measured_span(jobs[0])[0] - T_PROCESS_START
        window_s = jobs[-1]["t_end"] - jobs[0]["t_start"]
        spans = [measured_span(j) for j in jobs]
        measured_s = sum(end - start for start, end in spans)
        compared = verify(cell, driver, jobs)
        driver.finish(cell, jobs)   # device report, trace reduction
    finally:
        shutil.rmtree(cell.workroot, ignore_errors=True)

    device = cell.device
    check_device(cell)
    if not cell.rehearsal:
        cell.obs["peaks"] = load_peaks(device["kind"])

    done = [j for j in jobs if not j["problems"]]
    done_bytes = sum(j["bytes"] for j in done)
    walls = [j["t_end"] - j["t_start"] for j in jobs]
    compiles = sum(j.get("compiles", 0) for j in jobs)
    cell.obs.update({"jobs": jobs, "window_s": window_s,
                     "measured_s": measured_s,
                     "window_compiles": compiles, "cell": cell.name,
                     "config": cell.config, "traffic": cell.traffic,
                     "job_bytes": cell.job_bytes, "setup_s": setup_s})
    log(json.dumps({"window": {
        "jobs_started": len(jobs), "jobs_complete": len(done),
        "bytes_complete": done_bytes, "window_s": round(window_s, 4),
        "measured_s": round(measured_s, 4),
        "job_measured_s": [round(end - start, 4) for start, end in spans],
        "job_walls_s": [round(w, 4) for w in walls],
        "job_wall_median_s": round(statistics.median(walls), 4),
        "window_compiles": compiles, "setup_s": round(setup_s, 4)}}))

    if cell.trace:
        metrics = read_layer_metrics(cell)
    else:
        values = {"setup_s": setup_s}
        if done_bytes:
            # the configuration names its throughput metric; a mix whose
            # cell is held to a bound of its own names another
            values[cell.traffic.get("throughput_metric",
                                    cell.config["throughput_metric"])] = \
                done_bytes / 1e6 / measured_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metric_entries("end_to_end")
                   if m["name"] in values and not cell.rehearsal}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    result = {"correct": bool(correct and done), "attempted": len(jobs),
              "failed": len(jobs) - len(done), "metrics": metrics,
              "device": device}
    if cell.trace and cell.obs.get("breakdown"):
        result["breakdown"] = cell.obs["breakdown"]
    if cell.rehearsal:
        result["rehearsal"] = True
    result["compared"] = compared   # each number beside its limit, last
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
