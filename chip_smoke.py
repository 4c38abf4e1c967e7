#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at a
data size its users would call real, and checks every answer:

* batch — ``mrrun --workers 3 --backend tpu --check`` (coordinator + workers
  over RPC, the reference's path) for ``tpu_wc``, ``tpu_grep``
  (``[Tt]he``, the reference harness's pattern) and ``tpu_indexer`` over
  8 splits of 16 MiB - 64 B, ``--nreduce 10``, byte parity with the
  sequential oracle;
* streaming — ``wcstream --nreduce 10 --stats`` with default flags over 8
  more files of the same size with a 400,000-word vocabulary each, passed
  8 times (1 GiB, >= 10^6 distinct words, at least one capacity widen);
  parity against a per-file ``Counter`` over ``apps/wc.tokenize`` kept
  here, independent of the engine;
* cache — ``wcstream`` over the first file, twice in two processes: the
  second finds every program in the compile cache and compiles nothing;
* grep stream — ``grepstream --pattern the --workdir --check`` over the
  first file: the grep engine's step on the device, its result committed
  as ``mr-out-0``, its own check against the host scan passed.

This process never imports JAX: one process uses a chip at a time, and the
children need it.  It learns the device from a probe child that exits
before anything else starts.

Exit code 0 and, as the last line of stdout, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
only when a TPU was found and every phase passed: parity true, every map
task and stream step on the device (none on a host path), every child
exit code 0.  Without an accelerator, or outside the repository, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import ast
import collections
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

N_FILES = 8
FILE_BYTES = (16 << 20) - 64   # pads to 2^24 on the device
BATCH_VOCAB = 20_000           # generate_file's default: the repo's corpus
VOCAB = 400_000                # per stream file: >= 10^6 distinct in the job
REPEATS = 8                    # the 8 files passed 8 times: 1 GiB streamed
MIN_DISTINCT = 1_000_000
N_REDUCE = 10
WORKERS = 3
GREP_PATTERN = "[Tt]he"

class SmokeFailure(Exception):
    """A phase did not meet the contract."""


def log(msg: str) -> None:
    print(msg, flush=True)


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    env.update(extra or {})
    return env


def make_corpus(directory: str, n_files: int, file_bytes: int,
                vocab: int, seed: int) -> list:
    """Seeded corpus, generated before anything is timed."""
    from dsi_tpu.utils.corpus import generate_file

    os.makedirs(directory, exist_ok=True)
    paths = []
    for i in range(n_files):
        path = os.path.join(directory, f"pg-{i:02d}.txt")
        generate_file(path, file_bytes, seed=seed + i, vocab_size=vocab)
        paths.append(path)
    return paths


def plain_wordcount(paths: list, repeats: int) -> dict:
    """The streaming phase's reference: a per-file ``Counter`` over the
    app's tokenizer, times the repeat count.  Independent of the engine and
    of ``serve/pack.host_wordcount``."""
    from dsi_tpu.apps.wc import tokenize

    total: collections.Counter = collections.Counter()
    for path in paths:
        with open(path, "rb") as f:
            total.update(tokenize(f.read().decode("ascii")))
    return {w: c * repeats for w, c in total.items()}


def run_logged(cmd: list, env: dict, log_path: str, timeout: float):
    """Run ``cmd`` with stdout+stderr into ``log_path``; returns
    ``(rc, wall_s, text)``.  A child that outlives ``timeout`` is killed
    with its process group, so nothing it started survives."""
    t0 = time.monotonic()
    with open(log_path, "w") as out:
        proc = subprocess.Popen(cmd, env=env, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            import signal

            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            rc = -9
    wall = time.monotonic() - t0
    with open(log_path, errors="replace") as f:
        return rc, wall, f.read()


def compile_lines(text: str) -> dict:
    """``{program: seconds}`` from the ``[compile] name: compiled in Xs``
    lines a child logged (summed over repeats of a name)."""
    out: dict = {}
    for name, secs in re.findall(
            r"^\[compile\] (\S+): compiled in ([0-9.]+)s", text, re.M):
        out[name] = round(out.get(name, 0.0) + float(secs), 1)
    return out


def trace_counters(trace_dir: str) -> dict:
    """Sum of every worker's tracer counters (the ``meta`` head of each
    ``trace-<pid>.jsonl`` that ``mrrun --trace-dir`` collects)."""
    total: collections.Counter = collections.Counter()
    for path in glob.glob(os.path.join(trace_dir, "trace-*.jsonl")):
        with open(path) as f:
            head = json.loads(f.readline())
        for k, v in head.get("counters", {}).items():
            total[k] += v
    return dict(total)


def batch_phase(app: str, files: list, root: str, device: dict,
                timeout: float) -> dict:
    """One ``mrrun --backend tpu --check`` job; raises on any breach."""
    wd = os.path.join(root, app)
    trace = os.path.join(wd, "trace")
    os.makedirs(wd)
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", str(WORKERS), "--backend", "tpu",
           "--nreduce", str(N_REDUCE), "--workdir", wd,
           "--trace-dir", trace, "--timeout", str(timeout), "--check",
           app] + files
    rc, wall, text = run_logged(
        cmd, child_env({"DSI_GREP_PATTERN": GREP_PATTERN}),
        os.path.join(root, f"{app}.log"), timeout + 300)
    workers = [dict(kv.split("=") for kv in m.split())
               for m in re.findall(r"^mrworker: pid=\d+ (backend=tpu .*)$",
                                   text, re.M)]
    counters = trace_counters(trace)
    res = {"phase": f"mrrun {app}",
           "bytes": sum(os.path.getsize(f) for f in files),
           "wall_s": round(wall, 1),
           "device_maps": sum(int(w["device_maps"]) for w in workers),
           "host_maps": sum(int(w["host_maps"]) for w in workers),
           "device_workers": len(workers),
           "worker_platforms": sorted({w["platform"] for w in workers}),
           "parity": "mrrun: parity OK" in text,
           "compile_s": compile_lines(text)}
    log(json.dumps(res))
    # One device worker per chip; with the CPU named there is no chip to
    # share and every worker is a device-backend worker.
    want_workers = (min(WORKERS, device["count"])
                    if device["platform"] == "tpu" else WORKERS)
    problems = []
    if rc != 0:
        problems.append(f"exit code {rc}")
    if not res["parity"]:
        problems.append("no parity")
    if res["device_workers"] != want_workers:
        problems.append(f"{res['device_workers']} device workers reported, "
                        f"want {want_workers} (one per chip)")
    if res["worker_platforms"] != [device["platform"]]:
        problems.append(f"worker platforms {res['worker_platforms']}")
    # >= : a map task re-queued while its worker was still compiling may
    # run twice (presumed-dead re-execution, first commit wins).
    if res["device_maps"] < len(files) or res["host_maps"] != 0:
        problems.append(f"{res['device_maps']} device maps and "
                        f"{res['host_maps']} host maps of {len(files)}")
    if (counters.get("tpu_map_device", 0) != res["device_maps"]
            or counters.get("tpu_map_host", 0) != res["host_maps"]):
        problems.append(f"tracer counters {counters} disagree with the "
                        "workers' report")
    if problems:
        raise SmokeFailure(f"mrrun {app}: " + "; ".join(problems)
                           + "\n" + text[-3000:])
    return res


def _stats_dict(text: str, tag: str, prog: str = "wcstream") -> dict:
    m = re.search(rf"^{prog}: {tag}=(\{{.*\}})$", text, re.M)
    if not m:
        raise SmokeFailure(f"{prog} printed no {tag}\n" + text[-3000:])
    return ast.literal_eval(m.group(1))


def stream_phase(name: str, files: list, root: str, device: dict,
                 want: dict, timeout: float, extra=(),
                 need_widen: bool = True) -> dict:
    """One ``wcstream --stats`` run over ``files``; raises on any breach.
    ``want`` is the plain reference ``{word: count}``."""
    wd = os.path.join(root, name)
    os.makedirs(wd)
    cmd = [sys.executable, "-m", "dsi_tpu.cli.wcstream",
           "--nreduce", str(N_REDUCE), "--stats", "--workdir", wd,
           *extra] + files
    rc, wall, text = run_logged(cmd, child_env(),
                                os.path.join(root, f"{name}.log"), timeout)
    if rc != 0:
        raise SmokeFailure(f"{name}: exit code {rc}\n" + text[-3000:])
    if "needs the host path" in text:
        raise SmokeFailure(f"{name}: the stream took the host path")
    pstats = _stats_dict(text, "pipeline_stats")
    cstats = _stats_dict(text, "compile_stats")
    dev = _stats_dict(text, "device")
    got = {}
    for r in range(N_REDUCE):
        with open(os.path.join(wd, f"mr-out-{r}"), encoding="ascii") as f:
            for line in f:
                w, _, c = line.rstrip("\n").rpartition(" ")
                got[w] = int(c)
    res = {"phase": name,
           "bytes": sum(os.path.getsize(f) for f in files),
           "wall_s": round(wall, 1), "steps": pstats["steps"],
           "widen_replays": pstats["replays"],
           "device_rows": pstats["device_rows"],
           "distinct_words": len(got), "parity": got == want,
           "compile_s": {k: v[1] for k, v in cstats["programs"].items()
                         if v[1] >= 0.5},
           "cache": {k: cstats[k] for k in
                     ("cache_requests", "cache_hits", "cache_misses")}}
    for k in ("folds", "widens", "sync_pulls", "mesh_shards",
              "shard_imbalance"):
        if k in pstats:
            res[k] = pstats[k]
    log(json.dumps(res))
    problems = []
    if dev != device:
        problems.append(f"ran on {dev}, probe saw {device}")
    if not res["parity"]:
        problems.append("no parity with the plain per-file Counter")
    if need_widen and res["widen_replays"] < 1:
        problems.append("no capacity widen happened")
    if (len(res["device_rows"]) != device["count"]
            or min(res["device_rows"]) <= 0):
        problems.append(f"not every device held a shard: "
                        f"{res['device_rows']}")
    if problems:
        raise SmokeFailure(f"{name}: " + "; ".join(problems))
    return res


def grepstream_phase(files: list, root: str, timeout: float) -> dict:
    """One small ``grepstream --workdir --check`` job for the literal
    ``the``: the program's own check against its host scan decides parity;
    here the commit and the device path are what is looked at."""
    name = "grepstream"
    wd = os.path.join(root, name)
    cmd = [sys.executable, "-m", "dsi_tpu.cli.grepstream", "--pattern",
           "the", "--stats", "--check", "--workdir", wd] + files
    rc, wall, text = run_logged(cmd, child_env(),
                                os.path.join(root, f"{name}.log"), timeout)
    if rc != 0:
        raise SmokeFailure(f"{name}: exit code {rc}\n" + text[-3000:])
    if "needed the host path" in text:
        raise SmokeFailure(f"{name}: the stream took the host path")
    pstats = _stats_dict(text, "pipeline_stats", prog=name)
    with open(os.path.join(wd, "mr-out-0"), encoding="ascii") as f:
        records = dict(line.split(" ", 1) for line in f.read().splitlines())
    res = {"phase": name, "bytes": sum(os.path.getsize(f) for f in files),
           "wall_s": round(wall, 1), "steps": pstats["steps"],
           "device_rows": pstats["device_rows"],
           "lines": int(records["lines"]), "matched": int(records["matched"]),
           "parity": "grepstream: parity OK" in text}
    log(json.dumps(res))
    if not res["parity"] or sum(res["device_rows"]) != res["lines"] \
            or not res["lines"] or os.listdir(wd) != ["mr-out-0"]:
        raise SmokeFailure(f"{name}: {res}, committed {os.listdir(wd)}")
    return res


def run_phases(device: dict, root: str, n_files: int = N_FILES,
               file_bytes: int = FILE_BYTES, batch_vocab: int = BATCH_VOCAB,
               vocab: int = VOCAB, repeats: int = REPEATS,
               min_distinct: int = MIN_DISTINCT,
               timeout: float = 1000.0) -> list:
    """Every phase in order; returns their result dicts, raises
    :class:`SmokeFailure` at the first breach.  The tests call this at a
    tiny size with the CPU named explicitly."""
    t0 = time.monotonic()
    batch_files = make_corpus(os.path.join(root, "corpus-batch"), n_files,
                              file_bytes, batch_vocab, seed=2100)
    files = make_corpus(os.path.join(root, "corpus-stream"), n_files,
                        file_bytes, vocab, seed=2200)
    want = plain_wordcount(files, repeats)
    log(f"corpus: 2 x {n_files} files x {file_bytes} B (batch vocabulary "
        f"{batch_vocab}/file; stream {vocab}/file, {len(want)} distinct "
        f"words), generated and counted in {time.monotonic() - t0:.1f}s")
    if len(want) < min_distinct:
        raise SmokeFailure(f"stream corpus has {len(want)} distinct words, "
                           f"want >= {min_distinct}")
    results = [batch_phase(app, batch_files, root, device, timeout)
               for app in ("tpu_wc", "tpu_grep", "tpu_indexer")]
    # The same command in two processes against one compile cache: the
    # second walks exactly the first's programs and must compile none.
    want1 = plain_wordcount(files[:1], 1)
    for name in ("wcstream-cold", "wcstream-warm"):
        results.append(stream_phase(name, files[:1], root, device, want1,
                                    timeout))
    warm = results[-1]
    if warm["cache"]["cache_misses"] or not warm["cache"]["cache_hits"]:
        raise SmokeFailure("the second wcstream process compiled: "
                           f"{warm['cache']} {warm['compile_s']}")
    results.append(grepstream_phase(files[:1], root, timeout))
    results.append(stream_phase("wcstream", files * repeats, root, device,
                                want, timeout))
    if device["count"] > 1:
        # The sharded device table: mesh_fold_* crosses the interconnect.
        results.append(stream_phase(
            "wcstream-mesh", files, root, device,
            plain_wordcount(files, 1), timeout,
            extra=("--mesh-shards", str(device["count"]),
                   "--device-accumulate")))
    return results


def main() -> int:
    if not os.path.isdir(os.path.join(REPO, "dsi_tpu")):
        print("chip_smoke: the dsi_tpu package is not beside this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from dsi_tpu.cli.chips import probe_device  # JAX-free, like this file

    t0 = time.monotonic()
    device = probe_device(child_env())
    if device is None or device["platform"] != "tpu":
        print("chip_smoke: no accelerator: a probe process found "
              f"{device or 'no JAX backend'}, this smoke needs the TPU",
              file=sys.stderr)
        return 2
    log(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"count: {device['count']}")
    root = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        results = run_phases(device, root)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    log(f"phases: {len(results)} passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
