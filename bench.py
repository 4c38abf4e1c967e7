#!/usr/bin/env python
"""Benchmark: word-count throughput, TPU path vs the sequential oracle.

This measures exactly BASELINE.json's metric — word-count MB/s on a pg-style
corpus versus the sequential reference semantics (`main/mrsequential.go`),
with mr-out-* diff parity as a hard gate.  The oracle is this repo's
line-for-line-semantics port of `main/mrsequential.go:38-86`; the TPU path is
the whole-corpus fused program (`dsi_tpu/ops/corpus_wc.py`): pieced async
uploads, ONE tokenize/sort/group/count launch over the merged corpus, ONE
position-coded D2H pull (~8 bytes per unique word), host-side output files
partitioned by the reference's `ihash % NReduce` (`mr/worker.go:33-37,76`).
The program is compiled explicitly (`dsi_tpu/backends/aotcache.py`) and
persisted by JAX's compile cache (`dsi_tpu/utils/compilecache.py`), so only
the first process against a cache directory pays the XLA compile.

The timed region runs DSI_BENCH_REPS times (default 5): the first two reps
probe the raw and 6-bit-packed upload transports once each and every later
rep commits to the winner (DSI_BENCH_TRANSPORT=raw|pack6 pins the choice).
The best rep is the headline, with the median reported alongside
(``median_mbps``) so the variance stays visible.  A second row measures
the bounded-memory streaming path over DSI_BENCH_STREAM_MB (default 64) of
cycled corpus (``stream_mbps``, with its own exact-count parity gate).

Prints ONE JSON line on stdout:
  {"metric": ..., "value": MB/s, "unit": "MB/s", "vs_baseline": speedup}
`vs_baseline` is TPU MB/s over oracle MB/s measured in the same run on the
same corpus (the reference publishes no numbers of its own).

The oracle runs FIRST and needs no accelerator; the device half runs in a
watchdog subprocess with bounded retries and a global deadline, so this
parent never imports JAX.  With no chip (and the CPU not asked for by
name) the bench prints an error verdict naming the missing chip and exits
non-zero: nothing is re-measured on another backend.  Every failure mode
still emits the JSON line before exit.  Diagnostics go to stderr.

Environment knobs:
  DSI_BENCH_TPU_TIMEOUTS  per-attempt child timeouts, seconds (default
                          "1200,420,240" — the first attempt covers a
                          cold compile of every program; later ones
                          find the compile cache warm)
  DSI_BENCH_DEADLINE_S    global wall budget for the TPU half (default
                          2100).  An attempt only starts if >= 60 s of
                          budget remain (anything less cannot even cover
                          device init), so values under 60 disable the TPU
                          half entirely (error verdict, exit 1).
  DSI_BENCH_STREAM_MB     size of the streaming-path row (default 64;
                          0 disables it).  The row never pre-empts the
                          headline verdict (which is emitted first).  The
                          row runs
                          at the streaming engine's pipeline depth
                          (DSI_STREAM_PIPELINE_DEPTH, default 2) and
                          reports per-phase seconds as ``stream_phases``.
  DSI_BENCH_KERNEL_REPS   reps for the wire-independent kernel-only row
                          (default 5; 0 disables): upload one stream
                          chunk once, run the wc step K times on the
                          HBM-resident buffer, report median kernel-only
                          MB/s (kernel_sort_mbps).
  DSI_BENCH_TFIDF_MB      size of the TF-IDF engine row (default 16;
                          0 disables; accelerators run it only when the
                          knob is set explicitly): the pipelined wave
                          walk over the cycled corpus, token-invariant
                          gated, with tfidf_phases mirroring
                          stream_phases.
  DSI_BENCH_GREP_MB       size of the streaming-grep engine row (default
                          16; 0 disables; accelerators opt-in like the
                          tfidf row): grep_streaming over the cycled
                          corpus, parity-gated line-for-line against the
                          host-grep oracle, with grep_phases and the
                          oracle's own MB/s alongside.
                          DSI_BENCH_GREP_PATTERN picks the literal
                          (default "the"); DSI_BENCH_GREP_DEVICE_ACC=1
                          folds the match histogram + top-k candidates
                          on device (dsi_tpu/device/topk.py).
  DSI_BENCH_CKPT          the stream row's checkpoint/restore cost keys
                          (dsi_tpu/ckpt), a cadence-1 sync-vs-async A/B:
                          ckpt_overhead_pct (sync-full, the PR-5 path)
                          vs ckpt_async_overhead_pct (overlapped commits
                          + incremental saves), ckpt_full_bytes_per_save
                          vs ckpt_delta_bytes_per_save, and resume_gap_s
                          from the delta CHAIN — every pass parity-
                          gated.  CPU boxes run it whenever the stream
                          row measured; accelerators opt in with 1 (four
                          more stream passes on a time-boxed window);
                          0 disables.
  DSI_BENCH_SPEC_MB       size of the speculative-execution A/B row
                          (default 4; 0 disables): the same shard job
                          with one injected slow worker, backup
                          dispatch on vs --no-spec — spec_backup_mbps
                          vs spec_nobackup_mbps, spec_backup_fired,
                          spec_duplicate_commits (must be 0), each arm
                          parity-gated vs the sequential oracle.
  DSI_BENCH_NET_MB        size of the network-data-plane A/B row
                          (default 4; 0 disables): the same multi-file
                          wordcount with shuffle over localhost TCP and
                          private per-worker workdirs (mrrun --net) vs
                          the shared-directory plane — net_shuffle_mbps
                          vs net_fs_mbps, plus net_ratio (raw/wire
                          through the line codec) and locality_hits,
                          each arm parity-gated vs the oracle.
  DSI_BENCH_FRAMEWORK_MB  corpus size for the distributed N-worker row
                          (default 48; 0 disables it; auto-shrunk so its
                          oracle pass costs ~100 s on a slow box, skipped
                          outright when even the floor would exceed
                          ~240 s).  The row runs AFTER the accelerator
                          half, outside DSI_BENCH_DEADLINE_S: worst-case
                          total bench wall is deadline + row (<= ~240 +
                          its own DSI_BENCH_FRAMEWORK_TIMEOUT, default
                          300 s).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# The compile cache is placed by utils/compilecache.py (through
# platformpin.require_device) in the processes that compile: the
# --tpu-child and the CLIs the rows spawn.  This parent never imports JAX
# (a parent that holds the chip starves its children).

N_FILES = int(os.environ.get("DSI_BENCH_FILES", "8"))
FILE_SIZE = int(os.environ.get("DSI_BENCH_FILE_SIZE",
                               str((2 << 20) - 64)))  # pads to 2^21 on device
N_REDUCE = 10
# Stream-row program shape.  2 MiB chunks with a 2^15 unique capacity:
# fewer step boundaries than 1 MiB/2^14 and no capacity widening on the
# bench corpus's ~24k uniques/chunk (the two shapes' rates are not
# measured on the chip).
STREAM_CHUNK_BYTES = 1 << 21
STREAM_U_CAP = 1 << 15
# Overridable so tests (and ad-hoc small-corpus runs) don't overwrite the
# canonical .bench corpus/oracle.
WORKDIR = (os.environ.get("DSI_BENCH_WORKDIR")
           or os.path.join(REPO, ".bench"))
ORACLE_OUT = os.path.join(WORKDIR, "mr-correct.txt")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def env_float(name: str, default: float) -> float:
    """Float env knob with the always-emit-a-verdict discipline: malformed
    values fall back to the default (logged) instead of raising."""
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        log(f"ignoring malformed {name}")
        return default


def run_oracle(files) -> tuple[float, float]:
    """Sequential oracle (mrsequential.go:38-86 semantics); pure host CPU."""
    from dsi_tpu.apps import wc
    from dsi_tpu.mr.sequential import run_sequential
    from dsi_tpu.obs import span

    with span("task", stats={}, phase="bench.oracle") as pt:
        run_sequential(wc.Map, wc.Reduce, files, ORACLE_OUT)
    dt = pt.elapsed_s
    total_mb = sum(os.path.getsize(p) for p in files) / 1e6
    return dt, total_mb / dt


def tpu_child(result_path: str) -> int:
    """Child-process body: device init + kernel path + parity check.

    Everything that can hang (backend init, compiles) happens here, so
    the parent's kill-on-timeout recovers from any of it.  Writes a JSON
    result to ``result_path``; parent treats a missing file as failure.
    """
    from dsi_tpu.backends import aotcache
    from dsi_tpu.ops.corpus_wc import corpus_wordcount, write_corpus_output
    from dsi_tpu.utils.corpus import ensure_corpus
    from dsi_tpu.obs import span

    def emit(obj: dict) -> None:
        # Per-thread temp name: the init-watchdog thread and the main
        # thread may both emit around the init deadline; a shared temp
        # file could tear.  Both os.replace targets are atomic.
        import threading

        tmp = f"{result_path}.tmp{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, result_path)

    # Same deterministic list as the parent's oracle run — NOT a directory
    # glob, which would sweep in stale pg-*.txt files from an older corpus
    # configuration and guarantee a parity mismatch.
    files = ensure_corpus(WORKDIR, n_files=N_FILES, file_size=FILE_SIZE)

    # Graceful-shutdown seam for the parent watchdog's SIGTERM: SystemExit
    # unwinds the interpreter so the PJRT client's destructor releases the
    # device claim (a SIGKILL here wedges the claim for later processes).
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from dsi_tpu.utils.platformpin import (NoAcceleratorError,
                                           require_device)

    # Self-bounded init: a wedged device claim blocks jax.devices() inside
    # a C call indefinitely (signals deferred, so only SIGKILL from outside
    # works).  This daemon thread turns that into a clean, fast error
    # verdict: no claim is held pre-init, so _exit is safe here.
    # (When run under the full bench, the parent watchdog's init deadline
    # is the backstop; set this BELOW it so the clean child verdict wins
    # the race.)
    init_timeout = env_float("DSI_CHILD_INIT_TIMEOUT", 0.0)
    import threading

    init_settled = threading.Event()  # set once jax.devices() returns/raises
    # The settle lock serializes the watchdog's final decision against the
    # main thread's completion mark (the unlocked re-check left
    # the whole emit duration as a TOCTOU window): once the main thread
    # has acquired the lock and set the flag, _exit cannot fire.  The
    # residual hazard is inherent — the device claim goes live inside the
    # jax.devices() C call, so a window between the claim appearing and
    # _settle() acquiring the lock cannot be closed from Python; the 5 s
    # grace re-check plus this lock make it as narrow as the runtime
    # allows.
    settle_lock = threading.Lock()

    def _settle():
        with settle_lock:
            init_settled.set()

    if init_timeout > 0:
        def _init_watchdog():
            # wait() (not sleep) + a 5 s grace re-check narrow the race
            # where init completes right at the deadline; the lock below
            # closes it.
            if init_settled.wait(init_timeout):
                return
            if init_settled.wait(5.0):
                return
            emit({"error": f"device init exceeded {init_timeout:.0f}s "
                           "(outage or wedged claim)"})
            with settle_lock:
                if init_settled.is_set():
                    # Init completed during the emit: a verdict file now
                    # wrongly claims failure, but exiting would be worse
                    # (_exit on a live claim wedges the device) — let the
                    # main thread overwrite the verdict with the real one.
                    return
                os._exit(3)

        threading.Thread(target=_init_watchdog, daemon=True).start()

    t0 = time.perf_counter()
    try:
        devices = require_device("bench.py")  # also places the cache
    except NoAcceleratorError as e:
        _settle()
        emit({"error": str(e), "permanent": True})
        return 1
    _settle()
    init_s = time.perf_counter() - t0
    platform = devices[0].platform
    log(f"child: devices={devices} init={init_s:.1f}s")
    # Tell the watchdog parent init completed: a wedged device claim hangs
    # inside jax.devices() indefinitely (observed on this platform), and the
    # parent fails the attempt fast when this marker doesn't appear.
    with open(result_path + ".init", "w") as f:
        f.write(f"{init_s:.1f}")

    def run_once(pack6: bool):
        phases = {"mode": "pack6" if pack6 else "raw"}
        t0 = time.perf_counter()
        raws = []
        for p in files:
            with open(p, "rb") as f:
                raws.append(f.read())
        phases["read_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        res = corpus_wordcount(raws, pack6=pack6)
        phases["kernel_s"] = round(time.perf_counter() - t0, 3)
        # Upload sub-phase (inside kernel_s) when corpus_wc routes its
        # piece transfer through ops/xfer.put_views; 0.0 means it didn't
        # (pre-integration artifact or host fallback) — omit the keys
        # rather than report a phase that wasn't measured.
        from dsi_tpu.ops import xfer
        if xfer.stats["upload_s"] > 0:
            phases["upload_s"] = round(xfer.stats["upload_s"], 3)
            xfer.stats["upload_s"] = 0.0
        t0 = time.perf_counter()
        if res is not None:
            write_corpus_output(res, N_REDUCE, WORKDIR)
        phases["write_s"] = round(time.perf_counter() - t0, 3)
        return res, phases

    # Warm-up (untimed): pays the one-time XLA compiles (or finds them
    # in the compile cache), warms the first-D2H path, and produces one
    # full output set.  DSI_BENCH_TRANSPORT=raw|pack6 pins the transport;
    # "auto" compiles and probes both.
    transport = os.environ.get("DSI_BENCH_TRANSPORT", "auto")
    pack6_eligible = transport != "raw"
    with span("task", stats={}, phase="bench.warmup") as pt:
        for pack6 in ((False, True) if pack6_eligible else (False,)):
            wres, _ = run_once(pack6)
            if wres is None:
                emit({"error": "kernel fell back to host on this corpus",
                      "permanent": True})
                return 1
    warmup_s = pt.elapsed_s
    compile_s = aotcache.stats["compiled_s"]
    log(f"warmup {warmup_s:.2f}s (compiles: {aotcache.stats})")

    # Transport selection: probe each of raw / 6-bit-packed uploads ONCE,
    # then commit every remaining rep to the winner.  Min-of-N still
    # reports the machine's capability; the median is reported alongside
    # so run-to-run variance stays visible.
    reps = max(1, int(os.environ.get("DSI_BENCH_REPS", "5")))
    times_by_mode: dict = {False: [], True: []}

    def pack6_winning() -> bool:
        t = min(times_by_mode[True], default=1e18)
        f = min(times_by_mode[False], default=1e18)
        return t < f

    rep_times = []
    dt, best_phases = None, {}
    for rep in range(reps):
        if transport == "pack6":
            pack6 = True
        elif not pack6_eligible:
            pack6 = False  # raw pinned, or pack6 program not cached
        elif reps >= 2 and rep == 0:
            pack6 = False
        elif reps >= 2 and rep == 1:
            pack6 = True
        elif rep == 2 and reps > 3 and pack6_winning():
            # Upset guard: pack6 pays a decode prologue raw does not, so
            # a pack6 probe win may mean raw's single probe landed on a
            # slow rep — spend exactly one rep re-probing raw before
            # committing the rest.
            pack6 = False
        else:
            pack6 = pack6_winning()
        t_all = time.perf_counter()
        res, phases = run_once(pack6=pack6)
        rep_s = time.perf_counter() - t_all
        log(f"rep {rep + 1}/{reps}: {rep_s:.3f}s {phases}")
        if res is None:
            emit({"error": "kernel fell back mid-run", "permanent": True})
            return 1
        times_by_mode[pack6].append(rep_s)
        rep_times.append(rep_s)
        if dt is None or rep_s < dt:
            dt, best_phases = rep_s, phases
    tpu_lines = []
    for r in range(N_REDUCE):
        with open(os.path.join(WORKDIR, f"mr-out-{r}"),
                  encoding="utf-8") as f:
            tpu_lines.extend(l for l in f if l.strip())
    tpu_lines.sort()
    with open(ORACLE_OUT, encoding="utf-8") as f:
        oracle_lines = sorted(l for l in f if l.strip())

    parity = tpu_lines == oracle_lines
    if not parity:
        import itertools
        for i, (a, b) in enumerate(
                itertools.zip_longest(tpu_lines, oracle_lines)):
            if a != b:
                log(f"first diff at line {i}: tpu={a!r} oracle={b!r} (lines:"
                    f" tpu={len(tpu_lines)} oracle={len(oracle_lines)})")
                break

    import statistics

    total_mb = sum(os.path.getsize(p) for p in files) / 1e6
    median_s = statistics.median(rep_times)
    phases = {"init_s": round(init_s, 1),
              "compile_s": round(compile_s, 3),
              "warmup_s": round(warmup_s, 3),
              "reps": reps,
              "transports": "+".join(
                  m for m, used in (("raw", times_by_mode[False]),
                                    ("pack6", times_by_mode[True])) if used),
              "median_s": round(median_s, 3)}
    phases.update(best_phases)
    result = {"tpu_s": round(dt, 3), "tpu_mbps": round(total_mb / dt, 2),
              "median_mbps": round(total_mb / median_s, 2),
              "total_mb": round(total_mb, 2),
              "parity": parity, "platform": platform, "phases": phases}
    # The headline verdict is complete and durable from here on: emit it
    # BEFORE the stream row so a parent timeout mid-stream still finds a
    # valid result file (emit is atomic; last write wins).  The
    # provisional marker rides the SAME first emit — a two-emit sequence
    # would leave a SIGTERM window producing a verdict with no stream key
    # at all, violating the XOR contract test_bench_contract.py locks in.
    stream_mb = stream_row_mb()
    if parity and stream_mb > 0:
        result["stream_skipped"] = ("stream row started but did not "
                                    "complete (interrupted?)")
    emit(result)
    if parity and stream_mb > 0:
        try:
            stream = run_stream_row(files, stream_mb)
        except Exception as e:  # never trade the headline for the row
            stream = {"stream_skipped":
                      f"stream row failed: {type(e).__name__}: {e}"}
        result.pop("stream_skipped", None)
        result.update(stream)
        emit(result)
    # Wire-independent kernel-only row + the TF-IDF, grep, and
    # wire/ingest engine rows: same never-trade-the-verdict discipline
    # — each re-emits the (already durable) result with its keys or a
    # skip reason.
    if parity:
        for key, row_fn in (
                ("kernel_skipped", run_kernel_row),
                ("tfidf_skipped", run_tfidf_row),
                ("grep_skipped", run_grep_row),
                ("wire_skipped", run_wire_ingest_row)):
            try:
                result.update(row_fn(files))
            except Exception as e:
                result[key] = f"row failed: {type(e).__name__}: {e}"
            emit(result)
    return 0



def run_provenance() -> dict:
    """Attribution keys stamped into EVERY verdict (success, parity
    failure, no-chip error alike), so a ``scripts/bench_diff.py``
    comparison of two bench records can say WHAT produced each
    number — a throughput delta between two different jax versions or
    hosts is an environment change, not a code regression.  Every key
    degrades to "unknown" rather than failing the bench, and
    bench_diff treats missing/unknown as non-comparable, so old
    artifacts without the block stay diffable (backfill-tolerant)."""
    prov = {}
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        prov["git_sha"] = r.stdout.strip() or "unknown"
    except Exception:
        prov["git_sha"] = "unknown"
    try:  # version without importing jax into the parent process
        from importlib import metadata

        prov["jax_version"] = metadata.version("jax")
    except Exception:
        prov["jax_version"] = "unknown"
    import platform as _platform
    import socket

    prov["platform"] = f"{_platform.system()}-{_platform.machine()}"
    prov["hostname"] = socket.gethostname()
    prov["python"] = _platform.python_version()
    # The repo runs x64 SCOPED (utils/jaxcompat.x64_scoped) unless the
    # env pins it globally — record which, it changes kernel numerics.
    prov["x64"] = os.environ.get("JAX_ENABLE_X64", "scoped")
    prov["utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    return prov


def bench_tracer():
    """The bench's handle on the unified tracer (dsi_tpu/obs):
    DSI_BENCH_TRACE=1 turns on in-memory span buffering so the engine
    rows publish per-phase span rollups (``stream_spans``/``tfidf_spans``
    /``grep_spans``) in the verdict; DSI_TRACE_DIR additionally flushes
    the full trace durably at process exit (atomicio durable writes,
    ``.tmp-*`` reap on configure — the ckpt store's discipline)."""
    from dsi_tpu.obs import get_tracer

    tr = get_tracer()
    if os.environ.get("DSI_BENCH_TRACE") == "1":
        tr.enabled = True
    return tr


def stream_row_mb() -> float:
    return env_float("DSI_BENCH_STREAM_MB", 64.0)


def run_stream_row(files, stream_mb: float) -> dict:
    """Measure the bounded-memory streaming path (the
    headline number alone is the 16.7 MB fused-program special case) by
    cycling the bench corpus ``stream_mb`` worth through
    ``wordcount_streaming`` on the process's device mesh, with exact-count
    parity against the oracle file scaled by the cycle count.

    Always returns either a measured row or a ``stream_skipped`` reason —
    a missing row in the verdict is a contract violation.  A parity
    mismatch suppresses the throughput number (a rate for wrong counts
    must never enter a trend) and ships as a skip reason instead.
    Programs this process has not compiled yet compile here (or are
    found in the compile cache); the compile is logged and counted.
    """
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import stream_files, wordcount_streaming

    # DSI_BENCH_STREAM_DEVICE_ACC=1 runs the row with the device-resident
    # accumulator (device/table.py): folds on device, host pulls every
    # DSI_STREAM_SYNC_EVERY steps.
    device_acc = os.environ.get("DSI_BENCH_STREAM_DEVICE_ACC") == "1"
    from dsi_tpu.obs import span

    corpus_bytes = sum(os.path.getsize(p) for p in files)
    cycles = max(1, round(stream_mb * 1e6 / corpus_bytes))

    def blocks():
        for c in range(cycles):
            if c:
                yield b"\n"
            yield from stream_files(files)

    mesh = default_mesh()
    pstats: dict = {}
    tracer = bench_tracer()
    mark = tracer.mark()
    with span("task", stats={}, phase="bench.stream") as pt:
        acc = wordcount_streaming(blocks(), mesh=mesh, n_reduce=N_REDUCE,
                                  chunk_bytes=STREAM_CHUNK_BYTES,
                                  u_cap=STREAM_U_CAP, aot=True,
                                  device_accumulate=device_acc,
                                  pipeline_stats=pstats)
    dt = pt.elapsed_s
    if acc is None:
        return {"stream_skipped": "stream needed the host path "
                                  "(non-ASCII or >64-byte word)"}

    oracle: dict = {}
    with open(ORACLE_OUT, encoding="utf-8") as f:
        for line in f:
            if line.strip():
                w, _, c = line.rstrip("\n").rpartition(" ")
                oracle[w] = int(c)
    parity = (len(acc) == len(oracle)
              and all(acc.get(w, (0, 0))[0] == c * cycles
                      for w, c in oracle.items()))
    mb = corpus_bytes * cycles / 1e6
    # Per-phase attribution (mirrors the TPU path's ``phases`` dict):
    # says WHERE stream throughput went — kernel-bound,
    # or batch/upload/pull/merge overhead the pipeline failed to hide —
    # and, with device accumulation, show the pull amortization
    # (step_pulls vs sync_pulls: per-step D2H vs ceil(steps/K)+widens).
    phases = {k: pstats[k] for k in ("batch_s", "batch_wait_s", "upload_s",
                                     "kernel_s", "pull_s", "merge_s",
                                     "replay_s", "depth", "replays",
                                     "device_accumulate", "sync_every",
                                     "step_pulls", "folds", "fold_s",
                                     "fold_overflows", "sync_pulls",
                                     "sync_s", "widens", "widen_s",
                                     "table_cap")
              if k in pstats}
    log(f"stream row: {mb:.1f} MB in {dt:.2f}s = {mb / dt:.2f} MB/s "
        f"(cycles={cycles}, parity={parity}, phases={phases})")
    if not parity:
        return {"stream_skipped": f"parity mismatch over {mb:.1f} MB "
                                  f"(throughput suppressed)",
                "stream_parity": False}
    row = {"stream_mbps": round(mb / dt, 2), "stream_mb": round(mb, 1),
           "stream_s": round(dt, 2), "stream_parity": True,
           "stream_phases": phases}
    if tracer.enabled:
        # The per-phase span rollup (dsi_tpu/obs): same measurements as
        # stream_phases plus per-span counts/max — the verdict carries
        # it whenever the bench runs traced (DSI_BENCH_TRACE=1 buffers
        # in-memory; DSI_TRACE_DIR also flushes the full trace durably).
        row["stream_spans"] = tracer.rollup(mark)
    try:
        row.update(run_stream_ckpt_row(files, mesh, device_acc, oracle,
                                       corpus_bytes, stream_mb))
    except Exception as e:  # never trade the stream row for the ckpt one
        row["ckpt_skipped"] = f"ckpt row failed: {type(e).__name__}: {e}"
    return row


def run_stream_ckpt_row(files, mesh, device_acc, oracle,
                        corpus_bytes, stream_mb) -> dict:
    """The checkpoint/restore cost row riding the stream row
    (``dsi_tpu/ckpt``), now a CADENCE-1 sync-vs-async A/B (ISSUE 8):
    four passes over a bounded slice of the stream — a plain WARM pass
    (its own baseline: the stream row's pass may have paid one-time
    compiles, which would make a naive comparison report negative
    overhead), a sync-full checkpointed pass at ``checkpoint_every=1``
    (``ckpt_overhead_pct`` — the PR-5 path, every save a stall-and-
    write full image), an async+incremental pass at the same cadence
    (``ckpt_async_overhead_pct`` — captures overlap the pipeline
    window, saves ship deltas with a periodic full re-base;
    ``ckpt_delta_bytes_per_save`` vs ``ckpt_full_bytes_per_save`` is
    the payload A/B), and a resumed pass from the async pass's delta
    CHAIN (``resume_gap_s`` = load + re-apply deltas + re-upload +
    re-warm + seek), each parity-gated against the oracle counts.

    Cadence 1 is the deliberate, hostile setting: it is the ROADMAP's
    serving-daemon eviction target and the cadence where snapshot cost
    decides whether checkpointing is on by default.

    The slice is capped at ~16 MB (overhead is a ratio; it does not
    need the full row size).  CPU boxes run it whenever the
    stream row measured; accelerators opt in via ``DSI_BENCH_CKPT=1``
    (four more stream passes on a time-boxed run must be a
    choice, not a default), and ``DSI_BENCH_CKPT=0`` disables
    everywhere.  Always returns measured keys XOR ``ckpt_skipped`` —
    the bench-contract discipline; the per-save delta-bytes key rides
    only when the pass produced at least one delta
    (``ckpt_deltas`` >= 1 — a one-step slice has nothing to
    increment).
    """
    explicit = os.environ.get("DSI_BENCH_CKPT")
    if explicit == "0":
        return {"ckpt_skipped": "disabled (DSI_BENCH_CKPT=0)"}
    import jax

    if jax.devices()[0].platform != "cpu" and explicit != "1":
        return {"ckpt_skipped": "accelerator ckpt row is opt-in "
                                "(set DSI_BENCH_CKPT=1)"}
    import shutil

    from dsi_tpu.parallel.streaming import (stream_files,
                                            wordcount_streaming)
    from dsi_tpu.obs import span

    ckpt_dir = os.path.join(WORKDIR, "ckpt-row")
    async_dir = os.path.join(WORKDIR, "ckpt-row-async")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    shutil.rmtree(async_dir, ignore_errors=True)
    cycles = max(1, round(min(stream_mb, 16.0) * 1e6 / corpus_bytes))

    def blocks():
        for c in range(cycles):
            if c:
                yield b"\n"
            yield from stream_files(files)

    def run(**kw):
        pstats: dict = {}
        with span("task", stats={}, phase="bench.stream_ckpt") as pt:
            acc = wordcount_streaming(
                blocks(), mesh=mesh, n_reduce=N_REDUCE,
                chunk_bytes=STREAM_CHUNK_BYTES, u_cap=STREAM_U_CAP,
                aot=True, device_accumulate=device_acc,
                pipeline_stats=pstats, **kw)
        ok = (acc is not None and len(acc) == len(oracle)
              and all(acc.get(w, (0, 0))[0] == c * cycles
                      for w, c in oracle.items()))
        return ok, pt.elapsed_s, pstats

    every = 1  # the A/B's whole point: snapshot EVERY confirmed step
    try:
        base_ok, base_s, _ = run()  # warm plain baseline
        if not base_ok:
            return {"ckpt_skipped": "baseline pass parity mismatch"}
        ck_ok, ck_s, pstats = run(checkpoint_dir=ckpt_dir,
                                  checkpoint_every=every)
        saves = pstats.get("ckpt_saves", 0)
        if not ck_ok:
            return {"ckpt_skipped": "checkpointed pass parity mismatch "
                                    "(overhead suppressed)"}
        if not saves:
            return {"ckpt_skipped": f"stream too short to checkpoint "
                                    f"(0 saves at every={every})"}
        overhead = 100.0 * (ck_s - base_s) / base_s
        full_per_save = pstats.get("ckpt_full_bytes", 0) / saves
        as_ok, as_s, astats = run(checkpoint_dir=async_dir,
                                  checkpoint_every=every,
                                  checkpoint_async=True,
                                  checkpoint_delta=True)
        if not as_ok:
            return {"ckpt_skipped": "async+delta pass parity mismatch "
                                    "(A/B suppressed)"}
        as_overhead = 100.0 * (as_s - base_s) / base_s
        deltas = astats.get("ckpt_deltas", 0)
        # Resume from the async pass's chain — the stronger restore:
        # base image + ordered deltas re-applied, not one flat load.
        resume_ok, _, rstats = run(checkpoint_dir=async_dir,
                                   checkpoint_every=every,
                                   checkpoint_async=True,
                                   checkpoint_delta=True, resume=True)
    finally:
        # Every exit path — skip returns and exceptions included — must
        # drop the row's snapshot files, or stale state-*.npz piles up
        # in the bench workdir across runs.
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        shutil.rmtree(async_dir, ignore_errors=True)
    log(f"ckpt row (cadence 1): sync-full {overhead:.1f}% "
        f"({ck_s:.2f}s) vs async+delta {as_overhead:.1f}% ({as_s:.2f}s) "
        f"over {base_s:.2f}s warm; {saves} saves "
        f"({full_per_save:.0f} B/full) vs {astats.get('ckpt_saves', 0)} "
        f"saves / {deltas} deltas "
        f"({astats.get('ckpt_delta_bytes', 0) / max(1, deltas):.0f} "
        f"B/delta, barrier {astats.get('ckpt_barrier_s', 0)}s); resume "
        f"gap {rstats.get('resume_gap_s', 0)}s from cursor "
        f"{rstats.get('resume_cursor', 0)} (parity={resume_ok})")
    if not resume_ok:
        return {"ckpt_skipped": "resume parity mismatch (gap suppressed)",
                "resume_parity": False}
    row = {"ckpt_overhead_pct": round(overhead, 1),
           "ckpt_async_overhead_pct": round(as_overhead, 1),
           "ckpt_every": every, "ckpt_saves": saves,
           "ckpt_deltas": deltas,
           "ckpt_full_bytes_per_save": round(full_per_save),
           "ckpt_barrier_s": round(astats.get("ckpt_barrier_s", 0.0), 4),
           "resume_gap_s": rstats.get("resume_gap_s", 0.0),
           "resume_parity": True}
    if deltas:
        row["ckpt_delta_bytes_per_save"] = round(
            astats.get("ckpt_delta_bytes", 0) / deltas)
        # Compressed-delta attribution (ISSUE 13,
        # DSI_STREAM_CKPT_COMPRESS default "deltas"): what the same
        # delta arrays would have cost raw, and the resulting ratio —
        # the >= 2x ckpt_delta_bytes evidence rides these two keys.
        raw = astats.get("ckpt_delta_raw_bytes", 0)
        if raw:
            row["ckpt_delta_raw_bytes_per_save"] = round(raw / deltas)
            row["ckpt_compress_ratio"] = round(
                raw / max(1, astats.get("ckpt_delta_bytes", 0)), 2)
            row["ckpt_compress_s"] = round(
                astats.get("ckpt_compress_s", 0.0), 4)
    return row


def run_kernel_row(files) -> dict:
    """Transfer-independent kernel-only measurement: upload ONE
    stream-shaped chunk, run the wc step DSI_BENCH_KERNEL_REPS times
    (default 5; 0 disables) on the HBM-resident buffer, report the
    median kernel-only MB/s as ``kernel_sort_mbps``.
    """
    reps = int(env_float("DSI_BENCH_KERNEL_REPS", 5))
    if reps <= 0:
        return {}
    import statistics

    import jax
    import numpy as np

    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import (batch_stream, stream_files,
                                            stream_kernel_reps)

    mesh = default_mesh()
    n_dev = mesh.devices.size
    single = len(jax.devices()) == 1
    chunk = next(batch_stream(stream_files(files), n_dev,
                              STREAM_CHUNK_BYTES))
    chunk = np.array(chunk)  # detach from the batch-stream buffer
    mb = float(np.count_nonzero(chunk)) / 1e6  # honest: bytes processed
    out = {"kernel_reps": reps, "kernel_mb": round(mb, 2)}
    times, exact = stream_kernel_reps(
        chunk, mesh=mesh, n_reduce=N_REDUCE, u_cap=STREAM_U_CAP,
        reps=reps, aot=single)
    med = statistics.median(times)
    log(f"kernel row: {mb:.2f} MB x {reps} reps, median "
        f"{med:.3f}s = {mb / med:.2f} MB/s (exact={exact})")
    if exact:  # a rate for an overflowing kernel never enters a trend
        out["kernel_sort_mbps"] = round(mb / med, 2)
    else:
        out["kernel_sort_skipped"] = "kernel overflowed at this shape"
    return out


def run_tfidf_row(files) -> dict:
    """The TF-IDF engine row (DSI_BENCH_TFIDF_MB, default 16; 0
    disables): the pipelined wave walk (``parallel/tfidf.py``) over the
    bench corpus cycled to ~the requested size, with the whole-corpus
    token invariant as the parity gate (sum of tf over all postings ==
    the oracle's total token count x cycles) and ``tfidf_phases`` (the
    engine's ``wave_phases``) mirroring ``stream_phases``.

    On accelerators the row runs only when explicitly requested
    (DSI_BENCH_TFIDF_MB set): the wave programs are not yet in the warm
    ladder, and an implicit multi-minute cold compile must never ride
    the default bench."""
    explicit = "DSI_BENCH_TFIDF_MB" in os.environ
    mb = env_float("DSI_BENCH_TFIDF_MB", 16.0)
    if mb <= 0:
        return {}
    import jax

    if jax.devices()[0].platform != "cpu" and not explicit:
        return {"tfidf_skipped": "accelerator tfidf row is opt-in "
                                 "(set DSI_BENCH_TFIDF_MB)"}
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import FileDocs, tfidf_sharded
    from dsi_tpu.obs import span

    corpus_bytes = sum(os.path.getsize(p) for p in files)
    cycles = max(1, round(mb * 1e6 / corpus_bytes))
    # Lazy docs: each cycle of the corpus is its own document set, read
    # from disk per wave — the row's host footprint stays O(postings),
    # never O(corpus) (the FileDocs rationale).
    docs = FileDocs(list(files) * cycles)
    total_mb = sum(docs.lengths) / 1e6
    phases: dict = {}
    tracer = bench_tracer()
    mark = tracer.mark()
    with span("task", stats={}, phase="bench.tfidf") as pt:
        res = tfidf_sharded(docs, mesh=default_mesh(), n_reduce=N_REDUCE,
                            u_cap=STREAM_U_CAP, packed=True,
                            wave_stats=phases)
    dt = pt.elapsed_s
    if res is None:
        return {"tfidf_skipped": "tfidf needed the host path "
                                 "(non-ASCII or >64-byte word)"}
    # Token invariant: every (word, doc) posting's tf sums to the total
    # token count the oracle already established for this corpus.
    oracle_tokens = 0
    with open(ORACLE_OUT, encoding="utf-8") as f:
        for line in f:
            if line.strip():
                oracle_tokens += int(line.rstrip("\n").rpartition(" ")[2])
    got_tokens = int(res.tfs.astype("int64").sum())
    parity = got_tokens == oracle_tokens * cycles and len(res) > 0
    phases = {k: (round(v, 4) if isinstance(v, float) else v)
              for k, v in phases.items()}
    log(f"tfidf row: {total_mb:.1f} MB in {dt:.2f}s = "
        f"{total_mb / dt:.2f} MB/s (cycles={cycles}, parity={parity}, "
        f"phases={phases})")
    if not parity:
        return {"tfidf_skipped": f"token invariant failed "
                                 f"({got_tokens} != "
                                 f"{oracle_tokens * cycles})",
                "tfidf_parity": False}
    row = {"tfidf_mbps": round(total_mb / dt, 2),
           "tfidf_mb": round(total_mb, 1), "tfidf_s": round(dt, 2),
           "tfidf_parity": True, "tfidf_phases": phases}
    if tracer.enabled:
        row["tfidf_spans"] = tracer.rollup(mark)
    return row


def run_grep_row(files) -> dict:
    """The streaming grep engine row (DSI_BENCH_GREP_MB, default 16; 0
    disables; accelerators run it only when the knob is set explicitly):
    ``grep_streaming`` (``parallel/grepstream.py``) over the bench
    corpus cycled to ~the requested size, parity-gated against the
    single-pass host-grep oracle (same lines, matched counts,
    occurrences, histogram, and top-k — any divergence suppresses the
    rate), with ``grep_phases`` mirroring ``stream_phases`` and the
    oracle's own MB/s alongside (``grep_oracle_mbps``) so the row reads
    as engine-vs-host, not a bare number.

    DSI_BENCH_GREP_PATTERN picks the literal (default "the");
    DSI_BENCH_GREP_DEVICE_ACC=1 runs the row with the on-device top-k/
    histogram service (device/topk.py) folding confirmed steps and
    pulling every DSI_STREAM_SYNC_EVERY steps — step_pulls vs
    sync_pulls/widens is the amortization.
    """
    explicit = "DSI_BENCH_GREP_MB" in os.environ
    mb = env_float("DSI_BENCH_GREP_MB", 16.0)
    if mb <= 0:
        return {}
    import jax

    pattern = os.environ.get("DSI_BENCH_GREP_PATTERN", "the")
    if jax.devices()[0].platform != "cpu" and not explicit:
        return {"grep_skipped": "accelerator grep row is opt-in "
                                "(set DSI_BENCH_GREP_MB)"}
    from dsi_tpu.parallel.grepstream import (GREP_CHUNK_BYTES,
                                             grep_host_oracle,
                                             grep_streaming)
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import stream_files
    from dsi_tpu.obs import span

    device_acc = os.environ.get("DSI_BENCH_GREP_DEVICE_ACC") == "1"
    single = len(jax.devices()) == 1
    aot = jax.devices()[0].platform != "cpu" and single
    corpus_bytes = sum(os.path.getsize(p) for p in files)
    cycles = max(1, round(mb * 1e6 / corpus_bytes))

    def blocks():
        for c in range(cycles):
            if c:
                yield b"\n"
            yield from stream_files(files)

    # The oracle first: parity ground truth AND the host baseline rate.
    with span("task", stats={}, phase="bench.grep_oracle") as pt:
        want = grep_host_oracle(blocks(), pattern)
    oracle_s = pt.elapsed_s
    total_mb = corpus_bytes * cycles / 1e6

    mesh = default_mesh()
    pstats: dict = {}
    tracer = bench_tracer()
    mark = tracer.mark()
    with span("task", stats={}, phase="bench.grep") as pt:
        res = grep_streaming(blocks(), pattern, mesh=mesh,
                             chunk_bytes=GREP_CHUNK_BYTES, aot=aot,
                             device_accumulate=device_acc,
                             pipeline_stats=pstats)
    dt = pt.elapsed_s
    if res is None:
        return {"grep_skipped": "grep stream needed the host path "
                                "(non-literal pattern or over-wide line)"}
    parity = res == want
    phases = {k: pstats[k] for k in ("batch_s", "batch_wait_s", "upload_s",
                                     "kernel_s", "pull_s", "merge_s",
                                     "replay_s", "depth", "replays",
                                     "device_accumulate",
                                     "sync_every", "step_pulls", "folds",
                                     "fold_s", "fold_overflows",
                                     "sync_pulls", "sync_s", "widens",
                                     "widen_s", "table_cap",
                                     "topk_snapshots", "hist_folds",
                                     "hist_pulls")
              if k in pstats}
    log(f"grep row: {total_mb:.1f} MB in {dt:.2f}s = {total_mb / dt:.2f} "
        f"MB/s vs oracle {total_mb / oracle_s:.2f} MB/s (pattern="
        f"{pattern!r}, matched={res.matched}, parity={parity}, "
        f"phases={phases})")
    if not parity:
        return {"grep_skipped": f"parity mismatch vs host-grep oracle "
                                f"over {total_mb:.1f} MB (throughput "
                                f"suppressed)",
                "grep_parity": False}
    row = {"grep_mbps": round(total_mb / dt, 2),
           "grep_mb": round(total_mb, 1), "grep_s": round(dt, 2),
           "grep_matched": res.matched,
           "grep_oracle_mbps": round(total_mb / oracle_s, 2),
           "grep_vs_oracle": round(oracle_s / dt, 2),
           "grep_parity": True, "grep_phases": phases}
    if tracer.enabled:
        row["grep_spans"] = tracer.rollup(mark)
    return row


def run_wire_ingest_row(files) -> dict:
    """The compressed-wire + parallel-ingest A/B row (ISSUE 13,
    ``DSI_BENCH_WIRE``): three measurements over the bench corpus, each
    parity-gated and measured-XOR-skipped like every engine row.

    * **Shuffle-payload codec**: one real ``mapreduce_step`` over a
      stream-shaped chunk, its pulled packed table run through
      ``wirecodec.pack_rows`` — ``wire_ratio`` (raw valid-row bytes /
      packed bytes, the OSDI'04 combiner-compression analogue) with
      ``wire_parity`` the bit-exact unpack round-trip.
    * **Chunk-upload codec**: ``wordcount_streaming`` with
      ``wire_upload`` on vs off over the same cycled blocks —
      ``wire_upload_ratio``/``wire_decode_s`` with
      ``wire_upload_parity`` the result-dict equality (the decode
      prologue's end-to-end bit-identity evidence).
    * **Parallel ingest**: the same stream read through the
      ``utils/ioread.py`` reader pool (readers=4) vs inline reads —
      ``ingest_materialize_s`` vs ``ingest_serial_materialize_s`` (the
      read wall leaving the producer thread) plus
      ``readahead_hit_pct``, with ``ingest_parity`` the result
      equality.

    CPU boxes run it whenever the bench does; accelerators opt in via
    ``DSI_BENCH_WIRE=1``; ``DSI_BENCH_WIRE=0`` disables everywhere."""
    explicit = os.environ.get("DSI_BENCH_WIRE")
    if explicit == "0":
        return {"wire_skipped": "disabled (DSI_BENCH_WIRE=0)"}
    import jax
    import numpy as np

    if jax.devices()[0].platform != "cpu" and explicit != "1":
        return {"wire_skipped": "accelerator wire/ingest row is opt-in "
                                "(set DSI_BENCH_WIRE=1)"}
    from dsi_tpu.ops import wirecodec
    from dsi_tpu.parallel.shuffle import (_slice_pack, default_mesh,
                                          mapreduce_step, occupied_prefix)
    from dsi_tpu.parallel.streaming import (batch_stream, stream_files,
                                            wordcount_streaming)
    from dsi_tpu.utils.ioread import ParallelBlocks
    from dsi_tpu.obs import span

    mesh = default_mesh()
    n_dev = mesh.devices.size
    # ── shuffle-payload codec on one REAL step's pulled table ──
    chunk = np.array(next(batch_stream(stream_files(files), n_dev,
                                       STREAM_CHUNK_BYTES)))
    keys, lens, cnts, parts, scal = mapreduce_step(
        chunk, n_dev=n_dev, n_reduce=N_REDUCE, max_word_len=16,
        u_cap=STREAM_U_CAP, mesh=mesh, t_cap_frac=4)
    scal_np = np.asarray(scal)
    if scal_np[:, 4].any() or scal_np[:, 3].any():
        return {"wire_skipped": "probe step overflowed/non-ASCII at the "
                                "bench shape (payload unusable)"}
    nus = scal_np[:, 0].astype(np.int64)
    mp = occupied_prefix(int(nus.max()), keys.shape[1])
    packed = np.asarray(_slice_pack(keys, lens, cnts, parts, mp=mp))
    with span("task", stats={}, phase="bench.wire_pack") as pt:
        blob = wirecodec.pack_rows(packed, nus)
    rows2, nus2 = wirecodec.unpack_rows(blob)
    wire_parity = (np.array_equal(nus2, nus)
                   and all(np.array_equal(rows2[d, :int(nus[d])],
                                          packed[d, :int(nus[d])])
                           for d in range(n_dev)))
    raw_bytes = wirecodec.rows_raw_bytes(nus, keys.shape[2])
    if not wire_parity:
        return {"wire_skipped": "pack_rows round-trip mismatch "
                                "(ratio suppressed)",
                "wire_parity": False}
    row = {"wire_parity": True,
           "wire_ratio": round(raw_bytes / len(blob), 2),
           "wire_raw_kb": round(raw_bytes / 1e3, 1),
           "wire_packed_kb": round(len(blob) / 1e3, 1),
           "wire_pack_s": round(pt.elapsed_s, 4)}
    log(f"wire row: shuffle payload {raw_bytes / 1e3:.0f} kB -> "
        f"{len(blob) / 1e3:.0f} kB packed = x{row['wire_ratio']} "
        f"(parity={wire_parity}, pack {pt.elapsed_s:.3f}s)")

    # ── chunk-upload codec + ingest A/B over a bounded slice ──
    corpus_bytes = sum(os.path.getsize(p) for p in files)
    ab_mb = min(env_float("DSI_BENCH_WIRE_MB", 16.0), 64.0)
    cycles = max(1, round(ab_mb * 1e6 / corpus_bytes))
    paths = list(files) * cycles

    def run(source, **kw):
        pstats: dict = {}
        with span("task", stats={}, phase="bench.wire_ab") as pt:
            acc = wordcount_streaming(
                source, mesh=mesh, n_reduce=N_REDUCE,
                chunk_bytes=STREAM_CHUNK_BYTES, u_cap=STREAM_U_CAP,
                aot=True, pipeline_stats=pstats, **kw)
        return acc, pt.elapsed_s, pstats

    def blocks():
        for i, p in enumerate(paths):
            if i:
                yield b"\n"
            yield from stream_files([p])

    base_acc, base_s, _ = run(blocks())
    wired_acc, wired_s, wstats = run(blocks(), wire_upload=True)
    if base_acc is None or wired_acc != base_acc:
        row["wire_upload_parity"] = False
        row["wire_skipped"] = ("wire_upload pass diverged from the raw "
                               "pass (A/B suppressed)")
        return row
    row.update({"wire_upload_parity": True,
                "wire_upload_ratio": wstats.get("wire_ratio", 0.0),
                "wire_upload_steps": wstats.get("wire_steps", 0),
                "wire_raw_steps": wstats.get("wire_raw_steps", 0),
                "wire_decode_s": round(wstats.get("decode_s", 0.0), 4)})
    log(f"wire row: upload codec x{row['wire_upload_ratio']} over "
        f"{wstats.get('wire_steps', 0)} steps "
        f"({wstats.get('wire_raw_steps', 0)} raw fallbacks), wall "
        f"{wired_s:.2f}s vs {base_s:.2f}s raw, decode "
        f"{row['wire_decode_s']}s")

    pool = ParallelBlocks(paths, readers=4)
    pool_acc, pool_s, pstats = run(pool)
    if pool_acc != base_acc:
        row["ingest_parity"] = False
        row["wire_skipped"] = ("reader-pool pass diverged from inline "
                               "reads (ingest A/B suppressed)")
        return row
    # A FRESH serial pass, not the first one's stats: the first pass
    # pays one-time costs (in-process compiles, first-touch page
    # faults) that interleave
    # with the producer thread and inflate its materialize wall —
    # reusing it as the baseline would flatter the pool by exactly
    # that noise.  Warm-vs-warm is the honest A/B.
    serial_acc, serial_s, sstats = run(blocks())
    row.update({"ingest_parity": True, "ingest_readers": 4,
                "readahead_hit_pct": pstats.get("readahead_hit_pct", 0.0),
                "ingest_materialize_s": pstats.get("batch_s", 0.0),
                "ingest_serial_materialize_s": sstats.get("batch_s", 0.0),
                "ingest_wait_s": pstats.get("ingest_wait_s", 0.0)})
    log(f"ingest A/B: materialize {row['ingest_materialize_s']}s "
        f"(readers=4, hit {row['readahead_hit_pct']}%, wall {pool_s:.2f}s)"
        f" vs {row['ingest_serial_materialize_s']}s inline "
        f"(wall {serial_s:.2f}s)")
    return row


def framework_row_mb() -> float:
    return env_float("DSI_BENCH_FRAMEWORK_MB", 48.0)


def run_framework_row(bench_oracle_mbps: float) -> dict:
    """The reference's own headline measurement: the
    REAL distributed framework — coordinator + N worker processes over the
    pull-RPC control plane and shared-FS data plane — versus the
    sequential oracle on the same corpus (``main/test-mr.sh:36-53`` vs
    ``main/mrsequential.go:25-87``).  Chip-independent: host-backend
    workers.

    N = max(3, available cores) — the reference runs 3 workers
    (``test-mr.sh:43-45``); more cores, more workers.  ``framework_cores``
    rides the row because the speedup physically cannot exceed the core
    count: on a 1-core box the distributed run CANNOT beat the sequential
    oracle (process parallelism has nothing to run on), and the row must
    say so rather than look like a framework defect.

    Timing starts when workers spawn (coordinator already listening) and
    stops when the last worker exits (workers exit on TaskStatus=DONE,
    ``mr/worker.go:51-53`` semantics) — excluding the coordinator's 1 Hz
    done-poll + exit-grace, which are fixed constants, not job work.

    Always returns either a measured row or ``framework_skipped``; parity
    mismatch suppresses the throughput (same discipline as the stream
    row).
    """
    mb = framework_row_mb()
    if mb <= 0:
        return {}
    import shutil

    from dsi_tpu.apps import wc
    from dsi_tpu.mr.sequential import run_sequential
    from dsi_tpu.utils.corpus import ensure_corpus
    from dsi_tpu.obs import span

    budget = env_float("DSI_BENCH_FRAMEWORK_TIMEOUT", 300.0)
    # Never trade the verdict for the row: the row runs BEFORE the one
    # JSON line is printed, so its wall must stay bounded on ANY box.
    # The in-process oracle pass cannot be preempted — scale the corpus
    # so it costs ~100 s at this box's just-measured oracle rate (a slow
    # box gets a smaller, still-valid row), and on a box so slow that
    # even the 6 MB floor would blow the bound, skip outright.  Total
    # row wall is therefore <= ~240 (oracle estimate cap) + budget +
    # 30 s coordinator wait + corpus generation — documented in the
    # module header alongside DSI_BENCH_DEADLINE_S (which bounds the
    # accelerator half only).
    if bench_oracle_mbps > 0:
        mb = min(mb, max(6.0, bench_oracle_mbps * 100))
    est_oracle_s = (mb / bench_oracle_mbps * 1.3 + 10
                    if bench_oracle_mbps > 0 else 120.0)
    if est_oracle_s > 240:
        return {"framework_skipped":
                f"box too slow for a bounded row (oracle estimate "
                f"{est_oracle_s:.0f}s at {bench_oracle_mbps:.2f} MB/s)"}
    n_workers = max(3, len(os.sched_getaffinity(0)))
    fw_dir = os.path.join(WORKDIR, "fw")
    shutil.rmtree(fw_dir, ignore_errors=True)
    os.makedirs(fw_dir)
    n_files = max(n_workers, round(mb * 1e6 / FILE_SIZE))
    files = ensure_corpus(os.path.join(WORKDIR, "fw-corpus"),
                          n_files=n_files, file_size=FILE_SIZE)
    total_mb = sum(os.path.getsize(p) for p in files) / 1e6

    # Oracle at THIS scale: the parity ground truth and the same-corpus
    # baseline the speedup is computed against.
    oracle_out = os.path.join(fw_dir, "mr-correct.txt")
    with span("task", stats={}, phase="bench.fw_oracle") as pt:
        run_sequential(wc.Map, wc.Reduce, files, oracle_out)
    fw_oracle_mbps = total_mb / pt.elapsed_s

    # The native library builds lazily on first use (up to ~2 min of
    # g++, once per machine); force it now so no worker pays it inside
    # the timed window — and so the backend label below is TRUTHFUL: if
    # the build is unavailable, every native task body would silently
    # decline to the Python path, and reporting 'native' for a
    # pure-Python run would mislabel the measurement.
    from dsi_tpu import native

    native_ok = native.available()

    # Native-sequential oracle twin: the SAME C++
    # task bodies the distributed workers run, executed sequentially in
    # THIS process with no coordinator/RPC/respawn machinery — so the
    # framework row's headline speedup decomposes honestly into
    # language-speedup (native_oracle / python oracle) x framework-
    # efficiency (framework / native_oracle).  Without it, an 11.3x
    # framework-vs-oracle reads as distributed-systems magic when most
    # of it is compiled task bodies.
    native_row = run_native_oracle_row(files, oracle_out, total_mb,
                                       native_ok, fw_oracle_mbps)

    env = dict(os.environ)
    env["DSI_MR_SOCKET"] = os.path.join(fw_dir, "mr.sock")
    # cwd is the sandbox, so the repo must reach the children via
    # PYTHONPATH (the bench process itself gets it from sys.path.insert).
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    coord = subprocess.Popen(
        [sys.executable, "-m", "dsi_tpu.cli.mrcoordinator", *files],
        cwd=fw_dir, env=env, stdout=sys.stderr, stderr=sys.stderr)
    workers: list = []

    def reap(reason: str) -> dict:
        for p in [coord, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
        log(f"framework row skipped: {reason}")
        return {"framework_skipped": reason}

    # Every exit path below must reap: the explicit skip paths do it via
    # reap(), but an UNEXPECTED exception (worker spawn OSError, oracle
    # read failure) used to leave orphan coordinator/worker processes
    # contending for the core through the rest of the bench.  The finally is a no-op on the normal path — every child
    # has already been wait()ed.
    try:
        row = _run_framework_body(coord, workers, reap, env, fw_dir,
                                  oracle_out, total_mb, n_workers,
                                  native_ok, budget, fw_oracle_mbps)
    finally:
        for p in [coord, *workers]:
            if p.poll() is None:
                p.kill()
                p.wait()
        # Killed writers leave .tmp-* commit orphans (atomic_write's
        # temp prefix) — in the framework sandbox, and in the stream
        # row's checkpoint dir when an earlier interrupted bench died
        # mid-save.  Both directories are quiesced here, so the reap is
        # safe by construction.
        from dsi_tpu.utils.atomicio import reap_tmp_files

        reap_tmp_files(fw_dir)
        reap_tmp_files(os.path.join(WORKDIR, "ckpt-row"))
    row.update(native_row)
    if "framework_mbps" in row and "native_oracle_mbps" in row:
        # The decomposition: framework_vs_oracle ==
        # native_vs_python x framework_vs_native (up to rounding).
        row["framework_vs_native"] = round(
            row["framework_mbps"] / row["native_oracle_mbps"], 2)
    return row


def mesh_child(args_json: str) -> int:
    """Child entry for the mesh A/B row: one ``wordcount_streaming``
    pass over the given corpus on the (env-forced) 8-device virtual
    mesh, mesh-sharded or host-merge per config, printing one JSON line
    — result CRC (the parity bar), throughput, and the pull/widen/
    imbalance counters the parent compares."""
    import zlib

    cfg = json.loads(args_json)
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import (stream_files,
                                            wordcount_streaming)

    mesh = default_mesh(int(cfg["n_dev"]))

    def blocks():
        for c in range(int(cfg["cycles"])):
            if c:
                yield b"\n"
            yield from stream_files(cfg["files"])

    pstats: dict = {}
    t0 = time.perf_counter()
    # depth=1 pins BOTH passes to the lockstep path: the row measures
    # the pull SHAPE (pre-merged vs N partials), not pipelining — and on
    # the forced-8-vdev CPU mesh this jaxlib's collectives are flaky
    # when two in-flight programs both carry an all_to_all (observed
    # glibc heap corruption / misrouted rows at MB-scale shapes; real
    # chips execute in order and are unaffected).
    acc = wordcount_streaming(
        blocks(), mesh=mesh, n_reduce=N_REDUCE,
        chunk_bytes=int(cfg["chunk_bytes"]), u_cap=int(cfg["u_cap"]),
        depth=1, device_accumulate=True,
        mesh_shards=int(cfg["mesh_shards"]), pipeline_stats=pstats)
    dt = time.perf_counter() - t0
    if acc is None:
        print(json.dumps({"error": "stream needed the host path"}))
        return 1
    crc = zlib.crc32(repr(sorted(acc.items())).encode())
    out = {"crc": crc, "mbps": round(cfg["mb"] / dt, 2),
           "uniques": len(acc)}
    for k in ("pull_bytes", "sync_pulls", "widens", "shard_widens",
              "shard_imbalance", "folds", "steps"):
        if k in pstats:
            out[k] = pstats[k]
    print(json.dumps(out))
    return 0


def run_mesh_row() -> dict:
    """Mesh-vs-host-merge A/B on the 8-device virtual CPU mesh (ISSUE 7
    satellite): the same stream run twice in subprocesses — device
    services mesh-sharded (``mesh_shards=8``: ihash-routed shuffle-fold,
    per-shard widens, pre-merged occupied-prefix pulls) versus the
    host-merge device-accumulate path — reporting ``mesh_shuffle_mbps``
    A/B throughput, host bytes pulled per sync both ways, and the
    per-shard widen/imbalance counters.  Chip-independent structural
    evidence (the multichip dryrun's bench twin): subprocesses because
    the virtual 8-device mesh needs ``XLA_FLAGS`` set before jax
    imports.  Parity bar: both children's result CRCs must match (each
    child is the engine whose own parity grid is pinned by tier-1).
    Measured keys XOR ``mesh_skipped`` — the bench-contract discipline.
    ``DSI_BENCH_MESH_SHARDS=0`` disables; other values set the degree."""
    try:
        shards = int(os.environ.get("DSI_BENCH_MESH_SHARDS", "8"))
    except ValueError:
        shards = 8
    if shards <= 0:
        return {"mesh_skipped": "disabled (DSI_BENCH_MESH_SHARDS=0)"}
    mb = env_float("DSI_BENCH_MESH_MB", 4.0)
    # Controlled-vocabulary corpus (the multichip dryrun's discipline):
    # the row isolates the pull-SHAPE effect — with ~6k uniques the
    # hash-balanced shards' occupied prefix rounds to half the
    # partition-placed (n_reduce % n_dev) tables' — and an uncontrolled
    # corpus whose window vocabulary saturates the table capacity would
    # show both paths pulling full-capacity blocks, i.e. nothing.
    import numpy as np

    mesh_dir = os.path.join(WORKDIR, "mesh-corpus")
    os.makedirs(mesh_dir, exist_ok=True)
    path = os.path.join(mesh_dir, "corpus.txt")
    if not os.path.exists(path):
        rng = np.random.default_rng(7)
        vocab = ["".join(chr(97 + (i // 26 ** j) % 26) for j in range(4))
                 for i in range(6000)]
        toks = rng.integers(0, len(vocab), size=200_000)
        with open(path, "w") as f:
            f.write(" ".join(vocab[int(i)] for i in toks))
    files = [path]
    corpus_bytes = os.path.getsize(path)
    cycles = max(1, round(mb * 1e6 / corpus_bytes))
    cfg = {"files": files, "cycles": cycles,
           "mb": corpus_bytes * cycles / 1e6, "n_dev": shards,
           "chunk_bytes": 1 << 17, "u_cap": 1 << 10}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={shards}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    budget = env_float("DSI_BENCH_MESH_TIMEOUT", 240.0)

    def child(mesh_shards: int) -> dict:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-child",
             json.dumps({**cfg, "mesh_shards": mesh_shards})],
            capture_output=True, text=True, timeout=budget, env=env)
        if p.returncode != 0:
            raise RuntimeError(f"mesh child rc={p.returncode}: "
                               f"{p.stderr[-400:]}")
        return json.loads(p.stdout.strip().splitlines()[-1])

    # One retry absorbs the virtual mesh's residual collective flake
    # (a crashed child or a torn exchange fails the CRC gate — the gate
    # never lets a wrong pass publish throughput).
    host = meshed = None
    for attempt in (1, 2):
        try:
            host = child(0)
            meshed = child(shards)
        except Exception as e:
            if attempt == 2:
                return {"mesh_skipped": f"mesh row failed: "
                                        f"{type(e).__name__}: {e}"}
            continue
        if host["crc"] == meshed["crc"]:
            break
        if attempt == 2:
            return {"mesh_skipped": "mesh/host-merge parity mismatch "
                                    "(throughput suppressed)",
                    "mesh_parity": False}
    row = {"mesh_shards": shards, "mesh_parity": True,
           "mesh_mb": round(cfg["mb"], 1),
           "mesh_shuffle_mbps": meshed["mbps"],
           "mesh_host_mbps": host["mbps"],
           "mesh_pull_bytes_per_sync": round(
               meshed["pull_bytes"] / max(1, meshed["sync_pulls"])),
           "mesh_host_pull_bytes_per_sync": round(
               host["pull_bytes"] / max(1, host["sync_pulls"])),
           "mesh_shard_widens": meshed.get("shard_widens", []),
           "mesh_shard_imbalance": meshed.get("shard_imbalance", 0.0)}
    log(f"mesh row: {row['mesh_mb']} MB x2 on {shards} virtual devices — "
        f"shuffle {row['mesh_shuffle_mbps']} MB/s vs host-merge "
        f"{row['mesh_host_mbps']} MB/s, pull bytes/sync "
        f"{row['mesh_pull_bytes_per_sync']} vs "
        f"{row['mesh_host_pull_bytes_per_sync']}, imbalance "
        f"{row['mesh_shard_imbalance']}")
    return row


def run_serve_row() -> dict:
    """The serving-daemon A/B (ISSUE 11 satellite): M small word-count
    jobs submitted to the packed resident daemon (``dsi_tpu/serve``,
    one ``mrserve`` subprocess on the 8-vdev CPU mesh) versus the SAME
    M jobs run serially as one-shot ``wcstream`` CLIs — each of which
    pays its own process start + jax init + compile, which is exactly
    the cost the daemon exists to amortize.  Reports
    ``serve_packed_mbps`` / ``serve_oneshot_mbps`` (wall MB/s over the
    submit-to-done window vs the serial CLI loop) and
    ``serve_amortized_warm_s`` (the daemon's boot-to-ready cost divided
    across the M tenants).  Parity bar: every tenant's daemon output
    must byte-compare equal to the sequential oracle, or the row
    suppresses its throughput.  Measured keys XOR ``serve_skipped`` —
    the bench-contract discipline.  ``DSI_BENCH_SERVE_JOBS`` (default
    8; 0 disables) and ``DSI_BENCH_SERVE_MB`` (per-job MB, default 1)
    size it; chip-independent (host subprocesses), so it rides the
    verdict like the mesh row."""
    try:
        jobs = int(os.environ.get("DSI_BENCH_SERVE_JOBS", "8"))
    except ValueError:
        jobs = 8
    if jobs <= 0:
        return {"serve_skipped": "disabled (DSI_BENCH_SERVE_JOBS=0)"}
    per_mb = env_float("DSI_BENCH_SERVE_MB", 1.0)
    import shutil
    import tempfile

    from dsi_tpu.serve import client as sv

    sdir = os.path.join(WORKDIR, "serve-row")
    shutil.rmtree(sdir, ignore_errors=True)
    os.makedirs(sdir)
    spool = os.path.join(sdir, "spool")
    # AF_UNIX socket paths cap at ~108 bytes; WORKDIR can be deep.
    sock = os.path.join(tempfile.mkdtemp(prefix="dsi-bench-sv-"),
                        "s.sock")
    files = []
    for i in range(jobs):
        path = os.path.join(sdir, f"t{i}.txt")
        vocab = [f"t{i}w{j:04d}" for j in range(600)]
        line = " ".join(vocab) + "\n"
        reps = max(1, round(per_mb * 1e6 / len(line)))
        with open(path, "w") as f:
            f.write(line * reps)
        files.append(path)
    total_mb = sum(os.path.getsize(p) for p in files) / 1e6
    # Per-tenant oracles, no jax in this (parent) process.
    from dsi_tpu.apps import wc
    from dsi_tpu.mr.sequential import run_sequential

    oracles = {}
    for i, p in enumerate(files):
        out = p + ".oracle"
        run_sequential(wc.Map, wc.Reduce, [p], out)
        with open(out, encoding="utf-8") as f:
            oracles[i] = sorted(l for l in f if l.strip())
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    budget = env_float("DSI_BENCH_SERVE_TIMEOUT", 300.0)

    # ── packed daemon half ──
    t_boot = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dsi_tpu.cli.mrserve", "--spool", spool,
         "--socket", sock, "--chunk-bytes", "65536"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        sv.wait_ready(sock, timeout=budget)
        warm_s = time.perf_counter() - t_boot
        t0 = time.perf_counter()
        reps = [sv.submit(sock, f"t{i}", [files[i]])
                for i in range(jobs)]
        final = sv.wait(sock, [r["job_id"] for r in reps],
                        timeout=budget)
        packed_s = time.perf_counter() - t0
        bad = [j for j, r in final.items() if r["state"] != "done"]
        if bad:
            return {"serve_skipped": f"daemon jobs failed: {bad}"}
        for i, rep in enumerate(reps):
            got = []
            for r in range(10):
                with open(os.path.join(rep["out_dir"], f"mr-out-{r}"),
                          encoding="utf-8") as f:
                    got.extend(l for l in f if l.strip())
            if sorted(got) != oracles[i]:
                return {"serve_skipped": f"tenant t{i} parity mismatch "
                                         f"(throughput suppressed)",
                        "serve_parity": False}
        try:
            sv.shutdown(sock)
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
    except Exception as e:
        return {"serve_skipped": f"daemon half failed: "
                                 f"{type(e).__name__}: {e}"}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    # ── one-shot serial half: the same M jobs, a fresh CLI each ──
    t1 = time.perf_counter()
    for i, p in enumerate(files):
        wd = os.path.join(sdir, f"oneshot-{i}")
        os.makedirs(wd, exist_ok=True)
        r = subprocess.run(
            [sys.executable, "-m", "dsi_tpu.cli.wcstream",
             "--workdir", wd, p],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=budget)
        if r.returncode != 0:
            return {"serve_skipped": f"one-shot CLI {i} rc="
                                     f"{r.returncode}: {r.stderr[-200:]}"}
    oneshot_s = time.perf_counter() - t1
    row = {"serve_jobs": jobs, "serve_mb": round(total_mb, 2),
           "serve_parity": True,
           "serve_packed_mbps": round(total_mb / packed_s, 2),
           "serve_oneshot_mbps": round(total_mb / oneshot_s, 2),
           "serve_amortized_warm_s": round(warm_s / jobs, 3)}
    log(f"serve row: {jobs} jobs x {per_mb} MB — packed daemon "
        f"{row['serve_packed_mbps']} MB/s ({packed_s:.2f}s after "
        f"{warm_s:.2f}s boot = {row['serve_amortized_warm_s']}s/tenant) "
        f"vs serial one-shot CLIs {row['serve_oneshot_mbps']} MB/s "
        f"({oneshot_s:.2f}s)")
    return row


def _grep_oracle_payload(data: bytes, pattern: str) -> bytes:
    """The daemon's ``grep.json`` bytes for one tenant, computed with
    no jax import in this (parent) process: a pure-python replica of
    ``grep_host_oracle`` (overlapping occurrence counts, unterminated
    tail counts as a line) serialized exactly as
    ``ServeDaemon._write_grep_result`` spells it.  The latency row's
    per-tenant byte-parity ground truth."""
    pat = pattern.encode("ascii")
    bins, topk = 8, 16
    hist = [0] * bins
    matched = occurrences = line_no = 0
    cands = []
    parts = data.split(b"\n")
    carry = parts.pop()
    if carry:
        parts.append(carry)
    for line in parts:
        occ, i = 0, line.find(pat)
        while i >= 0:
            occ += 1
            i = line.find(pat, i + 1)
        hist[min(occ, bins - 1)] += 1
        if occ:
            matched += 1
            occurrences += occ
            cands.append((line_no, occ))
        line_no += 1
    top = sorted(cands, key=lambda r: (-r[1], r[0]))[:topk]
    return json.dumps(
        {"lines": line_no, "matched": matched,
         "occurrences": occurrences, "hist": hist,
         "topk": [list(r) for r in top]},
        sort_keys=True).encode("utf-8")


def run_serve_latency_row() -> dict:
    """The serving-QoS latency A/B (ISSUE 19 tentpole): N grep tenants
    submitted at once to the resident daemon with packed grep lanes
    (``serve/pack.py`` — up to 8 tenants per device dispatch) versus
    the SAME N tenants against a daemon running grep as
    time-multiplexed step objects (``--no-pack-grep``, the pre-packing
    behaviour).  Per-job latency is the daemon's own clock —
    ``done_ts - submitted_ts`` from the job journal — and the row
    reports nearest-rank p50/p99 across tenants for each arm
    (``serve_pack_p50_s``/``serve_pack_p99_s`` vs
    ``serve_tmux_p50_s``/``serve_tmux_p99_s``).  Parity bar: every
    tenant's ``grep.json`` must byte-compare equal to the no-jax host
    oracle in BOTH arms or the row suppresses its latencies.  Measured
    keys XOR ``serve_lat_skipped``.  ``DSI_BENCH_SERVE_LAT_TENANTS``
    (default 64; 0 disables), ``DSI_BENCH_SERVE_LAT_KB`` (per-tenant
    input, default 24) and ``DSI_BENCH_SERVE_LAT_TIMEOUT`` size it;
    chip-independent (host subprocesses on the 8-vdev CPU mesh)."""
    try:
        tenants = int(os.environ.get("DSI_BENCH_SERVE_LAT_TENANTS", "64"))
    except ValueError:
        tenants = 64
    if tenants <= 0:
        return {"serve_lat_skipped":
                "disabled (DSI_BENCH_SERVE_LAT_TENANTS=0)"}
    per_kb = env_float("DSI_BENCH_SERVE_LAT_KB", 24.0)
    budget = env_float("DSI_BENCH_SERVE_LAT_TIMEOUT", 300.0)
    import shutil
    import tempfile

    from dsi_tpu.serve import client as sv

    sdir = os.path.join(WORKDIR, "serve-lat")
    shutil.rmtree(sdir, ignore_errors=True)
    os.makedirs(sdir)
    files, pats, oracle = [], [], {}
    for i in range(tenants):
        # Same pattern LENGTH across tenants (one packed shape group,
        # the dense-wave case), distinct pattern BYTES per tenant.
        pat = f"w{i:04d}"
        lines = []
        j = 0
        size = 0
        want = int(per_kb * 1024)
        while size < want:
            line = (f"{pat} " * (j % 4) + f"filler{j % 97} text\n")
            lines.append(line)
            size += len(line)
            j += 1
        path = os.path.join(sdir, f"g{i}.txt")
        with open(path, "w") as f:
            f.writelines(lines)
        files.append(path)
        pats.append(pat)
        with open(path, "rb") as f:
            oracle[i] = _grep_oracle_payload(f.read(), pat)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()

    def pctl(lats: list, q: float) -> float:
        s = sorted(lats)
        return s[min(len(s) - 1, max(0, int(math.ceil(q * len(s))) - 1))]

    def arm(name: str, packed: bool):
        """One daemon run: submit every tenant, wait, return (per-job
        latencies, packed-step count) or raise."""
        spool = os.path.join(sdir, f"spool-{name}")
        # AF_UNIX socket paths cap at ~108 bytes; WORKDIR can be deep.
        sock = os.path.join(tempfile.mkdtemp(prefix="dsi-bench-lat-"),
                            "s.sock")
        cmd = [sys.executable, "-m", "dsi_tpu.cli.mrserve",
               "--spool", spool, "--socket", sock,
               "--chunk-bytes", "65536",
               "--max-resident", str(tenants),
               "--quota-steps", "1000000"]
        if not packed:
            cmd.append("--no-pack-grep")
        proc = subprocess.Popen(
            cmd, env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            sv.wait_ready(sock, timeout=budget)
            reps = [sv.submit(sock, f"g{i}", [files[i]], app="grep",
                              pattern=pats[i])
                    for i in range(tenants)]
            final = sv.wait(sock, [r["job_id"] for r in reps],
                            timeout=budget)
            bad = [j for j, r in final.items() if r["state"] != "done"]
            if bad:
                raise RuntimeError(f"{name} arm jobs failed: {bad[:4]}")
            lats = []
            for i, rep in enumerate(reps):
                job = final[rep["job_id"]]
                lats.append(max(0.0, float(job["done_ts"])
                                 - float(job["submitted_ts"])))
                with open(os.path.join(rep["out_dir"], "grep.json"),
                          "rb") as f:
                    if f.read() != oracle[i]:
                        raise AssertionError(
                            f"{name} arm tenant g{i} parity mismatch")
            steps = int(sv.ping(sock).get("grep_packed_steps") or 0)
            try:
                sv.shutdown(sock)
                proc.wait(timeout=30)
            except Exception:
                proc.kill()
            return lats, steps
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    try:
        pack_lats, pack_steps = arm("pack", True)
        tmux_lats, _ = arm("tmux", False)
    except AssertionError as e:
        return {"serve_lat_skipped": f"{e} (latency suppressed)",
                "serve_lat_parity": False}
    except Exception as e:
        return {"serve_lat_skipped": f"latency row failed: "
                                     f"{type(e).__name__}: {e}"}
    row = {"serve_lat_tenants": tenants,
           "serve_lat_kb": round(per_kb, 1),
           "serve_lat_parity": True,
           "serve_lat_packed_steps": pack_steps,
           "serve_pack_p50_s": round(pctl(pack_lats, 0.50), 4),
           "serve_pack_p99_s": round(pctl(pack_lats, 0.99), 4),
           "serve_tmux_p50_s": round(pctl(tmux_lats, 0.50), 4),
           "serve_tmux_p99_s": round(pctl(tmux_lats, 0.99), 4)}
    log(f"serve latency row: {tenants} grep tenants x {per_kb:.0f} KB — "
        f"packed p50/p99 {row['serve_pack_p50_s']}/"
        f"{row['serve_pack_p99_s']}s ({pack_steps} packed steps) vs "
        f"time-multiplexed p50/p99 {row['serve_tmux_p50_s']}/"
        f"{row['serve_tmux_p99_s']}s")
    return row


def run_plan_row() -> dict:
    """The plan-layer A/B (ISSUE 14 satellite): one grep→wordcount
    CHAIN with the matching-line intermediate device-resident
    (``dsi_tpu/plan``, ``planrun`` subprocess) versus the SAME two
    stages run staged — full host materialization between them, the
    6.5840 shape.  Reports ``plan_chained_mbps`` / ``plan_staged_mbps``
    (corpus MB over each run's summed stage walls, from the CLI's
    ``--stats-json``), ``plan_intermediate_bytes`` (host-crossing
    handoff bytes of the chained run — MUST be 0, the ``plan_zero_copy``
    bool gates it) vs ``plan_staged_intermediate_bytes`` (the full
    materialization), parity-gated by byte-comparing the runs'
    mr-out-* sets.  ISSUE 16 adds a third arm: the PIPELINED chained
    run (``--pipeline`` — the wordcount consumes sealed relay buffers
    while the grep still produces) reporting ``plan_pipelined_mbps``
    and the attributed overlap wall ``plan_overlap_s``, byte-parity
    gated against both other arms.  Runs in fresh subprocesses on
    1-device CPU, so it is chip-independent.  Measured keys XOR
    ``plan_skipped`` — the bench-contract discipline.
    ``DSI_BENCH_PLAN_MB`` (default 8; 0 disables) sizes it."""
    mb = env_float("DSI_BENCH_PLAN_MB", 8.0)
    if mb <= 0:
        return {"plan_skipped": "disabled (DSI_BENCH_PLAN_MB=0)"}
    budget = env_float("DSI_BENCH_PLAN_TIMEOUT", 300.0)
    import shutil

    pdir = os.path.join(WORKDIR, "plan-row")
    shutil.rmtree(pdir, ignore_errors=True)
    os.makedirs(pdir)
    corpus_path = os.path.join(pdir, "corpus.txt")
    with open(corpus_path, "w") as f:
        i = 0
        written = 0
        target = mb * 1e6
        while written < target:
            if i % 3 == 0:
                line = (f"dsi chain w{i % 211:03d} step keeps bytes on "
                        f"device w{i % 97:02d} dsi\n")
            else:
                line = f"filler row{i} nothing matches here at all\n"
            f.write(line)
            written += len(line)
            i += 1
    total_mb = os.path.getsize(corpus_path) / 1e6
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # 1-device CPU + fresh compiles: the stream rows' AOT-flake hygiene
    # (aot_fresh_cpu_guard), in subprocess form.
    env.pop("XLA_FLAGS", None)

    def one(mode: str) -> tuple[dict, str]:
        wd = os.path.join(pdir, mode)
        sj = os.path.join(pdir, f"{mode}.stats.json")
        cmd = [sys.executable, "-m", "dsi_tpu.cli.planrun",
               "--chain", "grep-wc", "--pattern", "dsi",
               "--chunk-bytes", str(1 << 20),
               "--workdir", wd, "--stats-json", sj, corpus_path]
        if mode == "staged":
            cmd.insert(-1, "--staged")
        elif mode == "pipelined":
            cmd.insert(-1, "--pipeline")
        r = subprocess.run(cmd, env=env,
                           cwd=os.path.dirname(os.path.abspath(__file__)),
                           capture_output=True, text=True, timeout=budget)
        if r.returncode != 0:
            raise RuntimeError(f"{mode} planrun rc={r.returncode}: "
                               f"{r.stderr[-300:]}")
        with open(sj, encoding="utf-8") as f:
            return json.load(f), wd

    try:
        chained, wd_c = one("chained")
        staged, wd_s = one("staged")
        pipelined, wd_p = one("pipelined")
    except Exception as e:
        return {"plan_skipped": f"plan row failed: "
                                f"{type(e).__name__}: {e}"}

    def outset(wd: str) -> list:
        got = []
        for r in range(10):
            with open(os.path.join(wd, f"mr-out-{r}"),
                      encoding="utf-8") as f:
                got.extend(l for l in f if l.strip())
        return sorted(got)

    try:
        want = outset(wd_s)
        parity = outset(wd_c) == want and outset(wd_p) == want
    except OSError as e:
        return {"plan_skipped": f"missing chain output: {e}"}
    if not parity:
        return {"plan_skipped": "chained/pipelined vs staged parity "
                                "mismatch (throughput suppressed)",
                "plan_parity": False}
    inter_c = int(chained.get("plan_intermediate_bytes", -1))
    inter_s = int(staged.get("plan_intermediate_bytes", 0))
    chained_s = float(chained.get("plan_s", 0.0)) or 1e-9
    staged_s = float(staged.get("plan_s", 0.0)) or 1e-9
    pipe_s = float(pipelined.get("plan_s", 0.0)) or 1e-9
    row = {"plan_mb": round(total_mb, 2), "plan_parity": True,
           "plan_zero_copy": inter_c == 0,
           "plan_chained_mbps": round(total_mb / chained_s, 2),
           "plan_staged_mbps": round(total_mb / staged_s, 2),
           "plan_pipelined_mbps": round(total_mb / pipe_s, 2),
           "plan_overlap_s": float(pipelined.get("plan_overlap_s",
                                                 0.0)),
           "plan_intermediate_bytes": inter_c,
           "plan_staged_intermediate_bytes": inter_s,
           "plan_stage_walls": chained.get("plan_stage_walls", {})}
    log(f"plan row: {total_mb:.1f} MB grep→wc — chained "
        f"{row['plan_chained_mbps']} MB/s ({chained_s:.2f}s, "
        f"{inter_c} host bytes between stages) vs staged "
        f"{row['plan_staged_mbps']} MB/s ({staged_s:.2f}s, "
        f"{inter_s} host bytes); pipelined "
        f"{row['plan_pipelined_mbps']} MB/s ({pipe_s:.2f}s, "
        f"{row['plan_overlap_s']:.2f}s overlapped)")
    return row


def run_spec_row() -> dict:
    """The speculative-execution A/B (ISSUE 15 satellite): one shard
    job with an INJECTED slow shard (worker 0 sleeps per advance
    slice), run twice in fresh subprocess fleets — backup dispatch ON
    (``spec_backup_mbps``) vs ``--no-spec`` (``spec_nobackup_mbps``).
    Reports ``spec_backup_fired`` (backup dispatches in the armed run —
    the row is only meaningful when >= 1), ``spec_duplicate_commits``
    (journal double-commits across BOTH arms — MUST be 0; the
    first-commit-wins gate), and ``spec_resumed`` (attempts that
    restored a checkpoint chain).  Each arm is parity-gated against the
    sequential host oracle by ``shardrun --check`` (exit 2 = mismatch,
    throughput suppressed).  ISSUE 16 adds a third arm under the SAME
    injected straggler: ``--resplit`` (dynamic re-split — the
    straggler's remaining range splits into sub-shards for the idle
    workers instead of one full-range backup), reporting
    ``spec_resplit_mbps`` / ``spec_resplits`` / ``spec_subshards``;
    its duplicate commits fold into the same must-be-0 gate.  The
    re-split trigger is load-dependent, so that arm skips honestly
    (``spec_resplit_skipped``) when no re-split fired, without
    suppressing the backup half.  Chip-independent (1-device CPU
    workers), measured keys XOR ``spec_skipped``.  ``DSI_BENCH_SPEC_MB``
    (default 4; 0 disables) sizes it."""
    mb = env_float("DSI_BENCH_SPEC_MB", 4.0)
    if mb <= 0:
        return {"spec_skipped": "disabled (DSI_BENCH_SPEC_MB=0)"}
    budget = env_float("DSI_BENCH_SPEC_TIMEOUT", 300.0)
    import shutil

    sdir = os.path.join(WORKDIR, "spec-row")
    shutil.rmtree(sdir, ignore_errors=True)
    os.makedirs(sdir)
    corpus_path = os.path.join(sdir, "corpus.txt")
    with open(corpus_path, "w") as f:
        i = 0
        written = 0
        target = mb * 1e6
        while written < target:
            line = (" ".join(
                "spec" + chr(ord("a") + (i + j) % 23) * 2
                for j in range(9)) + "\n")
            f.write(line)
            written += len(line)
            i += 1
    total_mb = os.path.getsize(corpus_path) / 1e6
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1-device CPU workers

    def one(mode: str) -> dict:
        wd = os.path.join(sdir, mode)
        sj = os.path.join(sdir, f"{mode}.stats.json")
        e = dict(env)
        e["DSI_MR_SOCKET"] = os.path.join(sdir, f"{mode}.sock")
        cmd = [sys.executable, "-m", "dsi_tpu.cli.shardrun",
               "--workers", "3", "--shards", "3",
               "--workdir", wd, "--chunk-bytes", str(1 << 16),
               "--ckpt-secs", "0.2", "--progress-s", "0.1",
               "--spec-floor", "2.0", "--shard-timeout", "120",
               "--slow-worker", "0:1.0",
               "--check", "--stats-json", sj, corpus_path]
        if mode == "nobackup":
            cmd.insert(-1, "--no-spec")
        elif mode == "resplit":
            cmd.insert(-1, "--resplit")
        r = subprocess.run(cmd, env=e,
                           cwd=os.path.dirname(os.path.abspath(__file__)),
                           capture_output=True, text=True,
                           timeout=budget)
        if r.returncode == 2:
            raise RuntimeError(f"{mode} arm parity mismatch")
        if r.returncode != 0:
            raise RuntimeError(f"{mode} shardrun rc={r.returncode}: "
                               f"{r.stderr[-300:]}")
        with open(sj, encoding="utf-8") as f:
            return json.load(f)

    try:
        backup = one("backup")
        nobackup = one("nobackup")
    except Exception as e:
        return {"spec_skipped": f"spec row failed: "
                                f"{type(e).__name__}: {e}"}
    resplit, resplit_skip = None, None
    try:
        resplit = one("resplit")
    except Exception as e:
        resplit_skip = (f"resplit arm failed: "
                        f"{type(e).__name__}: {e}")
    dup = (int(backup.get("duplicate_commits", 0))
           + int(nobackup.get("duplicate_commits", 0))
           + int((resplit or {}).get("duplicate_commits", 0)))
    backup_s = float(backup.get("wall_s", 0.0)) or 1e-9
    nobackup_s = float(nobackup.get("wall_s", 0.0)) or 1e-9
    row = {"spec_mb": round(total_mb, 2), "spec_parity": True,
           "spec_backup_mbps": round(total_mb / backup_s, 2),
           "spec_nobackup_mbps": round(total_mb / nobackup_s, 2),
           "spec_backup_fired": int(backup.get("backup_dispatches", 0)),
           "spec_duplicate_commits": dup,
           # Bool twin of duplicate_commits for the bench_diff gate: a
           # healthy old value of 0 reads "unknown" under the numeric
           # lower-better rule (the plan_zero_copy precedent), so the
           # bool carries the first-commit-wins regression gate.
           "spec_exactly_once": dup == 0,
           "spec_resumed": int(backup.get("resumed_attempts", 0)),
           "spec_commit_losses": int(backup.get("commit_losses", 0))}
    if resplit is not None and not int(resplit.get("resplits", 0)):
        resplit_skip = ("no re-split fired (straggler finished or "
                        "remainder under the split floor — backup "
                        "fallback ran)")
    if resplit_skip is not None:
        row["spec_resplit_skipped"] = resplit_skip
    else:
        resplit_s = float(resplit.get("wall_s", 0.0)) or 1e-9
        row.update({
            "spec_resplit_mbps": round(total_mb / resplit_s, 2),
            "spec_resplits": int(resplit["resplits"]),
            "spec_subshards": int(resplit.get("subshard_dispatches",
                                              0))})
    log(f"spec row: {total_mb:.1f} MB, slow shard injected — backup "
        f"{row['spec_backup_mbps']} MB/s ({backup_s:.2f}s, "
        f"{row['spec_backup_fired']} backups, {row['spec_resumed']} "
        f"resumed) vs no-backup {row['spec_nobackup_mbps']} MB/s "
        f"({nobackup_s:.2f}s); duplicate commits {dup}")
    if "spec_resplit_mbps" in row:
        log(f"spec row resplit arm: {row['spec_resplit_mbps']} MB/s "
            f"({resplit_s:.2f}s, {row['spec_resplits']} resplits -> "
            f"{row['spec_subshards']} sub-shards)")
    else:
        log(f"spec row resplit arm skipped: {row['spec_resplit_skipped']}")
    return row


def run_net_row() -> dict:
    """The network-data-plane A/B (ISSUE 17 satellite): the SAME
    multi-file wordcount job run twice in fresh ``mrrun`` fleets —
    shuffle over localhost TCP with per-worker PRIVATE workdirs
    (``--net``: ``net_shuffle_mbps``) vs the shared-directory data
    plane (``net_fs_mbps``).  Both arms are parity-gated against the
    sequential oracle by ``mrrun --check`` (exit 2 = mismatch, row
    suppressed).  The net arm also reports ``net_ratio`` (raw/wire —
    the PR-13 line codec's leverage on the shuffle link, gated >= 1.5
    by the acceptance bar) and ``locality_hits`` (reduce tasks placed
    on the host already holding their biggest input share).
    Chip-independent (host-backend CPU workers), measured keys XOR
    ``net_skipped``.  ``DSI_BENCH_NET_MB`` (default 4; 0 disables)
    sizes it."""
    mb = env_float("DSI_BENCH_NET_MB", 4.0)
    if mb <= 0:
        return {"net_skipped": "disabled (DSI_BENCH_NET_MB=0)"}
    budget = env_float("DSI_BENCH_NET_TIMEOUT", 300.0)
    import shutil

    ndir = os.path.join(WORKDIR, "net-row")
    shutil.rmtree(ndir, ignore_errors=True)
    os.makedirs(ndir)
    # Several input files: multiple map producers spread across the
    # workers, so the net arm's shuffle really crosses the wire (one
    # file would let locality placement make every fetch local).
    n_files = 4
    paths, total = [], 0
    for fi in range(n_files):
        path = os.path.join(ndir, f"corpus-{fi}.txt")
        with open(path, "w") as f:
            i = 0
            written = 0
            while written < mb * 1e6 / n_files:
                line = (" ".join(
                    "net" + chr(ord("a") + (fi + i + j) % 23) * 2
                    for j in range(9)) + "\n")
                f.write(line)
                written += len(line)
                i += 1
        total += os.path.getsize(path)
        paths.append(path)
    total_mb = total / 1e6
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1-device CPU workers
    # mrrun's children run with cwd=workdir: keep the package importable
    # there even when it is not installed (the test-sandbox case).
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))

    def one(mode: str) -> tuple:
        wd = os.path.join(ndir, mode)
        os.makedirs(wd, exist_ok=True)
        sj = os.path.join(ndir, f"{mode}.stats.json")
        e = dict(env)
        e["DSI_MR_SOCKET"] = os.path.join(ndir, f"{mode}.sock")
        cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
               "--workers", "2", "--nreduce", "4", "--workdir", wd,
               "--check", "--stats-json", sj]
        if mode == "net":
            cmd.append("--net")
        cmd += ["wc"] + paths
        t0 = time.perf_counter()
        r = subprocess.run(cmd, env=e,
                           cwd=os.path.dirname(os.path.abspath(__file__)),
                           capture_output=True, text=True,
                           timeout=budget)
        dt = time.perf_counter() - t0
        if r.returncode == 2:
            raise RuntimeError(f"{mode} arm parity mismatch")
        if r.returncode != 0:
            raise RuntimeError(f"{mode} mrrun rc={r.returncode}: "
                               f"{r.stderr[-300:]}")
        stats = {}
        if os.path.exists(sj):
            with open(sj, encoding="utf-8") as f:
                stats = json.load(f)
        return dt, stats

    try:
        net_s, net = one("net")
        fs_s, _fs = one("fs")
    except Exception as e:
        return {"net_skipped": f"net row failed: "
                               f"{type(e).__name__}: {e}"}
    row = {"net_mb": round(total_mb, 2), "net_parity": True,
           "net_shuffle_mbps": round(total_mb / (net_s or 1e-9), 2),
           "net_fs_mbps": round(total_mb / (fs_s or 1e-9), 2),
           "net_ratio": float(net.get("net_ratio", 0.0)),
           "net_fetches": int(net.get("net_fetches", 0)),
           "net_local_reads": int(net.get("net_local_reads", 0)),
           "locality_hits": int(net.get("locality_hits", 0)),
           "net_refetches": int(net.get("net_refetches", 0))}
    log(f"net row: {total_mb:.1f} MB over {n_files} files — shuffle/TCP "
        f"{row['net_shuffle_mbps']} MB/s ({net_s:.2f}s, "
        f"{row['net_fetches']} fetches + {row['net_local_reads']} "
        f"local, codec ratio {row['net_ratio']}, "
        f"{row['locality_hits']} locality hits) vs shared-dir "
        f"{row['net_fs_mbps']} MB/s ({fs_s:.2f}s)")
    return row


def run_net_pipeline_row() -> dict:
    """The overlapped-shuffle A/B (ISSUE 18): the SAME reduce-side
    fetch plan — P partitions spread across S in-process partition
    servers — pulled twice, serial (window 1: one blocking fetch at a
    time, the pre-pipeline path) vs pipelined (``FetchPipeline`` at
    the default window).  Localhost TCP is far too fast for prefetch
    to show, so every server runs with an injected per-chunk serve
    latency (``DSI_NET_CHUNK_SLEEP_S`` — the ``chunk_hook`` sleep,
    identical on BOTH arms); the pipelined arm hides it by keeping
    several streams in flight, which is exactly the claim
    ``net_pipelined_mbps``/``net_serial_mbps`` measures.  Parity-gated:
    both arms must yield byte-identical payload sequences (producer
    order) or the row is suppressed.  ``net_overlap_s`` (dialer wire
    time hidden behind the consumer) comes from the pipelined arm's
    stats.  Chip-independent, measured keys XOR
    ``net_pipeline_skipped``.  ``DSI_BENCH_NET_PIPE_MB`` (default 2;
    0 disables) sizes it; ``DSI_BENCH_NET_PIPE_SLEEP`` (default 0.03)
    is the injected per-chunk latency."""
    mb = env_float("DSI_BENCH_NET_PIPE_MB", 2.0)
    if mb <= 0:
        return {"net_pipeline_skipped":
                "disabled (DSI_BENCH_NET_PIPE_MB=0)"}
    sleep_s = env_float("DSI_BENCH_NET_PIPE_SLEEP", 0.03)
    import shutil

    from dsi_tpu.net.fetch import (DEFAULT_FETCH_WINDOW, FetchPipeline,
                                   fetch_partition)
    from dsi_tpu.net.partsrv import PartitionServer

    ndir = os.path.join(WORKDIR, "net-pipe-row")
    shutil.rmtree(ndir, ignore_errors=True)
    n_srv, n_part = 4, 8
    part_bytes = int(mb * 1e6 / n_part)
    servers = []
    old = os.environ.get("DSI_NET_CHUNK_SLEEP_S")
    os.environ["DSI_NET_CHUNK_SLEEP_S"] = str(sleep_s)
    try:
        items = []
        for p in range(n_part):
            if p < n_srv:
                srv = PartitionServer(os.path.join(ndir, f"srv-{p}"))
                srv.start()
                servers.append(srv)
            srv = servers[p % n_srv]
            name = f"mr-{p}-0"
            line = f"pipe{p:02d} " * 16 + "\n"
            srv.put(name, (line * (part_bytes // len(line) + 1))
                    [:part_bytes].encode())
            items.append((p, srv.address, name))
        total_mb = n_part * part_bytes / 1e6

        t0 = time.perf_counter()
        serial = [fetch_partition(a, n) for _, a, n in items]
        serial_s = time.perf_counter() - t0

        io_b: dict = {}
        t0 = time.perf_counter()
        piped = [raw for _, raw in
                 FetchPipeline(items, window=DEFAULT_FETCH_WINDOW,
                               stats=io_b)]
        piped_s = time.perf_counter() - t0
    except Exception as e:
        return {"net_pipeline_skipped": f"net pipeline row failed: "
                                        f"{type(e).__name__}: {e}"}
    finally:
        for srv in servers:
            srv.close()
        if old is None:
            os.environ.pop("DSI_NET_CHUNK_SLEEP_S", None)
        else:
            os.environ["DSI_NET_CHUNK_SLEEP_S"] = old
        shutil.rmtree(ndir, ignore_errors=True)
    if serial != piped:
        return {"net_pipeline_skipped":
                "parity mismatch: pipelined payloads != serial"}
    row = {"net_pipe_mb": round(total_mb, 2),
           "net_pipeline_parity": True,
           "net_serial_mbps": round(total_mb / (serial_s or 1e-9), 2),
           "net_pipelined_mbps": round(total_mb / (piped_s or 1e-9), 2),
           "net_overlap_s": float(io_b.get("net_overlap_s", 0.0)),
           "net_fetch_wait_s": float(io_b.get("net_fetch_wait_s", 0.0))}
    log(f"net pipeline row: {total_mb:.1f} MB over {n_part} partitions "
        f"x {n_srv} servers ({sleep_s}s/chunk injected) — pipelined "
        f"(window {DEFAULT_FETCH_WINDOW}) {row['net_pipelined_mbps']} "
        f"MB/s ({piped_s:.2f}s, overlap {row['net_overlap_s']}s) vs "
        f"serial {row['net_serial_mbps']} MB/s ({serial_s:.2f}s)")
    return row


def run_replica_row() -> dict:
    """The replicated-control-plane A/B (ISSUE 20): the same shard job
    run in fresh subprocess fleets three ways — a single in-process
    coordinator (``replica_single_mbps``), a 3-replica Raft group with
    nothing failing (``replica_group_mbps`` — its wall over the single
    arm's is ``replica_overhead_pct``, the price of majority-committing
    every journal record), and the same group with the LEADER kill -9'd
    mid-job.  The chaos arm reports ``replica_failover_s`` (kill
    instant → the first coordinator answer served by the NEW leader —
    THE tentpole number, gates lower-better in bench_diff) and the term
    handoff.  ``replica_exactly_once`` is the bool gate: zero duplicate
    commits in every arm's stats AND no shard with two commit records
    in ANY replica's journal across both group arms.  Every arm is
    parity-gated against the sequential host oracle by ``shardrun
    --check`` (exit 2 = mismatch).  Chip-independent (1-device CPU
    workers), measured keys XOR ``replica_skipped``.
    ``DSI_BENCH_REPLICA_MB`` (default 4; 0 disables) sizes it."""
    mb = env_float("DSI_BENCH_REPLICA_MB", 4.0)
    if mb <= 0:
        return {"replica_skipped": "disabled (DSI_BENCH_REPLICA_MB=0)"}
    budget = env_float("DSI_BENCH_REPLICA_TIMEOUT", 300.0)
    import shutil

    rdir = os.path.join(WORKDIR, "replica-row")
    shutil.rmtree(rdir, ignore_errors=True)
    os.makedirs(rdir)
    corpus_path = os.path.join(rdir, "corpus.txt")
    with open(corpus_path, "w") as f:
        i = 0
        written = 0
        target = mb * 1e6
        while written < target:
            line = (" ".join(
                "rep" + chr(ord("a") + (i + j) % 19) * 2
                for j in range(9)) + "\n")
            f.write(line)
            written += len(line)
            i += 1
    total_mb = os.path.getsize(corpus_path) / 1e6
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1-device CPU workers

    def one(mode: str) -> dict:
        wd = os.path.join(rdir, mode)
        sj = os.path.join(rdir, f"{mode}.stats.json")
        e = dict(env)
        cmd = [sys.executable, "-m", "dsi_tpu.cli.shardrun",
               "--workers", "2", "--shards", "4",
               "--workdir", wd, "--chunk-bytes", str(1 << 16),
               "--progress-s", "0.1", "--shard-timeout", "120",
               "--check", "--stats-json", sj, corpus_path]
        if mode == "single":
            e["DSI_MR_SOCKET"] = os.path.join(rdir, "single.sock")
        else:
            cmd[-1:-1] = ["--replicas", "3"]
            if mode == "failover":
                cmd[-1:-1] = ["--kill-leader-after", "1.0"]
        r = subprocess.run(cmd, env=e,
                           cwd=os.path.dirname(os.path.abspath(__file__)),
                           capture_output=True, text=True,
                           timeout=budget)
        if r.returncode == 2:
            raise RuntimeError(f"{mode} arm parity mismatch")
        if r.returncode != 0:
            raise RuntimeError(f"{mode} shardrun rc={r.returncode}: "
                               f"{r.stderr[-300:]}")
        with open(sj, encoding="utf-8") as f:
            return json.load(f)

    def journal_dups(mode: str) -> int:
        """Shard records appearing MORE than once in any one replica
        journal — the cross-term first-commit-wins audit."""
        import glob

        dups = 0
        for path in sorted(glob.glob(
                os.path.join(rdir, mode, "replica-*.journal"))):
            per: dict = {}
            with open(path, encoding="utf-8") as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("kind") == "shard":
                        per[rec["task"]] = per.get(rec["task"], 0) + 1
            dups += sum(n - 1 for n in per.values() if n > 1)
        return dups

    try:
        single = one("single")
        group = one("group")
        failover = one("failover")
    except Exception as e:
        return {"replica_skipped": f"replica row failed: "
                                   f"{type(e).__name__}: {e}"}
    dup = (int(single.get("duplicate_commits", 0))
           + int(group.get("duplicate_commits", 0))
           + int(failover.get("duplicate_commits", 0))
           + journal_dups("group") + journal_dups("failover"))
    single_s = float(single.get("wall_s", 0.0)) or 1e-9
    group_s = float(group.get("wall_s", 0.0)) or 1e-9
    failover_s_wall = float(failover.get("wall_s", 0.0)) or 1e-9
    row = {"replica_mb": round(total_mb, 2), "replica_parity": True,
           "replica_single_mbps": round(total_mb / single_s, 2),
           "replica_group_mbps": round(total_mb / group_s, 2),
           "replica_chaos_mbps": round(total_mb / failover_s_wall, 2),
           "replica_overhead_pct": round(
               (group_s - single_s) / single_s * 100.0, 1),
           "replica_failover_s": float(
               failover.get("replica_failover_s", 0.0)),
           "replica_terms": [int(failover.get("replica_old_term", 0)),
                             int(failover.get("replica_new_term", 0))],
           "replica_duplicate_commits": dup,
           # Bool twin for the bench_diff gate (the spec_exactly_once
           # precedent): a healthy old value of 0 reads "unknown" under
           # the numeric lower-better rule, so the bool carries the
           # first-commit-wins-across-terms regression gate.
           "replica_exactly_once": dup == 0}
    log(f"replica row: {total_mb:.1f} MB — single {row['replica_single_mbps']} "
        f"MB/s ({single_s:.2f}s) vs 3-replica group "
        f"{row['replica_group_mbps']} MB/s ({group_s:.2f}s, "
        f"+{row['replica_overhead_pct']}%); leader kill -9 arm "
        f"{row['replica_chaos_mbps']} MB/s ({failover_s_wall:.2f}s), "
        f"failover {row['replica_failover_s']}s (term "
        f"{row['replica_terms'][0]} -> {row['replica_terms'][1]}), "
        f"duplicate commits {dup}")
    return row


def run_native_oracle_row(files, oracle_out, total_mb, native_ok,
                          fw_oracle_mbps) -> dict:
    """Sequential run of the SAME C++ task bodies the native-backend
    workers execute (``dsi_tpu/native`` wcjob: map each file, write the
    mr-X-Y intermediates, reduce each partition) with no framework at
    all — the compiled-language twin of the python oracle.  Parity vs
    the python oracle's output is the gate; a declined native body (the
    library degrades on non-ASCII etc.) skips the row honestly."""
    if not native_ok:
        return {"native_oracle_skipped": "native library unavailable"}
    import shutil

    from dsi_tpu import native
    from dsi_tpu.obs import span

    ndir = os.path.join(os.path.dirname(oracle_out), "native-seq")
    shutil.rmtree(ndir, ignore_errors=True)
    os.makedirs(ndir)
    out_blobs = []
    with span("task", stats={}, phase="bench.native_oracle") as pt:
        for m, p in enumerate(files):
            blobs = native.wc_map_file(p, N_REDUCE)
            if blobs is None:
                return {"native_oracle_skipped":
                        "native map body declined this split"}
            for r, blob in enumerate(blobs):
                with open(os.path.join(ndir, f"mr-{m}-{r}"), "wb") as f:
                    f.write(blob)
        for r in range(N_REDUCE):
            blob = native.wc_reduce(ndir, r, len(files))
            if blob is None:
                return {"native_oracle_skipped":
                        "native reduce body declined"}
            out_blobs.append(blob)
    dt = pt.elapsed_s
    got = sorted(l for b in out_blobs
                 for l in b.decode("utf-8").splitlines() if l.strip())
    with open(oracle_out, encoding="utf-8") as f:
        want = sorted(l.rstrip("\n") for l in f if l.strip())
    if got != want:
        return {"native_oracle_skipped":
                "parity mismatch vs python oracle (rate suppressed)"}
    mbps = total_mb / dt
    log(f"native-sequential oracle: {total_mb:.1f} MB in {dt:.2f}s = "
        f"{mbps:.2f} MB/s ({mbps / fw_oracle_mbps:.2f}x the python "
        "oracle)")
    return {"native_oracle_mbps": round(mbps, 2),
            "native_vs_python": round(mbps / fw_oracle_mbps, 2)}


def _run_framework_body(coord, workers, reap, env, fw_dir, oracle_out,
                        total_mb, n_workers, native_ok, budget,
                        fw_oracle_mbps) -> dict:
    """The measured portion of :func:`run_framework_row`, factored out so
    the caller's try/finally reaps children on ANY exit.  ``workers`` is
    the caller's (initially empty) list and is mutated in place — the
    finally must see the same list object the spawns land in."""
    deadline = time.monotonic() + 15.0
    while not os.path.exists(env["DSI_MR_SOCKET"]):
        if coord.poll() is not None or time.monotonic() > deadline:
            return reap("coordinator did not open its socket")
        time.sleep(0.05)

    # Workers run the combiner app on the native (C++ task-body) backend
    # by default — the host data plane at compiled speed, the moral
    # equivalent of the reference's compiled-Go workers; output is
    # byte-identical to wc's (parity gate below).  Chip-independent
    # either way.
    fw_backend = os.environ.get("DSI_BENCH_FRAMEWORK_BACKEND", "native")
    if fw_backend == "native" and not native_ok:
        fw_backend = "host"  # label what actually runs
    # The accelerated backends need the combiner app (it declares the
    # native/tpu task bodies); plain host runs the reference-semantics
    # wc.  Either way the final output is byte-identical (parity gate).
    fw_app = "wc" if fw_backend == "host" else "tpu_wc"
    t0 = time.perf_counter()
    workers[:] = [
        subprocess.Popen([sys.executable, "-m", "dsi_tpu.cli.mrworker",
                          "--backend", fw_backend, fw_app],
                         cwd=fw_dir, env=env, stdout=sys.stderr,
                         stderr=sys.stderr)
        for _ in range(n_workers)]
    deadline = time.monotonic() + budget
    for p in workers:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            return reap(f"worker still running after {budget:.0f}s")
    dt = time.perf_counter() - t0
    if any(p.returncode != 0 for p in workers):
        return reap("worker exited nonzero")
    try:
        coord.wait(timeout=30.0)
    except subprocess.TimeoutExpired:
        return reap("coordinator did not exit after job completion")

    fw_lines = []
    for r in range(N_REDUCE):
        try:
            with open(os.path.join(fw_dir, f"mr-out-{r}"),
                      encoding="utf-8") as f:
                fw_lines.extend(l for l in f if l.strip())
        except OSError:
            return reap(f"missing output partition mr-out-{r}")
    fw_lines.sort()
    with open(oracle_out, encoding="utf-8") as f:
        oracle_lines = sorted(l for l in f if l.strip())
    parity = fw_lines == oracle_lines
    fw_mbps = total_mb / dt
    log(f"framework row: {total_mb:.1f} MB, {n_workers} workers on "
        f"{len(os.sched_getaffinity(0))} core(s): {dt:.2f}s = "
        f"{fw_mbps:.2f} MB/s vs oracle {fw_oracle_mbps:.2f} MB/s "
        f"(parity={parity})")
    if not parity:
        return {"framework_skipped": "parity mismatch (throughput "
                                     "suppressed)",
                "framework_parity": False}
    return {"framework_mbps": round(fw_mbps, 2),
            "framework_s": round(dt, 2),
            "framework_mb": round(total_mb, 1),
            "framework_workers": n_workers,
            "framework_cores": len(os.sched_getaffinity(0)),
            "framework_backend": fw_backend,
            "framework_oracle_mbps": round(fw_oracle_mbps, 2),
            "framework_vs_oracle": round(fw_mbps / fw_oracle_mbps, 2),
            "framework_parity": True}


def global_budget_s() -> float:
    """The TPU half's wall budget (DSI_BENCH_DEADLINE_S)."""
    return env_float("DSI_BENCH_DEADLINE_S", 2100.0)


def run_tpu_watchdogged(deadline: float) -> dict:
    """Run the TPU half in a subprocess with per-attempt timeouts, bounded
    by the caller's monotonic ``deadline``; return its result dict or
    {"error": ...}."""
    try:
        timeouts = [
            float(x) for x in os.environ.get(
                "DSI_BENCH_TPU_TIMEOUTS", "1200,420,240").split(",")]
    except ValueError:
        log("ignoring malformed DSI_BENCH_TPU_TIMEOUTS")
        timeouts = [1200.0, 420.0, 240.0]
    result_path = os.path.join(WORKDIR, "tpu-result.json")
    last_err = "no attempt ran"
    for attempt, budget in enumerate(timeouts, 1):
        remaining = deadline - time.monotonic()
        if remaining < 60:
            last_err += f"; global deadline reached before attempt {attempt}"
            break
        budget = min(budget, remaining)
        for suffix in ("", ".init"):
            try:
                os.remove(result_path + suffix)
            except OSError:
                pass
        log(f"tpu attempt {attempt}/{len(timeouts)} (timeout {budget:.0f}s)")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--tpu-child",
             result_path], stdout=sys.stderr)
        timed_out = False
        # Fail fast on a wedged device claim: the child drops a marker file
        # the moment jax.devices() returns; no marker within the init budget
        # means the claim is hung and the whole attempt budget would be
        # wasted inside device init.
        init_budget = env_float("DSI_BENCH_INIT_TIMEOUT", 180.0)
        init_deadline = time.monotonic() + min(init_budget, budget)
        attempt_deadline = time.monotonic() + budget
        rc = None
        while True:
            try:
                rc = proc.wait(timeout=2.0)
                break
            except subprocess.TimeoutExpired:
                pass
            now = time.monotonic()
            if now >= attempt_deadline or (
                    not os.path.exists(result_path + ".init")
                    and now >= init_deadline):
                if os.path.exists(result_path + ".init"):
                    # Post-init child: SIGTERM + grace so its handler can
                    # unwind the PJRT client and release the device claim
                    # (a SIGKILL mid-claim can wedge the device for later
                    # processes).
                    proc.terminate()
                    try:
                        rc = proc.wait(timeout=20.0)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        rc = proc.wait()
                else:
                    # Init-hang: the child is blocked inside the
                    # jax.devices() C call, where CPython cannot run the
                    # SIGTERM handler anyway — waiting 20 s would just burn
                    # deadline budget before the same SIGKILL.  A polling
                    # pre-init client holds no claim, so the kill is safe.
                    proc.kill()
                    rc = proc.wait()
                timed_out = True
                if not os.path.exists(result_path + ".init"):
                    log(f"attempt {attempt}: device init hung "
                        f">{min(init_budget, budget):.0f}s (wedged claim?)")
                break
        if os.path.exists(result_path):
            # Even after a timeout: the child writes its result atomically as
            # its LAST act, so a child that measured successfully but hung in
            # interpreter/JAX teardown still produced a valid verdict.
            with open(result_path) as f:
                res = json.load(f)
            if "error" not in res:
                return res
            if res.get("permanent"):
                # Deterministic failure (kernel fallback on this corpus):
                # retrying cannot change the outcome.
                return res
            last_err = f"attempt {attempt}: {res['error']}"
        elif timed_out:
            if not os.path.exists(result_path + ".init"):
                last_err = (f"attempt {attempt}: device init never completed "
                            "(wedged claim?)")
            else:
                last_err = f"attempt {attempt} timed out after {budget:.0f}s"
        else:
            last_err = f"attempt {attempt} exited rc={rc} with no result"
        log(last_err)
        # Cool down only when another attempt can actually run afterwards.
        if (attempt < len(timeouts)
                and deadline - time.monotonic() >= 60 + 15):
            time.sleep(15.0)
    return {"error": last_err}


def main() -> None:
    os.makedirs(WORKDIR, exist_ok=True)
    from dsi_tpu.utils.corpus import ensure_corpus

    files = ensure_corpus(WORKDIR, n_files=N_FILES, file_size=FILE_SIZE)
    total_mb = sum(os.path.getsize(p) for p in files) / 1e6
    log(f"corpus: {len(files)} files, {total_mb:.1f} MB")
    prov = run_provenance()
    log(f"provenance: {prov}")

    oracle_s, oracle_mbps = run_oracle(files)
    log(f"oracle (mrsequential semantics): {oracle_s:.2f}s = "
        f"{oracle_mbps:.2f} MB/s")

    budget_s = global_budget_s()
    deadline = time.monotonic() + budget_s
    res = run_tpu_watchdogged(deadline)
    if "error" in res:
        # No chip (or the device half failed): an error verdict and a
        # non-zero exit, at once.  Nothing is re-measured on another
        # backend and no rate is printed — a number from a machine
        # without the chip says nothing about this system's speed.
        print(json.dumps({"metric": "wc_tpu_throughput", "value": 0,
                          "unit": "MB/s", "vs_baseline": 0,
                          "error": res["error"], "provenance": prov}))
        sys.exit(1)
    # The distributed N-worker row runs host workers beside the device
    # verdict.  The budget<60 escape hatch stays fast unless the row is
    # explicitly requested.
    fw = {}
    if budget_s >= 60 or "DSI_BENCH_FRAMEWORK_MB" in os.environ:
        try:
            fw = run_framework_row(oracle_mbps)
        except Exception as e:  # never trade the verdict for the row
            fw = {"framework_skipped":
                  f"framework row failed: {type(e).__name__}: {e}"}
    # The mesh-sharded A/B row is chip-independent too (virtual 8-device
    # CPU mesh in subprocesses) and rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_MESH_SHARDS" in os.environ:
        try:
            fw.update(run_mesh_row())
        except Exception as e:
            fw["mesh_skipped"] = (f"mesh row failed: "
                                  f"{type(e).__name__}: {e}")
    else:
        # Measured-XOR-skipped holds on the fast path too.
        fw["mesh_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The serving-daemon A/B row: chip-independent (mrserve + one-shot
    # CLI subprocesses on the virtual CPU mesh), rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_SERVE_JOBS" in os.environ:
        try:
            fw.update(run_serve_row())
        except Exception as e:
            fw["serve_skipped"] = (f"serve row failed: "
                                   f"{type(e).__name__}: {e}")
    else:
        fw["serve_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The serving-QoS packed-grep latency A/B row (ISSUE 19):
    # chip-independent (two mrserve subprocesses on the virtual CPU
    # mesh), rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_SERVE_LAT_TENANTS" in os.environ:
        try:
            fw.update(run_serve_latency_row())
        except Exception as e:
            fw["serve_lat_skipped"] = (f"serve latency row failed: "
                                       f"{type(e).__name__}: {e}")
    else:
        fw["serve_lat_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The plan-layer chained-vs-staged A/B row (ISSUE 14):
    # chip-independent (planrun subprocesses on 1-device CPU).
    if budget_s >= 60 or "DSI_BENCH_PLAN_MB" in os.environ:
        try:
            fw.update(run_plan_row())
        except Exception as e:
            fw["plan_skipped"] = (f"plan row failed: "
                                  f"{type(e).__name__}: {e}")
    else:
        fw["plan_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The speculative-execution backup-dispatch A/B row (ISSUE 15):
    # chip-independent (shardrun subprocess fleets on 1-device CPU),
    # rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_SPEC_MB" in os.environ:
        try:
            fw.update(run_spec_row())
        except Exception as e:
            fw["spec_skipped"] = (f"spec row failed: "
                                  f"{type(e).__name__}: {e}")
    else:
        fw["spec_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The network-data-plane shuffle-over-TCP A/B row (ISSUE 17):
    # chip-independent (mrrun subprocess fleets on 1-device CPU),
    # rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_NET_MB" in os.environ:
        try:
            fw.update(run_net_row())
        except Exception as e:
            fw["net_skipped"] = (f"net row failed: "
                                 f"{type(e).__name__}: {e}")
    else:
        fw["net_skipped"] = f"budget {budget_s:.0f}s < 60s"
    # The overlapped-shuffle pipelined-vs-serial fetch A/B row
    # (ISSUE 18): chip-independent (in-process partition servers with
    # injected serve latency), rides the verdict.
    if budget_s >= 30 or "DSI_BENCH_NET_PIPE_MB" in os.environ:
        try:
            fw.update(run_net_pipeline_row())
        except Exception as e:
            fw["net_pipeline_skipped"] = (f"net pipeline row failed: "
                                          f"{type(e).__name__}: {e}")
    else:
        fw["net_pipeline_skipped"] = f"budget {budget_s:.0f}s < 30s"
    # The replicated-control-plane A/B row (ISSUE 20): chip-independent
    # (shardrun subprocess fleets on 1-device CPU, replicad coordinator
    # groups), rides the verdict.
    if budget_s >= 60 or "DSI_BENCH_REPLICA_MB" in os.environ:
        try:
            fw.update(run_replica_row())
        except Exception as e:
            fw["replica_skipped"] = (f"replica row failed: "
                                     f"{type(e).__name__}: {e}")
    else:
        fw["replica_skipped"] = f"budget {budget_s:.0f}s < 60s"
    log(f"tpu path: {res['tpu_s']:.3f}s = {res['tpu_mbps']:.2f} MB/s  "
        f"phases={res['phases']}")
    log(f"parity (sort mr-out-* vs oracle, test-mr.sh:52-53): {res['parity']}")
    if not res["parity"]:
        out = {"metric": "wc_tpu_throughput", "value": 0,
               "unit": "MB/s", "vs_baseline": 0,
               "oracle_mbps": round(oracle_mbps, 2),
               "error": "parity mismatch",
               "platform": res.get("platform", "?")}
        out.update(fw)
        out["provenance"] = prov
        print(json.dumps(out))
        sys.exit(1)

    out = {
        # The device metric's name belongs to the device: a run with the
        # CPU asked for by name (the contract tests) carries its own.
        "metric": ("wc_tpu_throughput" if res["platform"] == "tpu"
                   else f"wc_{res['platform']}_pinned_throughput"),
        "value": res["tpu_mbps"],
        "unit": "MB/s",
        "vs_baseline": round(res["tpu_mbps"] / oracle_mbps, 2),
        "platform": res["platform"],
        "oracle_mbps": round(oracle_mbps, 2),
        "phases": res["phases"],
    }
    # The median alongside the min, and the streaming-path row (or why
    # it was skipped).
    if "median_mbps" in res:
        out["median_mbps"] = res["median_mbps"]
    if "total_mb" in res:
        out["total_mb"] = res["total_mb"]

    for k in res:
        # Honesty rows measured in the child ride the verdict verbatim:
        # the stream row, the kernel-only rep row, the tfidf/grep engine
        # rows, the stream row's checkpoint/resume cost keys, and the
        # wire/ingest A/B keys (each either measured or carrying an
        # explicit skip reason).
        if k.startswith(("stream_", "kernel_", "tfidf_", "grep_",
                         "ckpt_", "resume_", "wire_", "ingest_",
                         "readahead_")):
            out[k] = res[k]
    out.update(fw)
    out["provenance"] = prov
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--tpu-child":
        sys.exit(tpu_child(sys.argv[2]))
    if len(sys.argv) >= 3 and sys.argv[1] == "--mesh-child":
        sys.exit(mesh_child(sys.argv[2]))
    main()
