"""Streaming SPMD word-count entry point — the corpus-bigger-than-memory
scaling path (``parallel/streaming.py``) as a user-facing command.

The reference's scaling lever is nMap = #input files on a shared filesystem
(``mr/coordinator.go:152``); this is that lever re-designed for a device
mesh: files become one bounded-memory block stream, every stream step runs
ONE compiled SPMD map/all_to_all/reduce program, and the output is the same
partitioned ``mr-out-<r>`` file set (``mr/worker.go:126-148`` layout,
``ihash % NReduce`` partitioning).  Falls back to the sequential host path
when the stream needs it (non-ASCII bytes, words > 64 chars) — correctness
never depends on the device kernel.

Usage:
    python -m dsi_tpu.cli.wcstream [--nreduce N] [--chunk-bytes B]
        [--devices D] [--workdir DIR] [--check] [--aot] [--u-cap U]
        [--pipeline-depth D] [--device-accumulate] [--sync-every K]
        [--checkpoint-dir DIR] [--checkpoint-every K] [--resume]
        [--ckpt-async] [--ckpt-delta] [--ingest-readers N]
        [--wire-upload] [--stats] inputfiles...

This is a device entry point: it fails at start unless JAX gives it a TPU
or the CPU was asked for by name (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import os
import sys


def _positive_int(s: str) -> int:
    """argparse type: capacities/sizes must be >= 1 (a 0 capacity could
    never widen in the exactness_retry ladder — cap*4 stays 0 — and a
    negative one breaks kernel shape construction)."""
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def _job(args, pstats: dict):
    """The job between its parsed arguments and its ``--stats`` line:
    ``(exit code, devices)``."""
    from dsi_tpu.obs import span

    with span("start", lane="host", stats=pstats):
        from dsi_tpu.utils.platformpin import require_device

        devices = require_device("wcstream")

        from dsi_tpu.ckpt import CheckpointMismatch
        from dsi_tpu.parallel.shuffle import (default_mesh,
                                              write_partitioned_output)
        from dsi_tpu.parallel.streaming import WordcountStep
        from dsi_tpu.utils.ioread import open_blocks

        mesh = default_mesh(args.devices)
        try:
            # Construction ends with the pipeline armed; close() below
            # drives it (wordcount_streaming is the two in one call).
            step = WordcountStep(
                open_blocks(args.files, readers=args.ingest_readers),
                mesh=mesh, n_reduce=args.nreduce,
                chunk_bytes=args.chunk_bytes, u_cap=args.u_cap,
                aot=args.aot, depth=args.pipeline_depth,
                device_accumulate=args.device_accumulate,
                sync_every=args.sync_every, mesh_shards=args.mesh_shards,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                checkpoint_async=args.ckpt_async,
                checkpoint_delta=args.ckpt_delta, resume=args.resume,
                wire_upload=args.wire_upload,
                pipeline_stats=pstats)
        except CheckpointMismatch as e:
            # A valid checkpoint for a DIFFERENT job (other corpus shape /
            # mesh / mode): resuming would corrupt it, starting fresh
            # would overwrite it — the caller must fix the command or the
            # dir.
            print(f"wcstream: {e}", file=sys.stderr)
            return 1, devices
    acc = step.close()
    if args.resume and not pstats.get("resume_cursor"):
        # Legitimate when the crash predated the first checkpoint, but a
        # typo'd --checkpoint-dir looks identical — say it out loud so a
        # GB-scale from-scratch replay is never a silent surprise.
        print("wcstream: --resume found no usable checkpoint in "
              f"{args.checkpoint_dir}; started from scratch",
              file=sys.stderr)
    if acc is None:
        # Host fallback: the sequential oracle semantics, partitioned
        # output — the ONE shared implementation (serve/pack.py), so the
        # CLI and the serving daemon cannot drift.
        print("wcstream: stream needs the host path; running host word count",
              file=sys.stderr)
        from dsi_tpu.serve.pack import host_wordcount

        acc = host_wordcount(args.files, args.nreduce)
    os.makedirs(args.workdir, exist_ok=True)
    with span("write", lane="host", stats=pstats, keys=len(acc)) as sp:
        paths = write_partitioned_output(acc, args.nreduce, args.workdir,
                                         stats=pstats)
        sp.set(bytes=sum(os.path.getsize(path) for path in paths))
    return 0, devices


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("files", nargs="+")
    p.add_argument("--nreduce", type=_positive_int, default=10)
    p.add_argument("--chunk-bytes", type=_positive_int, default=1 << 20,
                   help="per-device bytes per stream step")
    p.add_argument("--devices", type=int, default=None,
                   help="mesh size (default: all local devices)")
    p.add_argument("--workdir", default=".")
    p.add_argument("--check", action="store_true",
                   help="run the sequential oracle and verify parity "
                        "(sort mr-out-* | grep . vs oracle, test-mr.sh:52-53)")
    p.add_argument("--aot", action="store_true",
                   help="compile the stream's programs explicitly at "
                        "full-capacity shapes (one deterministic shape "
                        "per rung, so a warm pass covers the run)")
    p.add_argument("--u-cap", type=_positive_int, default=1 << 12,
                   help="starting per-device unique capacity (sticky; "
                        "widens on overflow)")
    p.add_argument("--pipeline-depth", type=_positive_int, default=None,
                   help="in-flight stream steps (default: "
                        "DSI_STREAM_PIPELINE_DEPTH or 2; 1 = synchronous)")
    p.add_argument("--device-accumulate", action="store_true",
                   help="fold confirmed steps into the device-resident "
                        "merge table (dsi_tpu/device/) and pull to the "
                        "host only every --sync-every steps — amortizes "
                        "the per-step D2H pull; results are bit-identical")
    p.add_argument("--sync-every", type=_positive_int, default=None,
                   help="folds between host pulls with "
                        "--device-accumulate (default: "
                        "DSI_STREAM_SYNC_EVERY or 8)")
    p.add_argument("--mesh-shards", type=int, default=None,
                   help="mesh-shard the device table across N shards "
                        "(ihash(key) %% N routing inside the fold "
                        "program, per-shard widens, pre-merged sync "
                        "pulls; implies --device-accumulate; default: "
                        "DSI_STREAM_MESH_SHARDS or 0 = off; results "
                        "are bit-identical either way)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable crash-resume checkpoints (dsi_tpu/ckpt): "
                        "durable snapshots of the accumulators + device "
                        "table + input cursor land here; see --resume")
    p.add_argument("--ckpt-async", action="store_true", default=None,
                   dest="ckpt_async",
                   help="overlap checkpoint commits with the pipeline "
                        "(capture at the boundary, durable write in a "
                        "background writer; env DSI_STREAM_CKPT_ASYNC)")
    p.add_argument("--ckpt-delta", action="store_true", default=None,
                   dest="ckpt_delta",
                   help="incremental checkpoints: ship only the step "
                        "payloads appended since the previous save, "
                        "full re-base every DSI_STREAM_CKPT_REBASE "
                        "saves (env DSI_STREAM_CKPT_DELTA)")
    p.add_argument("--checkpoint-every", type=_positive_int, default=None,
                   help="confirmed steps between checkpoints (default: "
                        "DSI_STREAM_CKPT_EVERY or 32)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest valid checkpoint in "
                        "--checkpoint-dir (restores state, seeks the "
                        "input to the confirmed cursor; final output is "
                        "bit-identical to an uninterrupted run)")
    p.add_argument("--ingest-readers", type=int, default=None,
                   dest="ingest_readers",
                   help="parallel mmap'd input readers with readahead "
                        "(utils/ioread.py): N threads fill blocks ahead "
                        "of the batcher so materialize_s overlaps disk; "
                        "cursors/checkpoints stay byte-exact (default: "
                        "DSI_INGEST_READERS or 0 = inline reads)")
    p.add_argument("--wire-upload", action="store_true", default=None,
                   dest="wire_upload",
                   help="compress chunk uploads host-side and decode on "
                        "device as a compiled map prologue "
                        "(ops/wirecodec.py): PCIe moves "
                        "0.63-0.88x the bytes, HBM sees identical "
                        "tensors (env DSI_STREAM_WIRE; results are "
                        "bit-identical either way)")
    p.add_argument("--stats", action="store_true",
                   help="print to stderr the device, the pipeline_stats "
                        "dict (phase walls + fold/sync/widen counters) "
                        "and compile_stats (backend-compile seconds per "
                        "program, compile-cache hits and misses)")
    p.add_argument("--trace-dir", default=None,
                   help="write this run's unified trace (dsi_tpu/obs) "
                        "there: trace.json (Perfetto-loadable, one lane "
                        "per pipeline stage) + trace.jsonl (event log); "
                        "render with scripts/tracecat.py")
    p.add_argument("--statusz-port", type=int, default=None,
                   help="serve live telemetry on 127.0.0.1:PORT — "
                        "/statusz (plain text: current step, stage "
                        "p50/p99, in-flight window) + /metrics "
                        "(Prometheus); 0 picks a free port (printed to "
                        "stderr); default off (env DSI_STATUSZ_PORT) = "
                        "zero threads; also arms the stall watchdog "
                        "and, with --trace-dir, a bounded live.jsonl "
                        "sample ring there")
    args = p.parse_args(argv)

    if args.resume and not args.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")

    if args.trace_dir:
        from dsi_tpu.obs import configure_tracing

        configure_tracing(trace_dir=args.trace_dir)

    # Live telemetry BEFORE the jax import below: /statusz answers
    # during device init.
    if args.statusz_port is not None or os.environ.get("DSI_STATUSZ_PORT"):
        from dsi_tpu.obs.live import start_from_args

        start_from_args(args.statusz_port, live_dir=args.trace_dir)

    from dsi_tpu.obs import span
    from dsi_tpu.obs.registry import job_children_s

    # The root of the main thread's account: its direct children (the
    # registry's JOB_CHILDREN) cover it, so job_s less job_children_s is
    # what no span holds.
    pstats: dict = {}
    with span("job", lane="host", stats=pstats):
        rc, devices = _job(args, pstats)
    if rc:
        return rc
    for key in ("job_s", "start_s", "write_s", "write_format_s",
                "write_commit_s"):
        pstats[key] = round(pstats[key], 4)
    pstats["job_children_s"] = round(job_children_s(pstats), 4)
    # After the write, so that the line holds the job's serial tail too
    # (finalize_s, write_s) and the trace its last span.
    if args.stats:
        from dsi_tpu.utils import compilecache

        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices)}
        print(f"wcstream: device={dev}", file=sys.stderr)
        print(f"wcstream: pipeline_stats={pstats}", file=sys.stderr)
        print(f"wcstream: compile_stats={compilecache.summary()}",
              file=sys.stderr)
    if args.trace_dir:
        from dsi_tpu.obs import flush_tracing_report

        flush_tracing_report(args.trace_dir, "wcstream")

    if args.check:
        from dsi_tpu.apps import wc
        from dsi_tpu.mr.sequential import run_sequential

        oracle_out = os.path.join(args.workdir, "mr-correct.txt")
        run_sequential(wc.Map, wc.Reduce, args.files, oracle_out)
        got: list = []
        for r in range(args.nreduce):
            with open(os.path.join(args.workdir, f"mr-out-{r}"),
                      encoding="utf-8") as f:
                got.extend(l for l in f if l.strip())
        with open(oracle_out, encoding="utf-8") as f:
            want = sorted(l for l in f if l.strip())
        if sorted(got) != want:
            print("wcstream: PARITY FAILURE vs sequential oracle",
                  file=sys.stderr)
            return 2
        print("wcstream: parity OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
