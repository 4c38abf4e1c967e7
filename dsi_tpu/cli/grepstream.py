"""Streaming grep entry point — the grep engine on the shared pipeline
core (``parallel/grepstream.py``) as a user-facing command, mirroring
``wcstream``'s knobs.

Files become one bounded-memory block stream cut at newline boundaries;
every stream step runs ONE compiled literal-match program (the
``ops/grepk.py`` shifted-compare idiom), the same one whatever the
lines look like, and the result is the whole-stream
match statistics: total/matched lines, occurrences, the per-line
match-count histogram, and the exact top-k lines by occurrence count.
``--workdir DIR`` commits them as ``DIR/mr-out-0`` (temp file + rename),
one self-describing record per line: ``lines <n>``, ``matched <n>``,
``occurrences <n>``, ``hist <bucket> <n>`` per histogram bucket,
``top <rank> <line_no> <occurrences>`` per winner (rank 0 first).
``--device-accumulate`` keeps the histogram and the top-k candidate
table ON DEVICE (``dsi_tpu/device/topk.py``), pulling every
``--sync-every`` steps instead of every step.

Falls back to the host oracle scan when the engine declines (non-literal
pattern, or a line wider than ``--chunk-bytes``) — correctness never
depends on the device kernel.

Usage:
    python -m dsi_tpu.cli.grepstream --pattern PAT [--chunk-bytes B]
        [--devices D] [--pipeline-depth D] [--device-accumulate]
        [--sync-every K] [--checkpoint-dir DIR] [--checkpoint-every K]
        [--ckpt-async] [--ckpt-delta]
        [--resume] [--topk K] [--workdir DIR] [--aot] [--stats] [--check]
        inputfiles...
"""

from __future__ import annotations

import argparse
import os
import sys


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def result_records(res) -> list:
    """A job's committed output, one record per line of ``mr-out-0``:
    every record names what it holds, so sorted lines lose nothing."""
    return ([f"lines {res.lines}", f"matched {res.matched}",
             f"occurrences {res.occurrences}"]
            + [f"hist {b} {n}" for b, n in enumerate(res.hist)]
            + [f"top {rank} {line_no} {occ}"
               for rank, (line_no, occ) in enumerate(res.topk)])


def _job(args, pattern: str, pstats: dict):
    """The job between its parsed arguments and its ``--stats`` line:
    ``(exit code, result, whether the host scan produced it)``."""
    from dsi_tpu.obs import span

    with span("start", lane="host", stats=pstats):
        from dsi_tpu.utils.platformpin import require_device

        require_device("grepstream")

        from dsi_tpu.ckpt import CheckpointMismatch
        from dsi_tpu.parallel.grepstream import GrepStep, grep_host_oracle
        from dsi_tpu.parallel.shuffle import default_mesh
        from dsi_tpu.parallel.streaming import stream_files
        from dsi_tpu.utils.ioread import open_blocks

        mesh = default_mesh(args.devices)
        try:
            # Construction ends with the pipeline armed; close() below
            # drives it (grep_streaming is the two in one call).
            step = GrepStep(
                open_blocks(args.files, readers=args.ingest_readers),
                pattern, mesh=mesh,
                chunk_bytes=args.chunk_bytes, depth=args.pipeline_depth,
                aot=args.aot, device_accumulate=args.device_accumulate,
                sync_every=args.sync_every, mesh_shards=args.mesh_shards,
                topk=args.topk,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                checkpoint_async=args.ckpt_async,
                checkpoint_delta=args.ckpt_delta, resume=args.resume,
                pipeline_stats=pstats)
        except CheckpointMismatch as e:
            # A valid checkpoint for a DIFFERENT job (other
            # pattern/shape): refuse loudly rather than corrupt or
            # overwrite the lineage.
            print(f"grepstream: {e}", file=sys.stderr)
            return 1, None, False
    res = step.close()
    if args.resume and not pstats.get("resume_cursor"):
        # Legitimate when the crash predated the first checkpoint, but a
        # typo'd --checkpoint-dir looks identical — never replay a whole
        # stream silently.
        print("grepstream: --resume found no usable checkpoint in "
              f"{args.checkpoint_dir}; started from scratch",
              file=sys.stderr)
    host_path = res is None
    if host_path:
        try:
            res = grep_host_oracle(stream_files(args.files), pattern,
                                   topk=args.topk)
        except UnicodeEncodeError:
            print("grepstream: pattern is not plain ASCII; use the "
                  "tpu_grep MR app for regex tiers", file=sys.stderr)
            return 1, None, True
        print("grepstream: stream needed the host path; ran the host scan",
              file=sys.stderr)

    if args.workdir:
        from dsi_tpu.utils.atomicio import atomic_write

        os.makedirs(args.workdir, exist_ok=True)
        records = result_records(res)
        with span("write", lane="host", stats=pstats,
                  records=len(records)) as sp:
            path = os.path.join(args.workdir, "mr-out-0")
            with atomic_write(path) as f:
                f.write("".join(r + "\n" for r in records))
            sp.set(bytes=os.path.getsize(path))
    return 0, res, host_path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("files", nargs="+")
    p.add_argument("--pattern", default=None,
                   help="literal pattern (default: DSI_GREP_PATTERN)")
    p.add_argument("--chunk-bytes", type=_positive_int, default=1 << 20,
                   help="per-device bytes per stream step (also the line "
                        "length ceiling: a wider line routes the stream "
                        "to the host scan)")
    p.add_argument("--devices", type=int, default=None,
                   help="mesh size (default: all local devices)")
    p.add_argument("--pipeline-depth", type=_positive_int, default=None,
                   help="in-flight stream steps (default: "
                        "DSI_STREAM_PIPELINE_DEPTH or 2; 1 = synchronous)")
    p.add_argument("--device-accumulate", action="store_true",
                   help="fold histograms + top-k candidates into the "
                        "device-resident service (dsi_tpu/device/topk.py) "
                        "and pull only every --sync-every steps — results "
                        "are bit-identical")
    p.add_argument("--sync-every", type=_positive_int, default=None,
                   help="folds between host pulls with --device-accumulate "
                        "(default: DSI_STREAM_SYNC_EVERY or 8)")
    p.add_argument("--mesh-shards", type=int, default=None,
                   help="mesh-shard the device services across N shards "
                        "(ihash %% N routing inside the fold, per-shard "
                        "widens, pre-merged histogram pulls; implies "
                        "--device-accumulate; default: "
                        "DSI_STREAM_MESH_SHARDS or 0 = off)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable crash-resume checkpoints (dsi_tpu/ckpt)")
    p.add_argument("--ckpt-async", action="store_true", default=None,
                   dest="ckpt_async",
                   help="overlap checkpoint commits with the pipeline "
                        "(env DSI_STREAM_CKPT_ASYNC)")
    p.add_argument("--ckpt-delta", action="store_true", default=None,
                   dest="ckpt_delta",
                   help="incremental checkpoints (env "
                        "DSI_STREAM_CKPT_DELTA)")
    p.add_argument("--checkpoint-every", type=_positive_int, default=None,
                   help="confirmed steps between checkpoints (default: "
                        "DSI_STREAM_CKPT_EVERY or 32)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest valid checkpoint in "
                        "--checkpoint-dir; results are bit-identical to "
                        "an uninterrupted run")
    p.add_argument("--topk", type=_positive_int, default=16,
                   help="top-k lines by occurrence count to report")
    p.add_argument("--workdir", default=None,
                   help="commit the result as DIR/mr-out-0 (temp file + "
                        "rename; default: print only)")
    p.add_argument("--aot", action="store_true",
                   help="compile the device services explicitly at "
                        "full-capacity shapes (the step programs always "
                        "are), so a warm pass covers the run")
    p.add_argument("--stats", action="store_true",
                   help="print the pipeline_stats dict (phase walls + "
                        "fold/sync/widen/snapshot counters) to stderr")
    p.add_argument("--check", action="store_true",
                   help="run the host oracle scan over the same stream "
                        "and verify parity (exit 2 on mismatch)")
    p.add_argument("--ingest-readers", type=int, default=None,
                   dest="ingest_readers",
                   help="parallel mmap'd input readers with readahead "
                        "(utils/ioread.py): N threads fill blocks ahead "
                        "of the batcher; cursors/checkpoints stay "
                        "byte-exact (default: DSI_INGEST_READERS or 0 "
                        "= inline reads)")
    p.add_argument("--trace-dir", default=None,
                   help="write this run's unified trace (dsi_tpu/obs): "
                        "Perfetto trace.json + trace.jsonl event log; "
                        "render with scripts/tracecat.py")
    p.add_argument("--statusz-port", type=int, default=None,
                   help="serve live telemetry on 127.0.0.1:PORT — "
                        "/statusz + /metrics (0 = pick a free port; "
                        "default off, env DSI_STATUSZ_PORT); arms the "
                        "stall watchdog and the live.jsonl ring")
    args = p.parse_args(argv)

    if args.resume and not args.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")

    if args.trace_dir:
        from dsi_tpu.obs import configure_tracing

        configure_tracing(trace_dir=args.trace_dir)

    if args.statusz_port is not None or os.environ.get("DSI_STATUSZ_PORT"):
        from dsi_tpu.obs.live import start_from_args

        start_from_args(args.statusz_port, live_dir=args.trace_dir)

    pattern = args.pattern or os.environ.get("DSI_GREP_PATTERN")
    if not pattern:
        print("grepstream: no pattern (--pattern or DSI_GREP_PATTERN)",
              file=sys.stderr)
        return 1

    from dsi_tpu.obs import span
    from dsi_tpu.obs.registry import job_children_s

    # The root of the main thread's account, as in wcstream: its direct
    # children (the registry's JOB_CHILDREN) cover it.
    pstats: dict = {}
    with span("job", lane="host", stats=pstats):
        rc, res, host_path = _job(args, pattern, pstats)
    if rc:
        return rc
    for key in ("job_s", "start_s", "write_s"):
        if key in pstats:  # no write without --workdir
            pstats[key] = round(pstats[key], 4)
    pstats["job_children_s"] = round(job_children_s(pstats), 4)
    # After the write, so that the line holds the job's tail too
    # (finalize_s, write_s) and the trace its last span.
    if args.stats:
        print(f"grepstream: pipeline_stats={pstats}", file=sys.stderr)
    if args.trace_dir:
        from dsi_tpu.obs import flush_tracing_report

        flush_tracing_report(args.trace_dir, "grepstream")

    print(f"lines={res.lines} matched={res.matched} "
          f"occurrences={res.occurrences}")
    print("hist=" + ",".join(str(h) for h in res.hist))
    for line_no, occ in res.topk:
        print(f"top line={line_no} occ={occ}")

    if args.check and not host_path:
        from dsi_tpu.parallel.grepstream import grep_host_oracle
        from dsi_tpu.parallel.streaming import stream_files

        want = grep_host_oracle(stream_files(args.files), pattern,
                                topk=args.topk)
        if res != want:
            print("grepstream: PARITY FAILURE vs host oracle",
                  file=sys.stderr)
            return 2
        print("grepstream: parity OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
