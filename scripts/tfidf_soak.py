#!/usr/bin/env python
"""TF-IDF GB-scale soak on the virtual mesh: one measured partition slice.

BASELINE.json's last config is TF-IDF over a 10 GB shard on a v5e-64; this
host has one core and a virtual mesh, so the honest reachable evidence is a
measured ~1 GB single-slice run: wall, throughput,
postings volume, and peak RSS (device work repeats per slice; host memory
divides by the slice count — parallel/tfidf.py module docs).

Verification at this scale: full oracle parity would cost more than the
run (it is covered byte-for-byte at test scale, tests/test_tfidf.py), so
the soak checks structural invariants over everything plus exact posting
parity for the first --verify-docs documents (host recount).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python scripts/tfidf_soak.py [--mb 1024] [--slice 5]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=1024)
    ap.add_argument("--doc-kb", type=int, default=1024)
    ap.add_argument("--slice", type=int, default=5,
                    help="accumulate the first N of --n-reduce partitions")
    ap.add_argument("--n-reduce", type=int, default=10)
    ap.add_argument("--verify-docs", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="in-flight wave window (default: "
                         "DSI_STREAM_PIPELINE_DEPTH or 2; 1 = the "
                         "synchronous lockstep walk)")
    ap.add_argument("--device-accumulate", action="store_true",
                    help="batch the wave walk's D2H through the "
                         "device-resident postings buffer (dsi_tpu/"
                         "device/postings.py)")
    ap.add_argument("--mesh-shards", type=int, default=None,
                    help="mesh-shard the postings buffer across N shards "
                         "(ihash %% N word routing inside the append; "
                         "implies --device-accumulate; default: "
                         "DSI_STREAM_MESH_SHARDS or 0 = off)")
    ap.add_argument("--sync-every", type=int, default=None,
                    help="waves between host pulls with "
                         "--device-accumulate (default: "
                         "DSI_STREAM_SYNC_EVERY or 8)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="enable crash-resume checkpoints (dsi_tpu/ckpt)")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="confirmed waves between checkpoints (default: "
                         "DSI_STREAM_CKPT_EVERY or 32)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest valid checkpoint in "
                         "--checkpoint-dir")
    ap.add_argument("--ckpt-async", action="store_true", default=None,
                    dest="ckpt_async",
                    help="overlap checkpoint commits with the wave walk "
                         "(env DSI_STREAM_CKPT_ASYNC)")
    ap.add_argument("--ckpt-delta", action="store_true", default=None,
                    dest="ckpt_delta",
                    help="incremental checkpoints, full re-base every "
                         "DSI_STREAM_CKPT_REBASE saves (env "
                         "DSI_STREAM_CKPT_DELTA)")
    ap.add_argument("--trace-dir", default=None,
                    help="write the soak's unified trace (dsi_tpu/obs): "
                         "Perfetto trace.json + trace.jsonl; render "
                         "with scripts/tracecat.py")
    ap.add_argument("--statusz-port", type=int, default=None,
                    help="serve live telemetry on 127.0.0.1:PORT — "
                         "/statusz + /metrics (0 = pick a free port; "
                         "default off, env DSI_STATUSZ_PORT); arms the "
                         "stall watchdog and the live.jsonl ring")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")

    if args.trace_dir:
        from dsi_tpu.obs import configure_tracing

        configure_tracing(trace_dir=args.trace_dir)

    if args.statusz_port is not None or os.environ.get("DSI_STATUSZ_PORT"):
        from dsi_tpu.obs.live import start_from_args

        start_from_args(args.statusz_port, live_dir=args.trace_dir)

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from dsi_tpu.mr.worker import ihash
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import FileDocs, tfidf_sharded
    from dsi_tpu.utils.corpus import ensure_corpus

    n_docs = max(1, (args.mb << 10) // args.doc_kb)
    doc_bytes = args.doc_kb << 10
    cdir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench", f"tfidf-soak-{args.mb}")
    t0 = time.perf_counter()
    paths = ensure_corpus(cdir, n_files=n_docs, file_size=doc_bytes)
    # Lazy docs + packed result (round 5): the corpus never sits resident
    # and the postings stay numpy — the r4 soak's 5.1 GB peak was mostly
    # the resident docs plus the pythonized result dict.
    docs = FileDocs(paths)
    gen_s = time.perf_counter() - t0
    total_mb = sum(docs.lengths) / 1e6
    print(f"corpus: {len(docs)} docs, {total_mb:.0f} MB "
          f"(gen {gen_s:.1f}s)", file=sys.stderr, flush=True)

    mesh = default_mesh(args.devices)
    partitions = set(range(args.slice)) if args.slice else None
    wave_stats: dict = {}
    t0 = time.perf_counter()
    res = tfidf_sharded(docs, mesh=mesh, n_reduce=args.n_reduce,
                        u_cap=1 << 15, partitions=partitions, packed=True,
                        depth=args.pipeline_depth,
                        device_accumulate=args.device_accumulate,
                        sync_every=args.sync_every,
                        mesh_shards=args.mesh_shards,
                        checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every,
                        checkpoint_async=args.ckpt_async,
                        checkpoint_delta=args.ckpt_delta,
                        resume=args.resume,
                        wave_stats=wave_stats)
    wall = time.perf_counter() - t0
    assert res is not None, "tfidf fell back to host"
    if args.trace_dir:
        from dsi_tpu.obs import flush_tracing_report

        flush_tracing_report(args.trace_dir)

    # Structural invariants over the whole result (vectorized on the
    # packed tables).
    ppw = res.postings_per_word()
    assert len(ppw) == 0 or (1 <= ppw.min() and ppw.max() <= len(docs))
    if partitions is not None:
        assert np.isin(res.parts,
                       np.fromiter(partitions, np.uint32)).all()
    postings = res.n_postings

    # Exact parity for the first --verify-docs documents: every sampled
    # doc's (word -> tf) with an in-slice partition must appear verbatim.
    sample_ok = True
    for di in range(min(args.verify_docs, len(docs))):
        counts: dict = {}
        for w in re.findall(r"[A-Za-z]+", docs[di].decode()):
            counts[w] = counts.get(w, 0) + 1
        hits = res.lookup_many(counts.keys())
        for w, tf in counts.items():
            if partitions is not None and ihash(w) % args.n_reduce \
                    not in partitions:
                continue
            ent = hits.get(w)  # a missing word is a mismatch, not a crash
            got = dict(ent[1]).get(di) if ent else None
            if got != tf:
                print(f"sample mismatch: doc {di} word {w!r}: {got} != {tf}",
                      file=sys.stderr, flush=True)
                sample_ok = False

    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    # Per-phase attribution (tfidf_sharded wave_stats), mirroring the
    # stream row's stream_phases: says WHERE the soak's seconds went —
    # and whether the pipeline actually took check/pull off the critical
    # path (kernel_s = time blocked on deferred scalar checks).
    wave_phases = {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in wave_stats.items()
        if k.endswith("_s") or k in (
            "waves", "depth", "replays", "max_inflight_waves",
            "step_pulls", "appends", "append_overflows", "sync_pulls",
            "postings_widens", "sync_every", "device_accumulate")}
    print(json.dumps({
        "tfidf_mb": round(total_mb, 1), "wall_s": round(wall, 1),
        "mbps": round(total_mb / wall, 2), "n_docs": len(docs),
        "slice": f"{args.slice}/{args.n_reduce}" if partitions else "full",
        "uniques": len(res), "postings": postings,
        "sample_parity": sample_ok, "peak_rss_mb": round(rss_mb, 1),
        "wave_phases": wave_phases}))
    return 0 if sample_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
