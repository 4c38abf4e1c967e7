"""The resident MapReduce-as-a-service daemon (``dsi_tpu/serve``).

Boots once — device mesh init, AOT warm, spool hygiene — then serves
job submissions over a Unix-socket control plane until shut down.  Many
small jobs amortize the start cost K one-shot CLIs would each pay, and
word-count tenants additionally PACK into shared device steps (K
tenants ≈ 1 dispatch; ``serve/pack.py``).  Kill it however you like:
accepted jobs are journaled durably and per-tenant delta-checkpoint
chains make the restart resume every in-flight tenant with
byte-identical output.

Usage:
    python -m dsi_tpu.cli.mrserve --spool DIR [--socket PATH]
        [--nreduce N] [--chunk-bytes B] [--devices D]
        [--max-resident K] [--quota-steps Q] [--checkpoint-every K]
        [--max-queue N] [--rate-limit R] [--rate-burst B]
        [--no-pack-grep] [--retention-days D] [--statusz-port P]
        [--trace-dir DIR] [--no-warm]
"""

from __future__ import annotations

import argparse
import os
import signal
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--spool", required=True,
                   help="daemon state root: control socket, job "
                        "journal, per-tenant checkpoint chains, job "
                        "outputs")
    p.add_argument("--socket", default=None,
                   help="control socket path (default: "
                        "<spool>/mrserve.sock)")
    p.add_argument("--nreduce", type=int, default=10,
                   help="the daemon's reduce-partition degree (packed "
                        "steps share it; submissions must match)")
    p.add_argument("--chunk-bytes", type=int, default=1 << 16,
                   help="per-lane bytes per packed step (rounded up to "
                        "a power of two, min 256)")
    p.add_argument("--devices", type=int, default=None,
                   help="mesh size = packing lanes (default: all local "
                        "devices)")
    p.add_argument("--max-resident", type=int, default=8,
                   help="jobs held in memory at once; the rest park as "
                        "checkpoint chains until scheduled")
    p.add_argument("--quota-steps", type=int, default=64,
                   help="packed steps a resident job may ride while "
                        "others queue before it is evicted to its "
                        "chain")
    p.add_argument("--checkpoint-every", type=int, default=8,
                   help="confirmed packed steps between per-tenant "
                        "snapshots (delta chains; eviction and crash "
                        "recovery both resume from them)")
    p.add_argument("--max-queue", type=int, default=1024,
                   help="queued jobs past which submissions are SHED "
                        "with a typed backpressure error (the journal "
                        "is never written for a shed job)")
    p.add_argument("--rate-limit", type=float, default=None,
                   help="per-tenant submit rate (jobs/second, token "
                        "bucket; default: unlimited)")
    p.add_argument("--rate-burst", type=int, default=4,
                   help="token-bucket burst capacity per tenant")
    p.add_argument("--no-pack-grep", action="store_true",
                   help="run grep jobs as time-multiplexed step "
                        "objects instead of packed lanes (the bench "
                        "row's control arm; env DSI_SERVE_PACK_GREP=0)")
    p.add_argument("--retention-days", type=float, default=14.0,
                   help="age after which a DONE tenant's checkpoint "
                        "chains are garbage-collected at boot (live "
                        "chains are never touched)")
    p.add_argument("--statusz-port", type=int, default=None,
                   help="serve live telemetry on 127.0.0.1:PORT — "
                        "/statusz gains a per-tenant section and "
                        "/metrics dsi_serve_* series; 0 picks a free "
                        "port (env DSI_STATUSZ_PORT)")
    p.add_argument("--trace-dir", default=None,
                   help="unified trace output dir (dsi_tpu/obs)")
    p.add_argument("--no-warm", action="store_true",
                   help="skip the boot-time AOT warm (tests)")
    p.add_argument("--replicas", type=int, default=0,
                   help="run N coordinator replicas (Raft group, "
                        "dsi_tpu/replica) instead of one daemon; the "
                        "leader hosts the daemon, admissions commit to "
                        "the replicated log before acking, and clients "
                        "dial the printed comma-separated socket list")
    args = p.parse_args(argv)

    if args.replicas:
        if args.replicas < 2:
            p.error("--replicas needs >= 2 (3 for kill-tolerance)")
        if args.socket:
            p.error("--socket conflicts with --replicas (each replica "
                    "binds <spool>/replica-<i>.sock)")
        return _replica_serve(args)

    if args.trace_dir:
        from dsi_tpu.obs import configure_tracing

        configure_tracing(trace_dir=args.trace_dir)

    # Live telemetry BEFORE jax init, the wcstream discipline: /statusz
    # answers while the mesh is still coming up.
    if args.statusz_port is not None or os.environ.get("DSI_STATUSZ_PORT"):
        from dsi_tpu.obs.live import start_from_args

        start_from_args(args.statusz_port, live_dir=args.trace_dir)

    from dsi_tpu.utils.platformpin import require_device

    require_device("mrserve")

    from dsi_tpu.serve.daemon import ServeDaemon

    daemon = ServeDaemon(
        args.spool, socket_path=args.socket, n_reduce=args.nreduce,
        chunk_bytes=args.chunk_bytes, devices=args.devices,
        max_resident=args.max_resident, quota_steps=args.quota_steps,
        checkpoint_every=args.checkpoint_every,
        retention_s=args.retention_days * 86400.0,
        warm=not args.no_warm, max_queue=args.max_queue,
        rate_limit=args.rate_limit, rate_burst=args.rate_burst,
        pack_grep=False if args.no_pack_grep else None)

    def _stop(_sig, _frm):
        daemon.stop()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    daemon.start()
    print(f"mrserve: spool={daemon.spool} socket={daemon.socket_path} "
          f"lanes={args.devices or 'auto'} (boot reaped "
          f"{daemon.boot_reaped} tmp orphans, gc'd "
          f"{daemon.boot_gc_chains} aged chains)",
          file=sys.stderr, flush=True)
    daemon.ready.wait()
    print("mrserve: ready", file=sys.stderr, flush=True)
    try:
        while daemon._thread.is_alive():
            daemon.join(timeout=0.5)
    finally:
        daemon.close()
        print(f"mrserve: pipeline_stats={daemon.stats_section()!r}",
              file=sys.stderr, flush=True)
        if args.trace_dir:
            from dsi_tpu.obs import flush_tracing_report

            flush_tracing_report(args.trace_dir, "mrserve")
    print("mrserve: stopped", file=sys.stderr, flush=True)
    return 0


def _replica_serve(args) -> int:
    """``--replicas N``: spawn the coordinator group and supervise it.

    The leader replica hosts the real ServeDaemon; this process only
    writes the group spec, babysits the N ``replicad`` children, and
    prints the comma-separated socket spec clients (``serve/client.py``,
    ``mrsubmit``) dial — the group dialer follows leader redirects, so
    a ``kill -9`` of the leader is invisible to submitters beyond the
    election wall."""
    import time as _time

    from dsi_tpu.replica.driver import ReplicaGroup

    spool = os.path.abspath(args.spool)
    serve_kw = {
        "n_reduce": args.nreduce, "chunk_bytes": args.chunk_bytes,
        "devices": args.devices, "max_resident": args.max_resident,
        "quota_steps": args.quota_steps,
        "checkpoint_every": args.checkpoint_every,
        "retention_s": args.retention_days * 86400.0,
        "warm": not args.no_warm, "max_queue": args.max_queue,
        "rate_limit": args.rate_limit, "rate_burst": args.rate_burst,
        "pack_grep": False if args.no_pack_grep else None,
    }
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if args.trace_dir:
        env["DSI_TRACE_DIR"] = os.path.abspath(args.trace_dir)

    group = ReplicaGroup("serve", spool, replicas=args.replicas,
                         spool=spool, serve=serve_kw, env=env)
    stop = {"flag": False}

    def _stop(_sig, _frm):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    rc = 0
    try:
        info = group.wait_leader(timeout=180.0)
        print(f"mrserve: replica group up, leader is replica "
              f"{info['index']} (term {info['term']})",
              file=sys.stderr, flush=True)
        print(f"mrserve: sockets {group.spec}", file=sys.stderr,
              flush=True)
        print("mrserve: ready", file=sys.stderr, flush=True)
        while not stop["flag"]:
            _time.sleep(0.2)
            for i, proc in group.procs.items():
                code = proc.poll()
                if code not in (None, 0, -signal.SIGTERM):
                    # A replica died outside our control (OOM, chaos
                    # harness): respawn it — the group tolerates a
                    # minority down, but not forever.
                    group.spawn(i)
                    group.respawns += 1
    except KeyboardInterrupt:
        pass
    except Exception as e:  # no leader ever emerged: say so, clean up
        print(f"mrserve: replica group failed: {e}", file=sys.stderr,
              flush=True)
        rc = 1
    finally:
        group.close()
    print("mrserve: stopped", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
