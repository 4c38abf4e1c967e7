"""Shard-worker process entry point (``mr/shardworker.py``).

Spawned by ``shardrun`` with cwd=workdir and the coordinator socket in
``DSI_MR_SOCKET``; every engine knob arrives over the wire in the shard
assignment, so the process needs no app argument.  Commits a
trace-<pid> file at exit when ``DSI_TRACE_DIR`` is inherited.
"""

from __future__ import annotations

import argparse


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workdir", default=".")
    p.add_argument("--progress-s", type=float, default=None,
                   help="ShardProgress heartbeat cadence, seconds")
    p.add_argument("--shard-timeout", type=float, default=None,
                   help="mirror of the coordinator's presumed-dead "
                        "silence (informational on the worker side)")
    args = p.parse_args(argv)
    import os
    import time

    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.shardworker import shard_worker_loop
    from dsi_tpu.utils.platformpin import require_device

    # Every shard runs a device engine: no chip (and no CPU asked for by
    # name) is an error here, not a silent CPU run (shardrun gives each
    # worker its chip, cli/chips.py).
    require_device("shardworker")

    kw = {"workdir": args.workdir}
    if args.progress_s is not None:
        kw["shard_progress_s"] = args.progress_s
    if args.shard_timeout is not None:
        kw["shard_timeout_s"] = args.shard_timeout
    # NET data plane (ISSUE 17, ``shardrun --hosts``): DSI_NET_SPOOL
    # names this worker's PRIVATE spool dir — boot a partition server
    # over it, advertise its address on every RPC, and LINGER after the
    # job so the driver can still fetch committed outputs; the driver
    # terminates the process once everything is fetched.
    spool = os.environ.get("DSI_NET_SPOOL")
    partsrv = None
    if spool:
        from dsi_tpu.net import PartitionServer

        kw["net_shuffle"] = True
        cfg0 = JobConfig(**kw)
        partsrv = PartitionServer(
            spool, bind=os.environ.get("DSI_NET_BIND", ""),
            retention_s=cfg0.net_spool_retention_s,
            codec=cfg0.net_codec)
        partsrv.start()
    # Tracing: DSI_TRACE_DIR (inherited from shardrun) arms the global
    # tracer with a durable atexit flush; chaos/fault kills flush
    # explicitly before os._exit (ckpt/fault.py).
    try:
        shard_worker_loop(JobConfig(**kw), partsrv=partsrv)
        if partsrv is not None:
            while True:
                time.sleep(3600)
    finally:
        if partsrv is not None:
            partsrv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
