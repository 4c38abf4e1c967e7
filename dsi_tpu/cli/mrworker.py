"""Worker process entry point.

Reference: ``main/mrworker.go:19-28`` — argv is one plugin; load its
Map/Reduce, then run the worker loop.  Extended with ``--backend=tpu``
(the BASELINE.json north-star flag) routing execution to the JAX backend,
and with the NET data plane (ISSUE 17): when ``DSI_NET_SPOOL`` is set
(by ``mrrun --net``) the worker boots a partition server over that
private spool directory, runs the loop in net mode, and LINGERS after
the job completes so consumers can still fetch its spooled bytes — the
driver terminates it once every output is safely fetched.

``--backend tpu`` means the device: the worker fails at start unless JAX
gives it a TPU or the CPU was asked for by name (``JAX_PLATFORMS=cpu``),
and at exit it prints how many map tasks ran on the device and how many
fell back to the host.  ``DSI_MR_REDUCE_ONLY=1`` (set by ``mrrun`` for the
host helpers of a ``--backend tpu`` fleet) makes the worker decline map
tasks, so every map of such a job runs on the device worker.

Usage: python -m dsi_tpu.cli.mrworker [--backend host|tpu|native] <app-name-or-path.py>
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from dsi_tpu.config import JobConfig
from dsi_tpu.mr.plugin import load_plugin
from dsi_tpu.mr.worker import worker_loop
from dsi_tpu.obs import event as _event, span as _span


def main(argv=None) -> int:
    # First thing: a worker enabled by DSI_TRACE_DIR builds its tracer
    # here, so that the tracer's epoch precedes the first task.
    _event("worker.start", lane="launch")
    p = argparse.ArgumentParser()
    p.add_argument("--backend", choices=("host", "tpu", "native"),
                   default="host")
    p.add_argument("app")
    args = p.parse_args(argv)
    mapf, reducef = load_plugin(args.app)
    # Build/load the native decoder NOW, before the task loop: the first
    # lazy build (up to 120 s of g++) must not land inside a live reduce
    # task, where it would blow straight through the coordinator's 10 s
    # requeue timeout and cause spurious task duplication (worst on NFS
    # fleets where many hosts race the same build).
    from dsi_tpu import native

    native.available()
    reduce_only = os.environ.get("DSI_MR_REDUCE_ONLY") == "1"
    cfg = JobConfig(backend=args.backend, take_maps=not reduce_only)
    runner = None
    if args.backend == "tpu":
        from dsi_tpu.backends.tpu import TpuTaskRunner

        with _span("backend_init", lane="launch"):
            runner = TpuTaskRunner.for_app(args.app)
        _event("backend_up", lane="launch", **runner.device)
    elif args.backend == "native":
        from dsi_tpu.backends.native import NativeTaskRunner

        runner = NativeTaskRunner.for_app(args.app)
    spool = os.environ.get("DSI_NET_SPOOL")
    partsrv = None
    if spool:
        from dsi_tpu.net import PartitionServer, fetch_window_from_env

        cfg = JobConfig(backend=args.backend, take_maps=not reduce_only,
                        net_shuffle=True,
                        net_fetch_window=fetch_window_from_env())
        partsrv = PartitionServer(
            spool, bind=os.environ.get("DSI_NET_BIND", ""),
            retention_s=cfg.net_spool_retention_s,
            codec=cfg.net_codec)
        partsrv.start()
    try:
        worker_loop(mapf, reducef, cfg, task_runner=runner,
                    partsrv=partsrv)
        if args.backend == "tpu":
            # one write: print() sends the newline separately, and the
            # workers of a job share their launcher's stderr
            sys.stderr.write(
                f"mrworker: pid={os.getpid()} {runner.report()}\n")
            sys.stderr.flush()
        if partsrv is not None:
            # Linger: the job is done but the driver may not have
            # fetched this spool's outputs yet — serve until killed.
            while True:
                time.sleep(3600)
    finally:
        if partsrv is not None:
            partsrv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
