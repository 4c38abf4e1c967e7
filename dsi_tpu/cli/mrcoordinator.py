"""Coordinator process entry point.

Reference: ``main/mrcoordinator.go:17-29`` — parse argv (input files), build a
coordinator with nReduce=10, poll Done() at 1 Hz, sleep one extra second after
done so workers can observe TaskStatus=DONE, then exit (the dying socket kills
any remaining workers' dials).

Usage: python -m dsi_tpu.cli.mrcoordinator [--nreduce N] inputfiles...
"""

from __future__ import annotations

import argparse
import time

from dsi_tpu.config import JobConfig
from dsi_tpu.mr.coordinator import make_coordinator
from dsi_tpu.obs import get_tracer


def main(argv=None) -> int:
    get_tracer()  # its epoch precedes the first assign
    p = argparse.ArgumentParser()
    p.add_argument("--nreduce", type=int, default=10)  # mrcoordinator.go:23
    p.add_argument("--task-timeout", type=float, default=10.0)
    p.add_argument("--journal", default="",
                   help="checkpoint journal path; an existing journal for "
                        "the same job resumes it (new capability — the "
                        "reference loses the job on coordinator death)")
    p.add_argument("files", nargs="+")
    args = p.parse_args(argv)
    cfg = JobConfig(n_reduce=args.nreduce, task_timeout_s=args.task_timeout,
                    journal_path=args.journal)
    c = make_coordinator(args.files, args.nreduce, cfg)
    addr = c.address()
    if addr and addr.startswith("tcp:"):
        import sys

        # With tcp:HOST:0 the port is kernel-assigned; tell the operator
        # what workers should set DSI_MR_SOCKET to.
        print(f"mrcoordinator: listening on {addr}",
              file=sys.stderr, flush=True)
    while not c.done():  # mrcoordinator.go:24-26
        time.sleep(cfg.done_poll_s)
    time.sleep(cfg.exit_grace_s)  # mrcoordinator.go:28
    c.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
