"""Typed configuration for the framework.

The reference has no config system: nReduce is the literal 10
(``main/mrcoordinator.go:23``), the straggler timeout 10 s
(``mr/coordinator.go:71,100``), the done-poll and exit-grace 1 s
(``main/mrcoordinator.go:25,28``), and the socket path a constant
(``mr/rpc.go:37-41``).  SURVEY.md §5 calls for a small typed config with
those values as defaults — this is it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os


def default_socket_path(workdir: str | None = None) -> str:
    """Unix-domain socket path for the coordinator.

    Reference: ``coordinatorSock()`` returns ``/var/tmp/824-mr-<uid>``
    (``mr/rpc.go:37-41``).  That per-UID name prevents concurrent jobs on one
    machine (noted in ``main/test-mr-many.sh:10-11``); we additionally hash the
    working directory into the name so independent jobs (and parallel test
    sandboxes) never collide.  Overridable via ``DSI_MR_SOCKET``.
    """
    env = os.environ.get("DSI_MR_SOCKET")
    if env:
        return env
    wd = os.path.abspath(workdir or os.getcwd())
    tag = hashlib.md5(wd.encode()).hexdigest()[:8]
    return f"/var/tmp/dsi-mr-{os.getuid()}-{tag}"


@dataclasses.dataclass(frozen=True)
class JobConfig:
    """Everything the coordinator + workers need for one MapReduce job."""

    # Number of reduce partitions.  Reference default: 10
    # (main/mrcoordinator.go:23).
    n_reduce: int = 10

    # Straggler re-queue threshold, seconds.  Reference: 10 s goroutine sleep
    # (mr/coordinator.go:71,100).
    task_timeout_s: float = 10.0

    # Coordinator Done() poll interval and post-done grace, seconds
    # (main/mrcoordinator.go:25,28).
    done_poll_s: float = 1.0
    exit_grace_s: float = 1.0

    # Worker sleep when told "waiting" (TaskStatus=2).  The reference worker
    # busy-polls with no backoff (no case 2 in mr/worker.go:54-162) — SURVEY.md
    # §3.3 flags this as a defect to fix; output is unaffected.
    wait_sleep_s: float = 0.2

    # Directory where mr-X-Y and mr-out-Y files live.  Reference: the cwd.
    workdir: str = "."

    # Execution backend for map/reduce tasks: "host" (reference semantics,
    # pure Python) or "tpu" (JAX kernels for TPU-aware apps).
    backend: str = "host"

    # Whether this worker takes map tasks.  False for the host helpers of
    # a ``mrrun --backend tpu`` fleet: they ask for reduce work only, so
    # every map of a device job runs on the one worker that owns the chip.
    take_maps: bool = True

    # Coordinator socket path ("" -> default_socket_path(workdir)).
    socket_path: str = ""

    # Coordinator checkpoint journal ("" = disabled, reference behavior —
    # coordinator death kills the job, SURVEY.md §5).  When set, unique task
    # completions are journaled and a restarted coordinator resumes the job.
    journal_path: str = ""

    # ── streaming-shard jobs (mr/shards.py) ──

    # Attempt presumed-dead silence, seconds: a shard attempt that has not
    # sent a progress RPC for this long is marked dead and the shard is
    # re-queued with a resume hint.  Progress-based, unlike task_timeout_s
    # (shards are long-running; assignment-age timeouts would kill every
    # healthy big shard).
    shard_timeout_s: float = 10.0

    # Speculative backup dispatch (Dean & Ghemawat §3.6).  An idle worker
    # asking for work when no shard is untouched may be handed a BACKUP
    # attempt of a shard whose newest attempt has been silent longer than
    # max(spec_k * p99(that worker's contact gaps), spec_floor_s) — the
    # percentile-aware straggler_suspects() signal.  First commit wins.
    spec_backup: bool = True
    spec_k: float = 2.0
    spec_floor_s: float = 2.0

    # Setup grace: an attempt that has not yet sent its first progress
    # RPC is still constructing its engine (jax init + first compiles,
    # seconds of legitimate silence) — the silence trigger waits at
    # least this long for such attempts so fresh attempts don't attract
    # spurious backups.
    spec_setup_s: float = 8.0

    # Dynamic re-split (the elastic-dataflow half of §3.5/§3.6): when
    # the straggler triggers fire on a splittable shard, split the slow
    # attempt's REMAINING cursor range (from its live confirmed cursor)
    # into newline-aligned sub-shards for idle workers instead of
    # racing one whole-range backup.  First commit wins PER SUB-RANGE;
    # the straggler keeps running and still wins the whole shard if it
    # commits before every sub-range has.
    spec_resplit: bool = False
    # How many ways the remaining range is split.
    spec_resplit_ways: int = 2
    # Remainders smaller than this fall back to a plain backup — a
    # sub-shard must amortize one engine setup.
    spec_resplit_min_bytes: int = 1 << 16

    # Worker-side progress-RPC cadence while driving a shard, seconds.
    shard_progress_s: float = 0.5

    # Total attempts allowed per shard (primaries + backups + takeovers)
    # before the job is declared failed — bounds a poisoned shard.
    shard_max_attempts: int = 8

    # ── network data plane (dsi_tpu/net, ISSUE 17) ──

    # Worker-served shuffle: workers spool partitions to a PRIVATE local
    # dir and serve them over TCP; reducers/consumers fetch via
    # net/fetch.py instead of reading a shared directory.  Off = the
    # reference's shared-filesystem data plane.
    net_shuffle: bool = False

    # Partition-server bind address for this worker ("" = tcp:127.0.0.1:0,
    # an OS-assigned loopback port; multi-host fleets set a real host and
    # DSI_MR_SECRET).  Env override: DSI_NET_BIND.
    net_bind: str = ""

    # Shuffle payloads cross the wire through the PR-13 line codec
    # (ops/wirecodec.pack_kv) when it shrinks them; raw otherwise.
    net_codec: bool = True

    # Fetch dial/stream timeout, seconds (per fetch attempt; the dial
    # itself retries transient errors through dial_backoff_schedule).
    net_fetch_timeout_s: float = 30.0

    # Reduce-side prefetch window (ISSUE 18): how many partition fetches
    # may be in flight or buffered-unconsumed while the consumer decodes.
    # 1 = the serial fetch→decode loop, bit-identically.  Env override:
    # DSI_NET_FETCH_WINDOW.
    net_fetch_window: int = 4

    # Spool entries untouched this long are aged out at partition-server
    # boot (dead-task spools from kill-9'd predecessors; the serve
    # daemon's retention discipline).
    net_spool_retention_s: float = 3600.0

    def sock(self) -> str:
        return self.socket_path or default_socket_path(self.workdir)
