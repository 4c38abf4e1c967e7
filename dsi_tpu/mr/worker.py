"""Worker: pull-loop task executor.

Reference: ``mr/worker.go`` (188 LoC).  Same loop: request a task; execute a
map or reduce task; report completion; exit when the coordinator says DONE or
becomes unreachable (``worker.go:46-165``).  Same data-plane contract:

* map writes NReduce intermediate files ``mr-<m>-<r>``, JSON records, committed
  by temp-file + atomic rename (worker.go:81-92),
* the partitioner is ``fnv32a(key) & 0x7fffffff  %  NReduce`` — bit-for-bit the
  reference's ``ihash`` (worker.go:33-37,76),
* reduce reads every ``mr-*-<r>``, *tolerating missing files*
  (worker.go:106-108), sorts by key, groups runs of equal keys, calls
  ``reducef(key, values)``, writes lines ``f"{key} {output}\n"`` — the Go
  ``"%v %v\n"`` format (worker.go:144) — commits ``mr-out-<r>`` atomically,
  then garbage-collects its intermediates (worker.go:151-154).

Intermediate record encoding: one JSON object per line, ``{"Key": k,
"Value": v}`` — byte-compatible with Go's ``json.Encoder`` stream of
``mr.KeyValue`` (worker.go:84-90).

Deviation (SURVEY.md §3.3, output-invariant): on TaskStatus=WAITING the
reference busy-polls over RPC with no backoff (no case 2 in its switch);
we sleep ``wait_sleep_s`` between polls.
"""

from __future__ import annotations

import json
import os
import time
import zlib
from typing import Callable, List, Sequence

from dsi_tpu.config import JobConfig
from dsi_tpu.mr import rpc
from dsi_tpu.mr.types import KeyValue, TaskStatus
# Leader-discovery shim (dsi_tpu/replica): DSI_MR_SOCKET may name a
# comma-separated coordinator GROUP; group_call follows NotLeader
# redirects and rides out elections.  A single address passes straight
# through to rpc.call, so the classic plane is unchanged.
from dsi_tpu.replica.client import group_call
from dsi_tpu.obs import span as _span
from dsi_tpu.utils.atomicio import atomic_write

MapFn = Callable[[str, str], List[KeyValue]]
ReduceFn = Callable[[str, List[str]], str]


def fnv32a(data: bytes) -> int:
    """FNV-1a 32-bit hash, exactly Go's hash/fnv.New32a (worker.go:33-37)."""
    h = 0x811C9DC5
    for b in data:
        h ^= b
        h = (h * 0x01000193) & 0xFFFFFFFF
    return h


def ihash(key: str) -> int:
    """Reference ihash: fnv32a(key) & 0x7fffffff (worker.go:33-37)."""
    return fnv32a(key.encode("utf-8")) & 0x7FFFFFFF


def intermediate_name(map_task: int, reduce_task: int, workdir: str = ".") -> str:
    return os.path.join(workdir, f"mr-{map_task}-{reduce_task}")


def output_name(reduce_task: int, workdir: str = ".") -> str:
    return os.path.join(workdir, f"mr-out-{reduce_task}")


def write_intermediates(kva: Sequence[KeyValue], map_task: int, n_reduce: int,
                        workdir: str = ".") -> None:
    """Partition by ihash and commit NReduce files atomically
    (worker.go:74-92).

    The partition + serialize pass runs through the native C encoder when
    available (dsi_tpu/native — one pass fusing the per-byte hash,
    json.dumps, and bucketing loops); the Python path below is the exact
    fallback, and both produce records every decoder accepts."""
    from dsi_tpu import native

    with _span("write", lane="host", records=len(kva),
               files=n_reduce) as sp:
        blobs = native.encode_partitions(kva, n_reduce)
        if blobs is not None:
            for r, blob in enumerate(blobs):
                with atomic_write(intermediate_name(map_task, r, workdir),
                                  mode="wb") as f:
                    f.write(blob)
            sp.set(bytes=sum(len(b) for b in blobs))
            return
        buckets: list[list[KeyValue]] = [[] for _ in range(n_reduce)]
        for kv in kva:
            buckets[ihash(kv.key) % n_reduce].append(kv)
        n_bytes = 0
        for r, bucket in enumerate(buckets):
            with atomic_write(intermediate_name(map_task, r, workdir)) as f:
                for kv in bucket:
                    # json.dumps escapes to ASCII: characters are bytes
                    line = json.dumps({"Key": kv.key, "Value": kv.value})
                    f.write(line)
                    f.write("\n")
                    n_bytes += len(line) + 1
        sp.set(bytes=n_bytes)


def read_intermediates(reduce_task: int, n_map: int,
                       workdir: str = ".") -> list[KeyValue]:
    """Read all mr-<i>-<r>, skipping missing files (worker.go:102-121).

    Per-file the native C++ decoder (dsi_tpu/native) is tried first; it
    returns None for anything it can't prove it parsed completely, in which
    case the lenient Python decoder below — the reference's exact
    break-on-bad-record semantics — takes over for that file.
    """
    from dsi_tpu import native

    out: list[KeyValue] = []
    for i in range(n_map):
        path = intermediate_name(i, reduce_task, workdir)
        pairs = native.decode_kv_file(path)
        if pairs is not None:
            out.extend(KeyValue(k, v) for k, v in pairs)
            continue
        try:
            # Explicit utf-8: the native encoder writes raw UTF-8, and the
            # locale default must not reinterpret (or reject) those bytes.
            f = open(path, "r", encoding="utf-8")
        except OSError:
            continue  # tolerated: worker.go:106-108
        with f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    break  # truncated record: reference's decoder break (worker.go:117)
                out.append(KeyValue(obj["Key"], obj["Value"]))
    return out


def group_and_reduce(intermediate: list[KeyValue], reducef: ReduceFn, out) -> None:
    """Sort by key, group runs of equal keys, reduce, format "%v %v\n"
    (worker.go:124-146; identical grouping in main/mrsequential.go:59-84)."""
    intermediate.sort(key=lambda kv: kv.key)
    i = 0
    n = len(intermediate)
    while i < n:
        j = i + 1
        while j < n and intermediate[j].key == intermediate[i].key:
            j += 1
        values = [intermediate[k].value for k in range(i, j)]
        out.write(f"{intermediate[i].key} {reducef(intermediate[i].key, values)}\n")
        i = j


def read_split(filename: str) -> bytes:
    """A map task's input, whole (worker.go:58-67)."""
    with _span("read", lane="host") as sp:
        with open(filename, "rb") as f:
            raw = f.read()
        sp.set(bytes=len(raw))
    return raw


def run_map_task(mapf: MapFn, filename: str, map_task: int, n_reduce: int,
                 workdir: str = ".") -> None:
    """One map task: read the split, run the app map, partition + commit
    (worker.go:55-92)."""
    contents = read_split(filename).decode("utf-8", errors="replace")
    kva = mapf(filename, contents)
    write_intermediates(kva, map_task, n_reduce, workdir)


def run_reduce_task(reducef: ReduceFn, reduce_task: int, n_map: int,
                    workdir: str = ".") -> None:
    """One reduce task: gather, sort, group, reduce, commit, GC
    (worker.go:99-154).

    The output commit is FIRST-writer-wins (utils/atomicio.py): a re-queued
    duplicate of this task that read ``mr-*-<r>`` after this run's GC below
    would otherwise rename an empty ``mr-out-<r>`` over the full one — the
    reference's latent duplicate-reduce race (worker.go:148,151-154), which
    its 10 s timeout hides but a tiny-timeout soak reproduces.  The
    coordinator clears stale ``mr-out-*`` at job start so reruns in the
    same cwd still overwrite (reference rerun behavior)."""
    intermediate = read_intermediates(reduce_task, n_map, workdir)
    with atomic_write(output_name(reduce_task, workdir),
                      first_wins=True) as out:
        group_and_reduce(intermediate, reducef, out)
    for i in range(n_map):  # GC intermediates, errors ignored (worker.go:151-154)
        try:
            os.remove(intermediate_name(i, reduce_task, workdir))
        except OSError:
            pass


def worker_loop(mapf: MapFn, reducef: ReduceFn,
                config: JobConfig | None = None,
                task_runner=None, partsrv=None) -> None:
    """The worker's task loop (mr.Worker, worker.go:43-165).

    `task_runner`, if given, is an object with run_map/run_reduce methods used
    instead of the host-Python execution above — this is the backend seam the
    TPU path plugs into (backends/tpu.py).

    `partsrv`, if given, is this worker's :class:`dsi_tpu.net.PartitionServer`
    (already started) and switches the loop to the NET data plane (ISSUE 17):
    every RPC carries the server's address, map completions register the
    partition locations + per-partition byte sizes with the coordinator, and
    a reduce assignment carrying ``Net``/``MapLocs`` shuffles over TCP
    (``net/fetch.run_reduce_task_net``) instead of reading a shared
    directory — a failed fetch is reported as ``Coordinator.FetchFailed``
    (the producer re-executes, §3.4) and the reduce is retried later.
    """
    import sys

    cfg = config or JobConfig()
    sock = cfg.sock()
    tasks_done = 0
    addr = partsrv.address if partsrv is not None else None
    net_stats = None
    if partsrv is not None:
        from dsi_tpu.obs import metrics_scope

        net_stats = metrics_scope("net")
    # Task-latency histogram (obs/hist.py), published as a registry
    # gauge after every task: lands in this process's trace-meta
    # snapshot and any ``/statusz`` peephole, and gives the
    # speculative-execution hook the worker-side view (how long do MY
    # tasks take) to pair with the coordinator's heartbeat percentiles.
    from dsi_tpu.obs import LatencyHistogram, get_registry

    task_hist = LatencyHistogram()
    # The task spans' stats sink: a span with a sink times its region
    # whether or not tracing is on, which is what note_task reads.
    task_s: dict = {}

    def note_task(seconds: float) -> None:
        task_hist.record(seconds)
        get_registry().set_gauge("mr_worker_task_hist",
                                 task_hist.snapshot())
    # Stable per-process identity, sent with every RPC: the coordinator
    # keys its per-worker heartbeat-age gauge on it (a requeue can then
    # say WHOSE heartbeat went stale — and the speculative-execution
    # hook reads the same gauge).  Old coordinators ignore the extra key.
    worker_id = f"w{os.getpid()}"

    def report_complete(method: str, task_number: int,
                        extra: dict | None = None) -> bool:
        """Completion RPC; False means the loop must exit.  An auth
        rejection is always LOUD — a misconfigured worker must not look
        like a clean end-of-job exit."""
        args = {"TaskNumber": task_number, "WorkerId": worker_id}
        if extra:
            args.update(extra)
        try:
            with _span("rpc", lane="control", method=method):
                group_call(sock, method, args)
            return True
        except rpc.AuthError as e:
            print(f"mrworker: {e}", file=sys.stderr)
            return False
        except rpc.CoordinatorGone:
            return False

    def net_snapshot() -> dict:
        return dict(net_stats) if net_stats is not None else {}

    def net_deltas(before: dict) -> dict:
        """Per-task net-attribution deltas for the completion RPC (the
        coordinator aggregates job-wide; totals would double-count)."""
        if net_stats is None:
            return {}
        out = {wire: int(net_stats.get(k, 0)) - int(before.get(k, 0))
               for wire, k in (("NetFetches", "net_fetches"),
                               ("NetLocal", "net_local_reads"),
                               ("NetRaw", "net_bytes_raw"),
                               ("NetWire", "net_bytes_wire"),
                               ("NetFailures", "net_fetch_failures"))}
        # Overlap attribution (ISSUE 18): wall-second deltas stay float;
        # the prefetch window is a gauge (coordinator folds it as max).
        for wire, k in (("NetWait", "net_fetch_wait_s"),
                        ("NetOverlap", "net_overlap_s")):
            out[wire] = round(float(net_stats.get(k, 0.0))
                              - float(before.get(k, 0.0)), 6)
        out["NetWindow"] = int(net_stats.get("net_prefetch_window", 0))
        return out

    # Chaos injection (DSI_CHAOS_WORKER_KILL=p[,seed], ckpt/fault.py): a
    # real os._exit with probability p at every task boundary, so
    # kill/recovery grids are deterministic and scriptable.  Imported
    # HERE, not at module top: the control plane stays importable on a
    # bare interpreter (the ckpt package init pulls numpy).
    from dsi_tpu.ckpt.fault import chaos_kill_point

    while True:
        chaos_kill_point("task")
        req = {"TaskNumber": 0, "WorkerId": worker_id}
        if not cfg.take_maps:
            req["NoMap"] = True  # coordinator answers WAITING in the map phase
        if addr:
            req["Addr"] = addr
        try:
            with _span("rpc", lane="control",
                       method="Coordinator.RequestTask"):
                ok, reply = group_call(sock, "Coordinator.RequestTask",
                                       req)
        except rpc.CoordinatorGone as e:
            # Coordinator exited; the reference worker dies here
            # (worker.go:176-178).  Normal at end-of-job; noteworthy if this
            # worker never got a single task, and always loud for an auth
            # rejection (see report_complete).
            if tasks_done == 0 or isinstance(e, rpc.AuthError):
                print(f"mrworker: coordinator unreachable: {e}", file=sys.stderr)
            break
        if not ok or reply is None or reply["TaskStatus"] == int(TaskStatus.DONE):
            break  # worker.go:51-53
        status = reply["TaskStatus"]
        if status == int(TaskStatus.MAP):
            # One span per task body: a --trace-dir run yields a
            # per-task timeline, and everything opened inside inherits
            # the task's kind and number.
            with _span("worker.map", lane="control", stats=task_s,
                       kind="map", task=reply["CMap"],
                       file=reply["Filename"]) as sp:
                if task_runner is not None:
                    task_runner.run_map(mapf, reply["Filename"], reply["CMap"],
                                        reply["NReduce"], cfg.workdir)
                else:
                    run_map_task(mapf, reply["Filename"], reply["CMap"],
                                 reply["NReduce"], cfg.workdir)
            note_task(sp.elapsed_s)
            tasks_done += 1
            extra = None
            if addr:
                # Register the partition locations (§3.1): this spool
                # serves mr-<m>-*; the byte sizes feed the locality-
                # share placement policy.
                sizes = []
                for r in range(int(reply["NReduce"])):
                    try:
                        sizes.append(os.path.getsize(intermediate_name(
                            reply["CMap"], r, cfg.workdir)))
                    except OSError:
                        sizes.append(0)
                extra = {"Addr": addr, "PartSizes": sizes}
            if not report_complete("Coordinator.RecieveMapComplete",
                                   reply["CMap"], extra):
                break
        elif status == int(TaskStatus.REDUCE):
            if reply.get("Net") and addr:
                # NET data plane: shuffle over TCP from the producers'
                # partition servers (ISSUE 17).
                from dsi_tpu.net.fetch import (FetchFailure,
                                               run_reduce_task_net)

                before = net_snapshot()
                try:
                    with _span("worker.reduce", lane="control",
                               stats=task_s, kind="reduce",
                               task=reply["CReduce"], net=1) as sp:
                        out_name = run_reduce_task_net(
                            reducef, reply["CReduce"],
                            reply.get("MapLocs") or {},
                            workdir=cfg.workdir, own_addr=addr,
                            stats=net_stats,
                            timeout=cfg.net_fetch_timeout_s,
                            window=cfg.net_fetch_window)
                except FetchFailure as e:
                    # The producer's server is gone: hand the failure
                    # to the coordinator (it re-executes the map, §3.4)
                    # and go back to the well — this reduce re-runs
                    # after the map barrier reopens.
                    try:
                        with _span("rpc", lane="control",
                                   method="Coordinator.FetchFailed"):
                            group_call(sock, "Coordinator.FetchFailed",
                                       {"Map": e.task,
                                        "Reduce": reply["CReduce"],
                                        "WorkerId": worker_id,
                                        "Addr": e.addr})
                    except rpc.CoordinatorGone:
                        break
                    print(f"mrworker: fetch failed ({e}); reported, "
                          "retrying later", file=sys.stderr)
                    continue
                note_task(sp.elapsed_s)
                tasks_done += 1
                extra = net_deltas(before)
                extra["Addr"] = addr
                extra["Name"] = out_name
                try:
                    with open(os.path.join(cfg.workdir, out_name),
                              "rb") as f:
                        extra["Crc"] = zlib.crc32(f.read())
                except OSError:
                    extra["Crc"] = 0
                if not report_complete("Coordinator.RecieveReduceComplete",
                                       reply["CReduce"], extra):
                    break
                continue
            with _span("worker.reduce", lane="control", stats=task_s,
                       kind="reduce", task=reply["CReduce"]) as sp:
                if task_runner is not None:
                    task_runner.run_reduce(reducef, reply["CReduce"],
                                           reply["NMap"], cfg.workdir)
                else:
                    run_reduce_task(reducef, reply["CReduce"], reply["NMap"],
                                    cfg.workdir)
            note_task(sp.elapsed_s)
            tasks_done += 1
            if not report_complete("Coordinator.RecieveReduceComplete",
                                   reply["CReduce"]):
                break
        else:  # WAITING — sleep instead of the reference's RPC busy-poll
            time.sleep(cfg.wait_sleep_s)
