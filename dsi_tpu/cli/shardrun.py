"""One-command speculative shard job: coordinator + N shard workers.

The ``mrrun`` shape for streaming-shard jobs (ISSUE 15): plan the input
into newline-aligned cursor-range shards, run the shard-scheduler
coordinator IN-PROCESS (it is jax-free, and the driver reads its
speculation counters directly), spawn N ``shardworker`` subprocesses,
wait for every shard to commit exactly once, then merge the committed
per-shard outputs into ``mr-out-0``.

Chaos/straggler injection for grids and the bench A/B:

* ``--slow-worker I:SECONDS`` — worker I sleeps that long per advance
  slice (``DSI_SHARD_SLOW_S``): the forced straggler the backup
  dispatcher must fire on;
* ``--fault-worker I:POINT[:STEP]`` — worker I inherits
  ``DSI_FAULT_POINT``/``DSI_FAULT_STEP`` (``ckpt/fault.py``): a real
  ``os._exit`` mid-shard, whose takeover must resume from the chain;
* ``DSI_CHAOS_WORKER_KILL=p[,seed]`` passes through to every worker
  (each stamped with ``DSI_CHAOS_WORKER_INDEX`` for determinism).

``--resplit`` arms dynamic straggler re-split (ISSUE 16): instead of
one whole-range backup, the coordinator cuts the straggler's REMAINING
cursor range (from its live reported cursor) into newline-aligned
sub-shards and fans them out to idle workers — each sub-range is its
own first-commit-wins race, and the merge consumes the coordinator's
``final_outputs()`` (full-range file, or sub-range files in order).

``--check`` runs the sequential host oracle over the whole input and
byte-compares the merged output.  ``--stats-json`` dumps the
coordinator's ``spec_stats()`` (backup_dispatches, requeues, commits,
duplicate_commits, resume cursors) plus walls — the evidence surface
the CI smoke and the bench row assert on.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _fetch_window() -> int:
    from dsi_tpu.net.fetch import fetch_window_from_env

    return fetch_window_from_env()


def _parse_worker_knob(text: str, what: str):
    i, _, rest = text.partition(":")
    if not rest:
        raise SystemExit(f"shardrun: malformed {what}: {text!r} "
                         f"(want INDEX:VALUE)")
    return int(i), rest


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("files", nargs="+")
    p.add_argument("--engine", choices=("wordcount", "grep"),
                   default="wordcount")
    p.add_argument("--pattern", default="",
                   help="literal pattern (grep engine)")
    p.add_argument("--workers", type=int, default=3)
    p.add_argument("--shards", type=int, default=0,
                   help="shard count (default 2x workers)")
    p.add_argument("--workdir", default=".")
    p.add_argument("--chunk-bytes", type=int, default=1 << 20)
    p.add_argument("--nreduce", type=int, default=10)
    p.add_argument("--ckpt-every", type=int, default=32,
                   help="engine checkpoint cadence, confirmed steps")
    p.add_argument("--ckpt-secs", type=float, default=1.0,
                   help="worker-driven durable checkpoint cadence, "
                        "seconds (the resume-granularity knob)")
    p.add_argument("--progress-s", type=float, default=0.25,
                   help="worker heartbeat cadence, seconds")
    p.add_argument("--shard-timeout", type=float, default=10.0,
                   help="presumed-dead progress silence, seconds")
    p.add_argument("--spec-floor", type=float, default=2.0,
                   help="backup-dispatch staleness floor, seconds")
    p.add_argument("--no-spec", action="store_true",
                   help="disable speculative backup dispatch (the "
                        "bench A/B's control arm)")
    p.add_argument("--resplit", action="store_true",
                   help="dynamic straggler re-split: cut a straggling "
                        "attempt's REMAINING range into sub-shards for "
                        "idle workers instead of one whole-range backup")
    p.add_argument("--resplit-ways", type=int, default=2,
                   help="sub-shard count per re-split (default 2)")
    p.add_argument("--journal", default="",
                   help="commit journal (default <workdir>/shards."
                        "journal; exactly-once needs it)")
    p.add_argument("--slow-worker", default="",
                   help="I:SECONDS — straggler injection for worker I")
    p.add_argument("--fault-worker", default="",
                   help="I:POINT[:STEP] — DSI_FAULT_POINT kill for "
                        "worker I")
    p.add_argument("--hosts", action="store_true",
                   help="NET data plane (ISSUE 17): per-worker PRIVATE "
                        "workdirs, coordinator control plane on "
                        "localhost TCP, committed shard outputs served "
                        "from each worker's spool and fetched by the "
                        "driver over the stream transport — the share-"
                        "nothing multi-host shape on one machine")
    p.add_argument("--replicas", type=int, default=0,
                   help="replicated control plane (dsi_tpu/replica): "
                        "run the coordinator as an N-member Raft group "
                        "of replicad processes; workers discover the "
                        "leader via NotLeader redirects, and a dead "
                        "leader is an election away instead of job-over")
    p.add_argument("--kill-leader-after", type=float, default=0.0,
                   help="chaos (needs --replicas): SIGKILL the leader "
                        "this many seconds into the job, measure the "
                        "kill->served failover wall, respawn the "
                        "victim as a follower")
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--check", action="store_true",
                   help="byte-compare the merged output vs the "
                        "sequential host oracle")
    p.add_argument("--stats-json", default="")
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--out", default="mr-out-0",
                   help="merged output name (relative to workdir)")
    args = p.parse_args(argv)

    workdir = os.path.abspath(args.workdir)
    os.makedirs(workdir, exist_ok=True)
    files = [os.path.abspath(f) for f in args.files]
    n_shards = args.shards or 2 * args.workers
    if args.hosts and args.resplit:
        p.error("--hosts does not support --resplit (the sub-range "
                "merge reads committed files from a shared directory)")
    if args.replicas and args.hosts:
        p.error("--hosts does not support --replicas yet (the driver "
                "reads the coordinator's location registry in-process)")
    if args.replicas and args.replicas < 2:
        p.error("--replicas wants >= 2 (3 tolerates one kill)")
    if args.kill_leader_after and not args.replicas:
        p.error("--kill-leader-after needs --replicas")
    journal = os.path.abspath(args.journal) if args.journal \
        else os.path.join(workdir, "shards.journal")

    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr import shards as sh
    from dsi_tpu.mr.coordinator import Coordinator

    env = dict(os.environ)
    env.setdefault("DSI_MR_SOCKET", os.path.join(workdir, "mr.sock"))
    # Workers run with cwd=workdir; make the package importable there
    # even when it is not installed (the test-sandbox case).
    import dsi_tpu as _pkg

    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(_pkg.__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    if args.trace_dir:
        trace_dir = os.path.abspath(args.trace_dir)
        env["DSI_TRACE_DIR"] = trace_dir
        from dsi_tpu.obs import configure_tracing, trace_event

        configure_tracing(trace_dir=trace_dir, basename="trace-shardrun")
        trace_event("shardrun.start", engine=args.engine,
                    workers=args.workers, shards=n_shards,
                    files=len(files))

    # Every shard worker runs a device engine, so each needs a chip of
    # its own (cli/chips.py; this process stays off JAX, and counts the
    # chips before the first worker starts).  With the CPU asked for by
    # name there is no chip to share and no limit.
    from dsi_tpu.cli.chips import chip_env, lost_chip, plan_device_workers

    slots, n_chips = plan_device_workers(args.workers, env, "shardrun")
    if None in slots:
        print(f"shardrun: --workers {args.workers} device workers but "
              f"only {n_chips} chip(s): one process per chip. Lower "
              "--workers, or set JAX_PLATFORMS=cpu to run the engines on "
              "the CPU on purpose.", file=sys.stderr)
        return 1

    plan = sh.plan_shards(files, n_shards)
    if not plan:
        print("shardrun: empty input", file=sys.stderr)
        return 1
    knobs = {"engine": args.engine, "chunk_bytes": args.chunk_bytes,
             "n_reduce": args.nreduce, "ckpt_every": args.ckpt_every,
             "ckpt_secs": args.ckpt_secs}
    if args.engine == "grep":
        if not args.pattern:
            p.error("--engine grep requires --pattern")
        knobs["pattern"] = args.pattern
    cfg = JobConfig(workdir=workdir,
                    socket_path=("tcp:127.0.0.1:0" if args.hosts
                                 else env["DSI_MR_SOCKET"]),
                    journal_path=journal,
                    shard_timeout_s=args.shard_timeout,
                    spec_backup=not args.no_spec,
                    spec_floor_s=args.spec_floor,
                    spec_resplit=args.resplit,
                    spec_resplit_ways=args.resplit_ways,
                    shard_progress_s=args.progress_s,
                    net_shuffle=args.hosts,
                    net_fetch_window=_fetch_window())
    group = None
    failover = None
    if args.replicas:
        # Replicated control plane: no in-process coordinator — an
        # N-member replicad group owns the task table, and this driver
        # talks to whoever leads.  Fresh-run hygiene the single-node
        # coordinator does itself (clearing a PREVIOUS job's outputs)
        # happens here: the leader's resuming check sees the replica
        # journal, which the appliers create at boot, so it never
        # clears — exactly what failover needs and fresh runs don't.
        if not os.path.exists(os.path.join(workdir,
                                           "replica-0.journal")):
            for name in os.listdir(workdir):
                if name.startswith(("mr-out-", "mr-shard-out-")):
                    try:
                        os.remove(os.path.join(workdir, name))
                    except OSError:
                        pass
        from dsi_tpu.replica.driver import ReplicaGroup

        group = ReplicaGroup(
            "shard", workdir, replicas=args.replicas, files=files,
            n_shards=n_shards, knobs=knobs,
            config={"shard_timeout_s": args.shard_timeout,
                    "spec_backup": not args.no_spec,
                    "spec_floor_s": args.spec_floor,
                    "spec_resplit": args.resplit,
                    "spec_resplit_ways": args.resplit_ways,
                    "shard_progress_s": args.progress_s},
            env=env)
        env["DSI_MR_SOCKET"] = group.spec
        coord = group
    else:
        coord = Coordinator(files, 0, cfg, shard_plan=plan,
                            shard_opts={"knobs": knobs})
        coord.serve()
    if args.hosts:
        # Workers dial the coordinator's REAL TCP port, not a path.
        env["DSI_MR_SOCKET"] = coord.address()

    slow = _parse_worker_knob(args.slow_worker, "--slow-worker") \
        if args.slow_worker else None
    fault = _parse_worker_knob(args.fault_worker, "--fault-worker") \
        if args.fault_worker else None

    def chip_of(i: int) -> int:
        """Worker ``i``'s chip.  A replacement past the original fleet
        takes the chip of a worker that has exited (the one whose death
        made the replacement necessary), never one still held."""
        while i >= len(slots):
            dead = [j for j, w in enumerate(workers)
                    if w.poll() is not None and j not in retired]
            j = dead[0] if dead else i % args.workers
            retired.add(j)
            slots.append(slots[j])
        return slots[i]

    def worker_dir(i: int) -> str:
        """--hosts: each worker's PRIVATE workdir (cwd + spool); the
        shared-dir plane runs every worker in the job workdir."""
        if not args.hosts:
            return workdir
        wdir = os.path.join(workdir, f"worker-{i}")
        os.makedirs(wdir, exist_ok=True)
        return wdir

    def worker_env(i: int) -> dict:
        we = chip_env(env, chip_of(i), n_chips)
        we["DSI_CHAOS_WORKER_INDEX"] = str(i)
        if args.hosts:
            we["DSI_NET_SPOOL"] = worker_dir(i)
        if slow is not None and i == slow[0]:
            we["DSI_SHARD_SLOW_S"] = slow[1]
        if fault is not None and i == fault[0]:
            point, _, step_n = fault[1].partition(":")
            we["DSI_FAULT_POINT"] = point
            if step_n:
                we["DSI_FAULT_STEP"] = step_n
        return we

    worker_cmd = [sys.executable, "-m", "dsi_tpu.cli.shardworker",
                  "--progress-s", str(args.progress_s)]
    t0 = time.monotonic()
    deadline = t0 + args.timeout
    workers: list = []
    retired: set = set()  # dead workers whose chip a replacement took
    envs = [worker_env(i) for i in range(args.workers)]
    workers.extend(subprocess.Popen(worker_cmd, env=envs[i],
                                    cwd=worker_dir(i))
                   for i in range(args.workers))
    dirs = [worker_dir(i) for i in range(args.workers)]
    next_idx = args.workers
    # A worker that died crashed (chaos/fault kill) is respawned WITHOUT
    # its kill knobs — the grid's "the fleet recovers" arm; budget keeps
    # a truly broken setup from spinning.
    respawn_budget = max(8, 2 * len(plan))
    fetched: set = set()
    net_io: dict = {}  # driver-side fetch attribution (hosts mode)
    rc = 0

    def fetch_committed() -> bool:
        """--hosts: pull each newly committed shard's bytes from the
        winner's spool into the shared workdir the moment its location
        registers (the merge below then reads the exact same paths the
        shared-dir plane commits to).  A dead server means the only
        copy is gone: ``refetch_shard`` forgets the commit and a
        REPLACEMENT worker re-executes the producer — lingering
        workers left the request loop, so one is spawned (clean env:
        the chaos/fault knobs that killed the original stay off).
        Returns False when the respawn budget is exhausted."""
        nonlocal next_idx, respawn_budget
        import zlib

        from dsi_tpu.net.fetch import (FetchFailure, FetchPipeline,
                                       fetch_partition)
        from dsi_tpu.utils.atomicio import atomic_write

        todo = [(sid, loc) for sid, loc in
                sorted(coord.final_locations().items())
                if sid not in fetched]
        if not todo:
            return True

        def commit(sid, a, name, crc, raw) -> None:
            if crc and zlib.crc32(raw) != crc:
                raise FetchFailure(sid, a, name,
                                   ValueError("crc mismatch"))
            with atomic_write(os.path.join(workdir,
                                           f"mr-shard-out-{sid}"),
                              mode="wb") as f:
                f.write(raw)
            fetched.add(sid)

        def reexecute(sid, e) -> bool:
            nonlocal next_idx, respawn_budget
            print(f"shardrun: shard {sid} output fetch failed "
                  f"({e}); re-executing", file=sys.stderr)
            coord.refetch_shard(sid)
            if respawn_budget <= 0:
                print("shardrun: workers failing repeatedly; "
                      "giving up", file=sys.stderr)
                return False
            respawn_budget -= 1
            i = next_idx
            next_idx += 1
            clean = {k: v for k, v in worker_env(i).items()
                     if k not in ("DSI_FAULT_POINT",
                                  "DSI_FAULT_STEP",
                                  "DSI_CHAOS_WORKER_KILL")}
            envs.append(clean)
            dirs.append(worker_dir(i))
            workers.append(subprocess.Popen(worker_cmd, env=clean,
                                            cwd=dirs[i]))
            return True

        window = cfg.net_fetch_window
        if window <= 1 or len(todo) == 1:
            for sid, (a, name, crc) in todo:
                try:
                    raw = fetch_partition(a, name, stats=net_io,
                                          timeout=cfg.net_fetch_timeout_s)
                    commit(sid, a, name, crc, raw)
                except FetchFailure as e:
                    return reexecute(sid, e)
            return True
        # Overlapped collection (ISSUE 18): prefetch the committed
        # shards' payloads while earlier ones CRC-check and write.
        locs = {sid: loc for sid, loc in todo}
        pipe = FetchPipeline(
            [(sid, a, name) for sid, (a, name, crc) in todo],
            window=window, stats=net_io,
            timeout=cfg.net_fetch_timeout_s)
        try:
            for sid, raw in pipe:
                a, name, crc = locs[sid]
                commit(sid, a, name, crc, raw)
        except FetchFailure as e:
            return reexecute(e.task, e)
        return True

    try:
        while True:
            if args.hosts and not fetch_committed():
                rc = 1
                break
            if group is not None and args.kill_leader_after > 0 \
                    and failover is None \
                    and time.monotonic() - t0 >= args.kill_leader_after:
                print("shardrun: chaos: kill -9 the leader replica",
                      file=sys.stderr)
                from dsi_tpu.mr import rpc as _rpc

                try:
                    failover = group.kill_leader()
                except _rpc.CoordinatorGone as e:
                    print(f"shardrun: failover FAILED: {e}",
                          file=sys.stderr)
                    rc = 1
                    break
                print(f"shardrun: failover in "
                      f"{failover['failover_s']}s (term "
                      f"{failover['old_term']} -> "
                      f"{failover['new_term']}, leader "
                      f"{failover['killed_index']} -> "
                      f"{failover['new_index']})", file=sys.stderr)
            if coord.done() and (not args.hosts
                                 or len(fetched) == len(plan)
                                 or coord.spec_stats()["job_failed"]):
                break
            if time.monotonic() > deadline:
                print("shardrun: job exceeded --timeout; killing",
                      file=sys.stderr)
                rc = 1
                break
            for i, w in enumerate(workers):
                if w.poll() is not None and w.returncode != 0 \
                        and not coord.done():
                    if lost_chip(w, "shardrun"):
                        rc = 1
                        break
                    if respawn_budget <= 0:
                        print("shardrun: workers failing repeatedly; "
                              "giving up", file=sys.stderr)
                        rc = 1
                        break
                    respawn_budget -= 1
                    clean = {k: v for k, v in envs[i].items()
                             if k not in ("DSI_FAULT_POINT",
                                          "DSI_FAULT_STEP",
                                          "DSI_CHAOS_WORKER_KILL")}
                    workers[i] = subprocess.Popen(worker_cmd, env=clean,
                                                  cwd=dirs[i])
            if rc:
                break
            time.sleep(0.1)
    finally:
        if group is not None:
            try:
                run_stats = coord.spec_stats()
            except Exception as e:  # noqa: BLE001 — group dead late
                print(f"shardrun: replica group unreachable at exit: "
                      f"{e}", file=sys.stderr)
                run_stats = {"job_failed": True, "shards": len(plan)}
                rc = rc or 1
        else:
            run_stats = coord.spec_stats()
        if args.hosts:
            run_stats.update(coord.net_stats())
            # The shard plane's only remote reads are the DRIVER's
            # output fetches — fold their attribution in.
            for k in ("net_fetches", "net_local_reads", "net_bytes_raw",
                      "net_bytes_wire", "net_fetch_failures"):
                run_stats[k] = run_stats.get(k, 0) + net_io.get(k, 0)
            for k in ("net_fetch_wait_s", "net_overlap_s"):
                run_stats[k] = round(run_stats.get(k, 0.0)
                                     + net_io.get(k, 0.0), 6)
            run_stats["net_prefetch_window"] = max(
                run_stats.get("net_prefetch_window", 0),
                net_io.get("net_prefetch_window", 0),
                cfg.net_fetch_window)
            wire = run_stats["net_bytes_wire"]
            run_stats["net_ratio"] = round(
                run_stats["net_bytes_raw"] / wire, 3) if wire else 0.0
        run_stats["wall_s"] = round(time.monotonic() - t0, 3)
        if group is not None:
            run_stats["replicas"] = args.replicas
            run_stats["replica_kills"] = group.kills
            if failover is not None:
                run_stats["replica_failover_s"] = failover["failover_s"]
                run_stats["replica_old_term"] = failover["old_term"]
                run_stats["replica_new_term"] = failover["new_term"]
        # A re-split shard commits as SUB-RANGE files, not one full-
        # range file: the coordinator knows the committed layout.
        if group is not None:
            out_paths = []
            if rc == 0 and not run_stats.get("job_failed"):
                try:
                    out_paths = coord.final_outputs()
                except Exception as e:  # noqa: BLE001
                    print(f"shardrun: could not read final outputs "
                          f"from the group: {e}", file=sys.stderr)
                    rc = 1
        else:
            out_paths = coord.final_outputs()
        coord.close()
        for w in workers:
            if w.poll() is None:
                w.terminate()
        for w in workers:
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()

    if rc == 0 and run_stats.get("job_failed"):
        print("shardrun: job failed (shard attempts exhausted)",
              file=sys.stderr)
        rc = 1

    merged_path = os.path.join(workdir, args.out)
    if rc == 0 and args.hosts:
        # Share-nothing audit: the ONLY job artifacts in the shared
        # workdir must be the ones the DRIVER fetched and wrote — a
        # worker-written mr-* / .part / .shards entry here means some
        # path escaped the private per-worker dirs and the run silently
        # leaned on the shared-directory assumption again.
        expect = {f"mr-shard-out-{sid}" for sid in fetched}
        leaked = [n for n in os.listdir(workdir)
                  if (n.startswith("mr-") or n.endswith(".part")
                      or n == ".shards")
                  and n not in expect and n != args.out]
        if leaked:
            print("shardrun: SHARE-NOTHING VIOLATION: worker artifacts "
                  f"in shared workdir: {sorted(leaked)[:8]}",
                  file=sys.stderr)
            rc = 1
    if rc == 0:
        from dsi_tpu.utils.atomicio import atomic_write

        payloads = []
        for path in out_paths:
            try:
                with open(path, "rb") as f:
                    payloads.append(f.read())
            except OSError as e:
                print(f"shardrun: missing committed shard output: {e}",
                      file=sys.stderr)
                rc = 1
                break
        if rc == 0:
            merged = (sh.merge_grep(payloads) if args.engine == "grep"
                      else sh.merge_wordcount(payloads))
            with atomic_write(merged_path, mode="wb") as f:
                f.write(merged)
            run_stats["merged_bytes"] = len(merged)
            # Every shard committed durably: the checkpoint chains are
            # dead weight now (a resume keys off the journal, which
            # says there is nothing left to run).
            import shutil

            shutil.rmtree(os.path.join(workdir, ".shards"),
                          ignore_errors=True)
            if args.hosts:
                # Spools served their purpose once the merge is durable.
                for d in dirs:
                    shutil.rmtree(d, ignore_errors=True)

    if args.stats_json:
        # dsicheck: allow[raw-write] bench/CI parse surface, not durable state
        with open(args.stats_json, "w", encoding="utf-8") as f:
            json.dump(run_stats, f, sort_keys=True, indent=1)
    if args.trace_dir:
        from dsi_tpu.obs import flush_tracing, trace_event

        trace_event("shardrun.exit", rc=rc,
                    backups=run_stats.get("backup_dispatches"),
                    commits=run_stats.get("commits"))
        flush_tracing()
    print(f"shardrun: {len(plan)} shards, "
          f"{run_stats.get('commits', 0)} commits, "
          f"{run_stats.get('backup_dispatches', 0)} backups, "
          f"{run_stats.get('requeues', 0)} requeues, "
          f"{run_stats.get('duplicate_commits', 0)} duplicate commits, "
          f"wall {run_stats.get('wall_s')}s", file=sys.stderr)
    if run_stats.get("resplits"):
        print(f"shardrun: {run_stats['resplits']} resplits -> "
              f"{run_stats.get('subshard_dispatches', 0)} sub-shard "
              f"dispatches, {run_stats.get('subshard_commits', 0)} "
              f"sub commits, {run_stats.get('split_shards', 0)} shards "
              f"resolved split", file=sys.stderr)
    if rc != 0:
        return rc

    if args.check:
        if args.engine == "grep":
            from dsi_tpu.parallel.grepstream import grep_host_oracle

            # format_grep drops topk exactly like merge_grep, so the
            # oracle bytes and the merged bytes share one shape.
            want = sh.format_grep(grep_host_oracle(
                sh.read_stream_range(files, 0,
                                     sh.stream_total_bytes(files)),
                args.pattern))
        else:
            want = sh.format_wordcount_counts(sh.wordcount_host_oracle(
                sh.read_stream_range(files, 0,
                                     sh.stream_total_bytes(files))))
        with open(merged_path, "rb") as f:
            got = f.read()
        if got != want:
            print("shardrun: PARITY FAILURE vs sequential oracle",
                  file=sys.stderr)
            return 2
        print("shardrun: parity OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
