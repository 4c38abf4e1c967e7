"""One-command MapReduce job runner: coordinator + N workers + wait.

The reference requires manual orchestration — one terminal for
``mrcoordinator``, more for each ``mrworker`` (``main/test-mr.sh:36-45`` is
that choreography scripted).  This runs the whole job as child processes of
one command, with the same process-level semantics (separate interpreters,
the real RPC control plane, the shared-filesystem data plane — NOT threads),
and exits when the coordinator does.

Usage:
    python -m dsi_tpu.cli.mrrun [--workers 3] [--nreduce 10]
        [--backend host|tpu|native] [--workdir DIR] [--task-timeout S]
        [--journal FILE [--resume]] [--check] <app> inputfiles...

``--check`` additionally runs the sequential oracle and byte-compares the
merged output (sort mr-out-* | grep ., test-mr.sh:52-53), exiting non-zero
on a parity failure.

``--backend tpu`` starts one device worker per chip (``cli/chips.py``) and
runs the remaining ``--workers`` as reduce-only host helpers, so every map
task runs on a device; respawned workers keep their slot's role.  A device
worker that can claim no chip (missing, or held by another process) ends
the job at once, non-zero, with its message on stderr: it is not
respawned.  This process never imports JAX: a parent that holds the chip
starves its children.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from dsi_tpu.cli.chips import chip_env, lost_chip, plan_device_workers
from dsi_tpu.obs import configure_tracing, flush_tracing, trace_event


def _worker_fleet(args, app: str, env: dict):
    """Per-slot ``(cmd, env)`` for the job's workers.  Host and native
    fleets are homogeneous.  A ``tpu`` fleet gets one device worker per
    chip, each pinned to its chip; the other slots are reduce-only host
    helpers (``DSI_MR_REDUCE_ONLY``), the shape scripts/test_mr.sh used
    to build by hand.  With the CPU asked for by name every slot is a
    device-backend worker (no chip to share)."""
    base = [sys.executable, "-m", "dsi_tpu.cli.mrworker", "--backend"]
    if args.backend != "tpu":
        return [(base + [args.backend, app], env)] * args.workers
    slots, n_chips = plan_device_workers(args.workers, env,
                                         "mrrun --backend tpu")
    fleet = []
    for chip in slots:
        if chip is None:
            helper = dict(env)
            helper["DSI_MR_REDUCE_ONLY"] = "1"
            fleet.append((base + ["host", app], helper))
        else:
            fleet.append((base + ["tpu", app],
                          chip_env(env, chip, n_chips)))
    return fleet


def _spawn_worker(cmd: list, env: dict, workdir: str):
    """Start one worker of the fleet; its role is its ``--backend``."""
    proc = subprocess.Popen(cmd, env=env, cwd=workdir)
    trace_event("spawn", lane="launch", pid=proc.pid,
                role="worker:" + cmd[cmd.index("--backend") + 1])
    return proc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("app")
    p.add_argument("files", nargs="+")
    p.add_argument("--workers", type=int, default=3)
    p.add_argument("--nreduce", type=int, default=10)
    p.add_argument("--backend", choices=("host", "tpu", "native"),
                   default="host")
    p.add_argument("--workdir", default=".")
    p.add_argument("--task-timeout", type=float, default=10.0)
    p.add_argument("--journal", default="",
                   help="coordinator checkpoint journal (resume support)")
    p.add_argument("--resume", action="store_true",
                   help="assert this run resumes a crashed job from "
                        "--journal: completed tasks replay as DONE (their "
                        "output files were already atomically committed), "
                        "in-progress tasks hand out afresh.  Requires "
                        "--journal and errors if the journal file does "
                        "not exist (nothing to resume is a caller "
                        "mistake, not a fresh start).  NOTE the "
                        "coordinator resumes from any EXISTING --journal "
                        "either way — this flag adds the assertion, and "
                        "mrrun warns when resuming implicitly without it")
    p.add_argument("--replicas", type=int, default=0,
                   help="replicated control plane (dsi_tpu/replica): "
                        "run the coordinator as an N-member Raft group; "
                        "workers follow NotLeader redirects, so a dead "
                        "leader is an election, not a dead job")
    p.add_argument("--kill-leader-after", type=float, default=0.0,
                   help="chaos (needs --replicas): SIGKILL the leader "
                        "this many seconds in; measure failover")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="whole-job wall budget, seconds")
    p.add_argument("--net", action="store_true",
                   help="NET data plane (ISSUE 17): per-worker PRIVATE "
                        "workdirs, worker-served shuffle over localhost "
                        "TCP, coordinator control plane on TCP — the "
                        "share-nothing harness (no worker reads any "
                        "other process's directory)")
    p.add_argument("--fetch-window", type=int, default=0,
                   help="reduce-side prefetch window (ISSUE 18): fetches "
                        "in flight + buffered while the consumer decodes; "
                        "1 = the serial loop bit-identically.  0 (default) "
                        "defers to DSI_NET_FETCH_WINDOW (default 4)")
    p.add_argument("--stats-json", default="",
                   help="dump the coordinator's net_stats() (net mode) "
                        "— the CI smoke's and bench row's evidence "
                        "surface")
    p.add_argument("--check", action="store_true",
                   help="run the sequential oracle and verify parity")
    p.add_argument("--trace-dir", default=None,
                   help="unified job trace (dsi_tpu/obs): the "
                        "coordinator and every worker inherit "
                        "DSI_TRACE_DIR and each commits a "
                        "trace-<pid>.json/.jsonl at exit (assign/"
                        "complete/requeue events, per-task spans, "
                        "heartbeat ages); render the whole directory "
                        "with scripts/tracecat.py")
    args = p.parse_args(argv)

    workdir = os.path.abspath(args.workdir)
    os.makedirs(workdir, exist_ok=True)
    files = [os.path.abspath(f) for f in args.files]
    app = args.app
    if os.sep in app or app.endswith(".py"):
        app = os.path.abspath(app)  # workers run with cwd=workdir
    journal = os.path.abspath(args.journal) if args.journal else ""
    if args.resume:
        if not journal:
            p.error("--resume requires --journal")
        if not os.path.exists(journal):
            print(f"mrrun: --resume: journal not found: {journal}",
                  file=sys.stderr)
            return 1
    elif journal and os.path.exists(journal):
        # The coordinator keys resume off journal existence alone; say
        # so out loud when the caller did not ask for it — a fresh job
        # against a stale journal would silently skip completed tasks.
        print(f"mrrun: existing journal {journal} will be RESUMED "
              "(pass --resume to assert this, or delete the journal "
              "for a fresh job)", file=sys.stderr)
    env = dict(os.environ)
    env.setdefault("DSI_MR_SOCKET", os.path.join(workdir, "mr.sock"))
    if args.trace_dir:
        trace_dir = os.path.abspath(args.trace_dir)
        env["DSI_TRACE_DIR"] = trace_dir
        # mrrun's own lane records the job lifecycle; children commit
        # their trace-<pid>.* files at exit via the env inheritance.
        configure_tracing(trace_dir=trace_dir, basename="trace-mrrun")
        trace_event("mrrun.start", lane="launch", app=args.app,
                    workers=args.workers, nreduce=args.nreduce,
                    files=len(files))

    # Clear stale oracle files so a failed job can't pass --check against
    # a previous run's ground truth (the reference harness's rm,
    # test-mr.sh:54).  mr-out-* lifecycle belongs to the coordinator alone
    # (Coordinator.__init__ clears stale partitions with the same
    # resume-awareness) — one owner, one predicate.
    for name in os.listdir(workdir):
        if name.startswith("mr-correct"):
            try:
                os.remove(os.path.join(workdir, name))
            except OSError:
                pass

    # Decided before anything is spawned: where a probe child counts the
    # chips it must exit before the first worker starts.
    fleet = _worker_fleet(args, app, env)

    if args.replicas:
        if args.net:
            p.error("--net does not support --replicas yet")
        if args.replicas < 2:
            p.error("--replicas wants >= 2 (3 tolerates one kill)")
        rc = _replica_job(args, workdir, files, fleet, env)
        if args.trace_dir:
            trace_event("mrrun.exit", rc=rc, replicas=args.replicas)
            flush_tracing()
        if rc != 0:
            return rc
        return _parity_check(args, workdir, files) if args.check else 0
    if args.kill_leader_after:
        p.error("--kill-leader-after needs --replicas")

    if args.net:
        rc = _net_job(args, workdir, files, fleet, env, journal)
        if args.trace_dir:
            trace_event("mrrun.exit", rc=rc, net=1)
            flush_tracing()
        if rc != 0:
            return rc
        return _parity_check(args, workdir, files) if args.check else 0

    # Children run WITH cwd=workdir — the reference's data plane is "the
    # working directory" (mr-X-Y / mr-out-R relative paths), same as the
    # harness's sandbox cd (test-mr.sh:13-16).
    coord_cmd = [sys.executable, "-m", "dsi_tpu.cli.mrcoordinator",
                 "--nreduce", str(args.nreduce),
                 "--task-timeout", str(args.task_timeout)]
    if journal:
        coord_cmd += ["--journal", journal]
    coord = subprocess.Popen(coord_cmd + files, env=env, cwd=workdir)
    trace_event("spawn", lane="launch", role="coordinator", pid=coord.pid)
    deadline = time.monotonic() + args.timeout
    time.sleep(1.0)  # socket-creation grace (test-mr.sh:39-40)
    trace_event("coordinator_up", lane="launch")

    spawn = time.monotonic()
    workers = [_spawn_worker(cmd, wenv, workdir) for cmd, wenv in fleet]
    spawned_at = [spawn] * len(workers)
    # A worker that dies crashed (non-zero) is respawned, but an app that
    # can never start (typo'd name, broken plugin) must not burn the whole
    # wall budget spawning doomed interpreters 3/sec.  Two detectors:
    #
    # * instant-death streak — every death so far was < _INSTANT_S old,
    #   with the SAME exit code, and the job has made zero progress (no
    #   mr-* data-plane file exists): after a streak covering the whole
    #   fleet twice over, the app provably cannot start, and waiting out
    #   the old ~26-respawn budget (~26 x a 1-3 s interpreter startup)
    #   just burned the wall clock.  Seconds, not
    #   minutes.  Any slow death, differing exit code, or completed task
    #   resets the streak — a legitimate crash-app run (which dies
    #   mid-task AFTER committing output) never trips it.
    # * total budget — scaled to job size, as before: a legitimate
    #   crash-app run kills at most ~one worker per task.
    respawn_budget = max(16, 2 * (len(files) + args.nreduce))
    instant_streak = 0
    streak_code = None
    # High enough that a fault-injecting app (crash exit prob p) has only
    # ~p^cap odds of a spurious all-instant-death streak before its first
    # commit; low enough to fail a broken app in a few respawn rounds.
    streak_cap = max(6, 2 * args.workers + 2)
    _INSTANT_S = 5.0

    def job_progressed() -> bool:
        """Any data-plane artifact (mr-X-Y intermediate or mr-out-R)
        means at least one task body ran — the app starts fine."""
        return any(n.startswith("mr-") and not n.startswith("mr-correct")
                   for n in os.listdir(workdir))

    rc = 0
    try:
        while coord.poll() is None:
            if time.monotonic() > deadline:
                print("mrrun: job exceeded --timeout; killing",
                      file=sys.stderr)
                rc = 1
                break
            # Workers are expendable (the 10 s requeue covers crashes); the
            # crash app even kills them on purpose — respawn CRASHED
            # workers to keep the fleet at full strength, as test_mr.sh's
            # respawner does.  A zero exit is end-of-job, not a crash.
            for i, w in enumerate(workers):
                if (w.poll() is not None and w.returncode != 0
                        and coord.poll() is None):
                    if lost_chip(w, "mrrun"):
                        rc = 1
                        break
                    lifetime = time.monotonic() - spawned_at[i]
                    if lifetime >= _INSTANT_S:
                        instant_streak, streak_code = 0, None
                    elif streak_code == w.returncode:
                        instant_streak += 1
                    else:
                        instant_streak, streak_code = 1, w.returncode
                    if (instant_streak >= streak_cap
                            and not job_progressed()):
                        print("mrrun: workers failing repeatedly "
                              f"({instant_streak} consecutive instant "
                              f"deaths, rc={streak_code}, zero tasks "
                              "completed); giving up", file=sys.stderr)
                        rc = 1
                        break
                    if respawn_budget <= 0:
                        print("mrrun: workers failing repeatedly; giving up",
                              file=sys.stderr)
                        rc = 1
                        break
                    respawn_budget -= 1
                    spawned_at[i] = time.monotonic()
                    workers[i] = _spawn_worker(*fleet[i], workdir)
            if rc:
                break
            time.sleep(0.3)
    finally:
        for proc in [coord] + workers:
            if proc.poll() is None:
                proc.terminate()
        for proc in [coord] + workers:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    if rc == 0 and coord.returncode not in (0, None):
        print(f"mrrun: coordinator exited rc={coord.returncode}",
              file=sys.stderr)
        rc = 1
    if args.trace_dir:
        trace_event("mrrun.exit", rc=rc)
        flush_tracing()
        print(f"mrrun: traces in {args.trace_dir} (render: python "
              f"scripts/tracecat.py {args.trace_dir})", file=sys.stderr)
    if rc != 0:
        return rc
    if args.check:
        return _parity_check(args, workdir, files)
    return 0


def _parity_check(args, workdir: str, files: list) -> int:
    """Run the sequential oracle and byte-compare the merged mr-out-*
    lines (sort mr-out-* | grep ., test-mr.sh:52-53)."""
    from dsi_tpu.mr.plugin import load_plugin
    from dsi_tpu.mr.sequential import run_sequential

    # Oracle twins: fault-injecting / device apps check against their
    # deterministic host equivalents (scripts/test_mr.sh:32-43).
    oracle_app = {"crash": "nocrash", "tpu_wc": "wc",
                  "tpu_indexer": "indexer",
                  "tpu_grep": "grep"}.get(args.app, args.app)
    mapf, reducef = load_plugin(oracle_app)
    oracle_out = os.path.join(workdir, "mr-correct.txt")
    run_sequential(mapf, reducef, files, oracle_out)
    got: list = []
    for r in range(args.nreduce):
        path = os.path.join(workdir, f"mr-out-{r}")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                got.extend(l for l in f if l.strip())
    with open(oracle_out, encoding="utf-8") as f:
        want = sorted(l for l in f if l.strip())
    if sorted(got) != want:
        print("mrrun: PARITY FAILURE vs sequential oracle",
              file=sys.stderr)
        return 2
    print("mrrun: parity OK", file=sys.stderr)
    return 0


def _replica_job(args, workdir: str, files: list, fleet: list,
                 env: dict) -> int:
    """Classic map/reduce under the replicated control plane: the
    coordinator is an N-member ``replicad`` group, workers dial the
    whole group (``DSI_MR_SOCKET`` comma list) and follow redirects,
    and an optional mid-job ``kill -9`` of the leader exercises the
    failover the single-coordinator plane cannot survive."""
    import json as _json

    from dsi_tpu.mr import rpc as _rpc
    from dsi_tpu.replica.driver import ReplicaGroup

    env = dict(env)
    # replicad + workers must import the package from any cwd.
    import dsi_tpu as _pkg

    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(_pkg.__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    # Fresh-run hygiene the leader coordinator skips in replica mode
    # (its resuming check sees the always-present replica journal).
    if not os.path.exists(os.path.join(workdir, "replica-0.journal")):
        for name in os.listdir(workdir):
            if name.startswith("mr-out-"):
                try:
                    os.remove(os.path.join(workdir, name))
                except OSError:
                    pass
    group = ReplicaGroup(
        "classic", workdir, replicas=args.replicas, files=files,
        n_reduce=args.nreduce,
        config={"n_reduce": args.nreduce,
                "task_timeout_s": args.task_timeout},
        env=env)
    extra = {"DSI_MR_SOCKET": group.spec, "PYTHONPATH": env["PYTHONPATH"]}
    fleet = [(cmd, {**wenv, **extra}) for cmd, wenv in fleet]
    t0 = time.monotonic()
    deadline = t0 + args.timeout
    workers = [subprocess.Popen(cmd, env=wenv, cwd=workdir)
               for cmd, wenv in fleet]
    respawn_budget = max(16, 2 * (len(files) + args.nreduce))
    failover = None
    rc = 0
    try:
        while True:
            if time.monotonic() > deadline:
                print("mrrun: job exceeded --timeout; killing",
                      file=sys.stderr)
                rc = 1
                break
            if args.kill_leader_after > 0 and failover is None \
                    and time.monotonic() - t0 >= args.kill_leader_after:
                print("mrrun: chaos: kill -9 the leader replica",
                      file=sys.stderr)
                try:
                    failover = group.kill_leader()
                except _rpc.CoordinatorGone as e:
                    print(f"mrrun: failover FAILED: {e}",
                          file=sys.stderr)
                    rc = 1
                    break
                print(f"mrrun: failover in {failover['failover_s']}s "
                      f"(term {failover['old_term']} -> "
                      f"{failover['new_term']})", file=sys.stderr)
            if group.done():
                break
            for i, w in enumerate(workers):
                if w.poll() is not None and w.returncode != 0:
                    if lost_chip(w, "mrrun"):
                        rc = 1
                        break
                    if respawn_budget <= 0:
                        print("mrrun: workers failing repeatedly; "
                              "giving up", file=sys.stderr)
                        rc = 1
                        break
                    respawn_budget -= 1
                    workers[i] = subprocess.Popen(fleet[i][0],
                                                  env=fleet[i][1],
                                                  cwd=workdir)
            if rc:
                break
            time.sleep(0.2)
    finally:
        run_stats = {"wall_s": round(time.monotonic() - t0, 3),
                     "replicas": args.replicas,
                     "replica_kills": group.kills}
        try:
            run_stats.update(group.spec_stats())
        except _rpc.CoordinatorGone:
            pass
        if failover is not None:
            run_stats["replica_failover_s"] = failover["failover_s"]
            run_stats["replica_old_term"] = failover["old_term"]
            run_stats["replica_new_term"] = failover["new_term"]
        group.close()
        for w in workers:
            if w.poll() is None:
                w.terminate()
        for w in workers:
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()
    if args.stats_json:
        # dsicheck: allow[raw-write] bench/CI parse surface, not
        # durable state
        with open(args.stats_json, "w", encoding="utf-8") as f:
            _json.dump(run_stats, f, sort_keys=True, indent=1)
    print(f"mrrun: replicated run done rc={rc} "
          f"(c_map={run_stats.get('c_map')}, "
          f"c_reduce={run_stats.get('c_reduce')}, "
          f"wall {run_stats['wall_s']}s)", file=sys.stderr)
    return rc


def _net_job(args, workdir: str, files: list, fleet: list,
             env: dict, journal: str = "") -> int:
    """The share-nothing job (``--net``): coordinator in-process on
    localhost TCP, each worker in its own PRIVATE workdir serving its
    spool over a partition server, the shuffle and the final output
    collection both over the stream transport.

    The driver fetches each ``mr-out-<r>`` the moment its completion
    registers a location, verifying the completion CRC; a dead server
    at THAT stage triggers ``refetch_reduce`` (the reduce re-executes
    on a fresh worker — lingering workers left the task loop, so one is
    spawned) and, transitively, ``Coordinator.FetchFailed`` re-executes
    any lost producers.  Exit asserts share-nothing really held: the
    shared workdir carries only driver-written outputs."""
    import shutil
    import zlib

    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.coordinator import Coordinator
    from dsi_tpu.net.fetch import FetchFailure, fetch_partition
    from dsi_tpu.utils.atomicio import atomic_write

    cfg = JobConfig(n_reduce=args.nreduce, workdir=workdir,
                    socket_path="tcp:127.0.0.1:0",
                    task_timeout_s=args.task_timeout,
                    net_shuffle=True,
                    journal_path=journal)
    coord = Coordinator(files, args.nreduce, cfg)
    coord.serve()
    env = dict(env)
    env["DSI_MR_SOCKET"] = coord.address()
    if args.fetch_window > 0:  # CLI twin of DSI_NET_FETCH_WINDOW
        env["DSI_NET_FETCH_WINDOW"] = str(args.fetch_window)
    # Workers run with cwd=their private dir; make the package
    # importable there even when not installed (the test-sandbox case).
    import dsi_tpu as _pkg

    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(_pkg.__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(i: int, clean: bool = False):
        """Worker ``i``.  A replacement past the original fleet reuses
        the last slot's command; where that is a reduce-only helper it
        runs as a plain host worker instead — the workers that finished
        linger outside the task loop, so the replacement must be able to
        re-execute a lost producer's map as well as the reduce."""
        wdir = os.path.join(workdir, f"worker-{i}")
        os.makedirs(wdir, exist_ok=True)
        worker_cmd, wenv = fleet[min(i, len(fleet) - 1)]
        we = {**wenv, "DSI_MR_SOCKET": env["DSI_MR_SOCKET"],
              "PYTHONPATH": env["PYTHONPATH"]}
        if i >= len(fleet):
            we.pop("DSI_MR_REDUCE_ONLY", None)
        if "DSI_NET_FETCH_WINDOW" in env:
            we["DSI_NET_FETCH_WINDOW"] = env["DSI_NET_FETCH_WINDOW"]
        we["DSI_NET_SPOOL"] = wdir
        we["DSI_CHAOS_WORKER_INDEX"] = str(i)
        if clean:
            for k in ("DSI_CHAOS_WORKER_KILL", "DSI_FAULT_POINT",
                      "DSI_FAULT_STEP"):
                we.pop(k, None)
        return subprocess.Popen(worker_cmd, env=we, cwd=wdir)

    t0 = time.monotonic()
    deadline = t0 + args.timeout
    procs = {i: spawn(i) for i in range(args.workers)}
    next_idx = args.workers
    fetched: set = set()
    respawn_budget = max(16, 2 * (len(files) + args.nreduce))
    rc = 0
    try:
        while True:
            if time.monotonic() > deadline:
                print("mrrun: job exceeded --timeout; killing",
                      file=sys.stderr)
                rc = 1
                break
            # Fetch outputs AS they commit — while producers of a
            # possible re-execution round are still in their task loop.
            for r, (a, name, crc) in sorted(
                    coord.output_locations().items()):
                if r in fetched:
                    continue
                try:
                    raw = fetch_partition(a, name,
                                          timeout=cfg.net_fetch_timeout_s)
                    if crc and zlib.crc32(raw) != crc:
                        raise FetchFailure(
                            -1, a, name,
                            ValueError("output crc mismatch"))
                except FetchFailure as e:
                    print(f"mrrun: output fetch failed ({e})",
                          file=sys.stderr)
                    coord.refetch_reduce(r)
                    if respawn_budget <= 0:
                        rc = 1
                    else:
                        respawn_budget -= 1
                        procs[next_idx] = spawn(next_idx, clean=True)
                        next_idx += 1
                    break
                with atomic_write(os.path.join(workdir, f"mr-out-{r}"),
                                  mode="wb") as f:
                    f.write(raw)
                fetched.add(r)
            if rc:
                break
            if coord.done() and len(fetched) == args.nreduce:
                break
            for i, w in list(procs.items()):
                if w.poll() is not None and w.returncode != 0 \
                        and not coord.done():
                    if lost_chip(w, "mrrun"):
                        rc = 1
                        break
                    if respawn_budget <= 0:
                        print("mrrun: workers failing repeatedly; "
                              "giving up", file=sys.stderr)
                        rc = 1
                        break
                    respawn_budget -= 1
                    procs[i] = spawn(i, clean=True)
            if rc:
                break
            time.sleep(0.2)
    finally:
        run_stats = coord.net_stats()
        run_stats["wall_s"] = round(time.monotonic() - t0, 3)
        run_stats["workers_spawned"] = next_idx
        coord.close()
        for w in procs.values():
            if w.poll() is None:
                w.terminate()
        for w in procs.values():
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()

    # Share-nothing assertion: nothing but DRIVER-written artifacts may
    # exist in the shared workdir — a stray mr-X-Y intermediate there
    # means some worker fell back to the shared-directory data plane.
    leaked = [n for n in os.listdir(workdir)
              if n.startswith("mr-")
              and not n.startswith(("mr-out-", "mr-correct", "mr.sock"))]
    if leaked:
        print(f"mrrun: SHARE-NOTHING VIOLATION: shared workdir has "
              f"{sorted(leaked)}", file=sys.stderr)
        rc = rc or 1
    if rc == 0:
        # The private spools carried the job; reap them (retention GC
        # would otherwise hold gigabytes for an hour).
        for i in range(next_idx):
            shutil.rmtree(os.path.join(workdir, f"worker-{i}"),
                          ignore_errors=True)
    if args.stats_json:
        import json

        # dsicheck: allow[raw-write] bench/CI parse surface, not durable state
        with open(args.stats_json, "w", encoding="utf-8") as f:
            json.dump(run_stats, f, sort_keys=True, indent=1)
    print(f"mrrun: net data plane: {run_stats['net_fetches']} fetches "
          f"({run_stats['net_local_reads']} local), "
          f"{run_stats['net_bytes_raw']}B raw / "
          f"{run_stats['net_bytes_wire']}B wire "
          f"(ratio {run_stats['net_ratio']}), "
          f"{run_stats['locality_hits']} locality hits, "
          f"{run_stats['net_fetch_failures']} fetch failures, "
          f"{run_stats['net_refetches']} refetches, "
          f"window {run_stats.get('net_prefetch_window', 0)} "
          f"(overlap {run_stats.get('net_overlap_s', 0.0)}s, "
          f"wait {run_stats.get('net_fetch_wait_s', 0.0)}s)",
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
