"""Coordinator: job state + pull-based task scheduler + RPC server.

Reference: ``mr/coordinator.go`` (entire file, 160 LoC).  Same state machine:

* per-task logs with states 0=untouched / 1=in-progress / 2=completed
  (coordinator.go:16,20),
* map tasks are assigned first; **no reduce task is assigned until every map
  has completed** — the `cMap == nMap` barrier (coordinator.go:47,79), which is
  load-bearing for correctness (reduce must see all mr-*-r files),
* a task in-progress for `task_timeout_s` (10 s) is re-queued for another
  worker — presumed-dead-by-timeout fault tolerance (coordinator.go:70-77,
  99-106),
* `Done()` is `c_reduce == n_reduce` under the lock (coordinator.go:138-142).

Two reference defects documented in SURVEY.md §5 are fixed here (both
output-invariant):

1. **Unique-transition completion counting.**  The reference increments
   `cMap`/`cReduce` on every completion RPC (coordinator.go:30-31,38-39), so a
   re-queued task finished by two workers double-counts and can prematurely
   satisfy the map barrier or `Done()`.  We count only the first transition of
   a task's log to COMPLETED.
2. The waiting busy-poll fix lives in the worker (see worker.py).
"""

from __future__ import annotations

import heapq
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from dsi_tpu.config import JobConfig
from dsi_tpu.obs import LatencyHistogram, event as _event, get_registry
from dsi_tpu.mr import rpc
from dsi_tpu.mr.journal import Journal
from dsi_tpu.mr.shards import ShardSpec
from dsi_tpu.mr.types import (LOG_COMPLETED, LOG_IN_PROGRESS, LOG_UNTOUCHED,
                              TaskStatus)
from dsi_tpu.utils.atomicio import fsync_dir


class Coordinator:
    """Owns all job state; hands out tasks on pull (mr/coordinator.go:14-25).

    **Shard mode** (``shard_plan`` given): the coordinator is a shard
    scheduler for the streaming engines (ISSUE 15 — the speculative-
    execution loop the PR-9 telemetry armed).  Each :class:`ShardSpec`
    is a cursor-range task a worker drives as a resumable step object;
    the coordinator tracks ATTEMPTS per shard (primary / takeover /
    backup), presumes an attempt dead when its progress RPCs go silent
    past ``shard_timeout_s`` (re-queueing the shard with a resume hint
    pointing at the best checkpoint chain), speculatively hands an idle
    worker a BACKUP attempt of a shard whose newest attempt is silent
    past the percentile-aware suspect threshold (Dean & Ghemawat §3.6),
    and arbitrates FIRST-COMMIT-WINS: the first ``CommitShard`` RPC for
    a shard durably renames that attempt's output and journals the
    commit record (shard id + attempt id + output CRC32) under the
    lock — every later attempt is told it lost and reaps its partials.
    """

    def __init__(self, files: List[str], n_reduce: int,
                 config: JobConfig | None = None,
                 shard_plan: Optional[List[ShardSpec]] = None,
                 shard_opts: Optional[dict] = None,
                 journal: Optional[Journal] = None):
        self.config = config or JobConfig(n_reduce=n_reduce)
        self.files = list(files)
        self.n_map = len(files)
        self.c_map = 0
        self.map_log = [LOG_UNTOUCHED] * self.n_map
        self.n_reduce = n_reduce
        self.c_reduce = 0
        self.reduce_log = [LOG_UNTOUCHED] * n_reduce
        # Assignment heaps: lowest untouched index first — the same order
        # as the reference's linear scan (mr/coordinator.go:50-55), O(log n)
        # per assignment instead of O(n) (which is O(n^2) across a big
        # job).  Entries are lazily invalidated: pop until one is still
        # UNTOUCHED; requeue pushes the index back.
        self._map_ready = list(range(self.n_map))
        self._reduce_ready = list(range(n_reduce))
        self.mu = threading.Lock()
        # ── shard-scheduler state (shard mode only; all guarded by mu) ──
        self.shard_plan = list(shard_plan) if shard_plan else None
        self.shard_opts = dict(shard_opts or {})
        self.n_shards = len(self.shard_plan) if self.shard_plan else 0
        if self.shard_plan:
            self.n_map = 0  # shard jobs have no map/reduce phases
            self.n_reduce = 0
            self._map_ready = []
            self._reduce_ready = []
        self._shards: Dict[int, dict] = {}
        self._shard_ready: list[int] = []
        self.job_failed = False
        #: Speculation counters — the differential harness's evidence
        #: surface (``spec_stats()``).  duplicate_commits counts journal
        #: double-commits and MUST stay 0; commit_losses counts attempts
        #: that finished second (normal when a backup races the primary).
        self._spec = {"backup_dispatches": 0, "requeues": 0, "commits": 0,
                      "commit_losses": 0, "duplicate_commits": 0,
                      "resumed_attempts": 0, "failed_attempts": 0,
                      "resplits": 0, "subshard_dispatches": 0,
                      "subshard_commits": 0,
                      "resume_cursors": {}}
        #: Dispatchable sub-shards of re-split shards: (sid, k) heap,
        #: lazily invalidated like the shard heap.
        self._sub_ready: list[tuple] = []
        #: assignment→commit walls of committed shards — the "normal
        #: shard duration" reference the slow-progress backup trigger
        #: compares against (§3.6: back up what takes abnormally long).
        self._commit_walls: list[float] = []
        if self.shard_plan:
            for spec in self.shard_plan:
                self._shards[spec.sid] = {
                    "spec": spec, "status": LOG_UNTOUCHED,
                    "attempts": {}, "next_aid": 0, "committed": None,
                    "backups": 0, "subs": None}
            self._shard_ready = list(range(self.n_shards))
            heapq.heapify(self._shard_ready)
        # Worker liveness (observability + the speculative-execution
        # hook): last-contact time per WorkerId — every RPC carrying an
        # id refreshes it — and which worker holds each in-progress
        # task, so a requeue can report WHOSE heartbeat went stale and
        # how stale it was (the reference reassigns silently,
        # coordinator.go:70-77).
        self._worker_seen: Dict[str, float] = {}
        self._task_worker: Dict[tuple, str] = {}
        # ── network data plane (dsi_tpu/net, ISSUE 17) ──
        # In net mode workers serve their spooled partitions over TCP;
        # the coordinator is the location registry (Dean & Ghemawat
        # §3.1: "the locations of these buffered pairs ... are passed
        # back to the master, who is responsible for forwarding these
        # locations to the reduce workers") and re-executes completed
        # map tasks whose server died (§3.4).
        self.net = bool(self.config.net_shuffle)
        #: worker id → its partition-server address (from every RPC).
        self._net_addrs: Dict[str, str] = {}
        #: map task → producer's partition-server address.
        self._map_locs: Dict[int, str] = {}
        #: map task → per-reduce-partition byte sizes (locality shares).
        self._map_sizes: Dict[int, List[int]] = {}
        #: reduce task → (addr, name, crc) of the committed output.
        self._out_locs: Dict[int, tuple] = {}
        #: Net-plane counters (schema: obs/registry.COUNTER_KEYS).
        self._net_counters = {
            "net_fetches": 0, "net_local_reads": 0, "net_bytes_raw": 0,
            "net_bytes_wire": 0, "net_ratio": 0.0,
            "net_fetch_failures": 0, "net_refetches": 0,
            "locality_hits": 0,
            "net_fetch_wait_s": 0.0, "net_overlap_s": 0.0,
            "net_prefetch_window": 0}
        # Per-worker contact-GAP histograms (obs/hist.py): every RPC
        # records the gap since the worker's previous contact, so a
        # requeue can compare the stale worker's current silence to its
        # own p99 gap — "presumed dead" (silence way past anything it
        # ever did) vs "slow task" (still phoning home, the task is
        # just long).  The percentile-aware signal the speculative-
        # execution item dispatches backup tasks on.
        self._hb_hist: Dict[str, LatencyHistogram] = {}
        # Straggler watchdog: ONE monitor thread over a deadline heap
        # replaces the reference's goroutine-per-assignment
        # (mr/coordinator.go:70-77,99-106) — a per-task Timer thread melts
        # at ~10^4 tasks (~0.4 ms spawn each, thousands of live threads);
        # the heap is O(log n) per assignment and one thread total.
        # Entries: (due, "map"|"reduce", task_id) or, in shard mode,
        # (due, "shard", sid, attempt_id) — progress-based, re-armed by
        # the watchdog while the attempt keeps phoning home.
        self._deadlines: list[tuple] = []
        self._deadline_cv = threading.Condition(self.mu)
        self._closing = False
        self._monitor = threading.Thread(target=self._watchdog,
                                         name="dsi-mr-watchdog", daemon=True)
        self._monitor.start()
        self._server: Optional[rpc.RpcServer] = None

        # Clear stale mr-out-* so a leftover file from a PREVIOUS job in the
        # same cwd can't win the workers' first-writer-wins output commit
        # (atomicio.py) — preserving reference rerun-overwrites behavior at
        # job granularity.  NOT on journal resume: there, a
        # committed-but-unjournaled mr-out-<r> whose intermediates were
        # already GC'd is the only surviving copy of that partition, and
        # deleting it would make the re-run reducer commit an empty file.
        # This must happen BEFORE the journal file is created below: a crash
        # between journal creation and the clear would otherwise look like a
        # resume forever and skip the clear.
        resuming = bool(self.config.journal_path
                        and os.path.exists(self.config.journal_path))
        if not resuming:
            prefixes = ("mr-out-", "mr-shard-out-") if self.shard_plan \
                else ("mr-out-",)
            try:
                stale = [n for n in os.listdir(self.config.workdir)
                         if n.startswith(prefixes)]
            except OSError:
                stale = []
            for name in stale:  # ALL partitions, incl. a previous job's
                try:            # higher-numbered ones (n_reduce may shrink)
                    os.remove(os.path.join(self.config.workdir, name))
                except OSError:
                    pass

        # Optional checkpoint/resume (journal.py; disabled by default — the
        # reference keeps coordinator state purely in-memory).
        # An INJECTED journal (replica mode) swaps the local append-only
        # file for the replicated log's propose-and-wait path: same
        # record surface, but a record is durable only once a majority
        # of the coordinator group holds it (replica/node.py).
        self._journal: Optional[Journal] = journal
        if self.config.journal_path or journal is not None:
            if self._journal is None:
                self._journal = Journal(self.config.journal_path,
                                        self.files, self.n_reduce,
                                        n_shards=self.n_shards)
            done_maps, done_reduces = self._journal.replay()
            for t in done_maps:
                if self.map_log[t] != LOG_COMPLETED:
                    self.map_log[t] = LOG_COMPLETED
                    self.c_map += 1
            for t in done_reduces:
                if self.reduce_log[t] != LOG_COMPLETED:
                    self.reduce_log[t] = LOG_COMPLETED
                    self.c_reduce += 1
            # Net mode (ISSUE 18): re-learn the partition location
            # registry from the journaled completions.  A replayed
            # address whose server died with the old coordinator is
            # only advisory — the first reducer to hit it reports
            # FetchFailed and the producer re-executes (§3.4), exactly
            # the live-run convergence path.
            if self.net:
                for t, a in self._journal.map_locations.items():
                    self._map_locs.setdefault(t, a)
                for t, sz in self._journal.map_sizes.items():
                    self._map_sizes.setdefault(t, list(sz))
                for t, loc in self._journal.out_locations.items():
                    self._out_locs.setdefault(t, tuple(loc))
            # Shard commits replay as COMMITTED: the journal record was
            # written only after the output file's durable rename, so
            # the shard's output exists and must never be re-run.
            for sid, (aid, crc) in self._journal.shard_commits.items():
                shard = self._shards.get(sid)
                if shard is not None and shard["committed"] is None:
                    shard["committed"] = (aid, crc)
                    shard["status"] = LOG_COMPLETED
            if self._journal.shard_commits:
                self._shard_ready = [
                    s for s in self._shard_ready
                    if self._shards[s]["committed"] is None]
                heapq.heapify(self._shard_ready)
            # Re-split records replay as live sub-shard state: the
            # ranges partition the shard exactly, so the remaining work
            # IS the uncommitted subs — the full range is never
            # re-queued once a re-split was journaled (the dead
            # straggler's chain still serves sub 0 via adoption).
            for sid, ranges in self._journal.resplits.items():
                shard = self._shards.get(sid)
                if shard is None or shard["committed"] is not None:
                    continue
                self._make_subs(sid, ranges, parent_chain=None)
                shard["status"] = LOG_IN_PROGRESS
            for (sid, k), (aid, crc) in \
                    self._journal.subshard_commits.items():
                shard = self._shards.get(sid)
                subs = shard["subs"] if shard is not None else None
                sub = subs.get(k) if subs else None
                if sub is not None and sub["committed"] is None:
                    sub["committed"] = (aid, crc)
                    sub["status"] = LOG_COMPLETED
            for shard in self._shards.values():
                if shard["committed"] is None \
                        and self._split_resolved(shard):
                    shard["status"] = LOG_COMPLETED
            self._journal.open()

    # ---- RPC handlers (the wire API, mr/coordinator.go:27-114) ----

    def request_task(self, args: dict) -> dict:
        """Assign a map task, a reduce task, "waiting", or "done"
        (mr/coordinator.go:43-114)."""
        reply = {"TaskStatus": int(TaskStatus.WAITING), "NMap": self.n_map,
                 "CMap": 0, "NReduce": self.n_reduce, "CReduce": 0, "Filename": ""}
        wid = str(args.get("WorkerId") or "")
        addr = str(args.get("Addr") or "")
        with self.mu:
            if wid:
                self._touch(wid)
                if addr:
                    self._net_addrs[wid] = addr
            if self.c_map < self.n_map:
                # A reduce-only worker (host helper of a device fleet)
                # waits out the map phase.
                tba = None if args.get("NoMap") else \
                    self._pop_untouched(self._map_ready, self.map_log)
                if tba is None:
                    reply["TaskStatus"] = int(TaskStatus.WAITING)  # :58-60
                else:
                    self.map_log[tba] = LOG_IN_PROGRESS  # :62
                    reply["TaskStatus"] = int(TaskStatus.MAP)
                    reply["Filename"] = self.files[tba]
                    reply["CMap"] = tba
                    self._arm_timeout(tba, "map")  # :70-77
                    if wid:
                        self._task_worker[("map", tba)] = wid
                    _event("assign", kind="map", task=tba,
                           file=self.files[tba], worker=wid or None)
            elif self.c_reduce < self.n_reduce:  # map barrier passed (:79)
                tba = self._pick_reduce_locked(addr) if self.net \
                    else self._pop_untouched(self._reduce_ready,
                                             self.reduce_log)
                if tba is None:
                    reply["TaskStatus"] = int(TaskStatus.WAITING)
                else:
                    self.reduce_log[tba] = LOG_IN_PROGRESS
                    reply["TaskStatus"] = int(TaskStatus.REDUCE)
                    reply["CReduce"] = tba
                    if self.net:
                        # §3.1: the master forwards the buffered pairs'
                        # locations to the reduce worker.
                        reply["Net"] = True
                        reply["MapLocs"] = {str(m): a for m, a
                                            in self._map_locs.items()}
                    self._arm_timeout(tba, "reduce")  # :99-106
                    if wid:
                        self._task_worker[("reduce", tba)] = wid
                    _event("assign", kind="reduce", task=tba,
                           worker=wid or None)
            else:
                reply["TaskStatus"] = int(TaskStatus.DONE)  # :109-112
        return reply

    def map_complete(self, args: dict) -> dict:
        """Reference: RecieveMapComplete [sic] (mr/coordinator.go:27-33), with
        the unique-transition counting fix."""
        t = int(args["TaskNumber"])
        wid = str(args.get("WorkerId") or "")
        addr = str(args.get("Addr") or "")
        with self.mu:
            if wid:
                self._touch(wid)
            self._task_worker.pop(("map", t), None)
            if self.map_log[t] != LOG_COMPLETED:  # fix: count first completion only
                self.map_log[t] = LOG_COMPLETED
                self.c_map += 1
                if addr:
                    # Location registry (§3.1): this producer serves
                    # mr-<t>-* from its spool; the per-partition byte
                    # sizes feed the locality-share placement policy.
                    self._map_locs[t] = addr
                    sizes = args.get("PartSizes")
                    if isinstance(sizes, list):
                        self._map_sizes[t] = [int(x) for x in sizes]
                if self._journal is not None:
                    extra = None
                    if addr:  # net mode: journal the location registry
                        extra = {"addr": addr}
                        if t in self._map_sizes:
                            extra["sizes"] = list(self._map_sizes[t])
                    self._journal.record("map", t, extra)
                _event("complete", kind="map", task=t, c_map=self.c_map,
                       worker=wid or None)
            else:
                _event("duplicate_completion", kind="map", task=t)
        return {}

    def reduce_complete(self, args: dict) -> dict:
        """Reference: RecieveReduceComplete [sic] (mr/coordinator.go:35-41)."""
        t = int(args["TaskNumber"])
        wid = str(args.get("WorkerId") or "")
        addr = str(args.get("Addr") or "")
        with self.mu:
            if wid:
                self._touch(wid)
            self._task_worker.pop(("reduce", t), None)
            if self.reduce_log[t] != LOG_COMPLETED:
                self.reduce_log[t] = LOG_COMPLETED
                self.c_reduce += 1
                if addr:
                    # Net mode: mr-out-<t> lives in the reducer's spool;
                    # the driver fetches it by this location.
                    self._out_locs[t] = (addr,
                                         str(args.get("Name") or ""),
                                         int(args.get("Crc", 0) or 0))
                self._absorb_net_locked(args)
                if self._journal is not None:
                    extra = None
                    if addr:  # net mode: where mr-out-<t> is served from
                        extra = {"addr": addr,
                                 "name": str(args.get("Name") or ""),
                                 "crc": int(args.get("Crc", 0) or 0)}
                    self._journal.record("reduce", t, extra)
                _event("complete", kind="reduce", task=t,
                       c_reduce=self.c_reduce, worker=wid or None)
            else:
                _event("duplicate_completion", kind="reduce", task=t)
        return {}

    def fetch_failed(self, args: dict) -> dict:
        """Re-fetch-from-replacement (§3.4): a reducer could not fetch
        ``mr-<Map>-<Reduce>`` from its producer's partition server (the
        server died, or died mid-stream).  The completed map task is
        reset to UNTOUCHED — ``c_map`` drops below ``n_map``, so the map
        barrier RE-ENGAGES and the task re-executes on a live worker
        (its completion re-registers a replacement location); the
        reporting reducer's task is re-queued to run after the barrier
        reopens.  Unique-transition counting absorbs the duplicate
        completion a slow original could still send."""
        m = int(args.get("Map", -1))
        r = int(args.get("Reduce", -1))
        wid = str(args.get("WorkerId") or "")
        with self.mu:
            if wid:
                self._touch(wid)
            self._net_counters["net_fetch_failures"] += 1
            requeued_map = False
            if 0 <= m < self.n_map and self.map_log[m] == LOG_COMPLETED:
                self.map_log[m] = LOG_UNTOUCHED
                self.c_map -= 1  # the map barrier re-engages
                heapq.heappush(self._map_ready, m)
                self._map_locs.pop(m, None)
                self._map_sizes.pop(m, None)
                self._net_counters["net_refetches"] += 1
                requeued_map = True
            if 0 <= r < self.n_reduce \
                    and self.reduce_log[r] == LOG_IN_PROGRESS:
                self.reduce_log[r] = LOG_UNTOUCHED
                heapq.heappush(self._reduce_ready, r)
                self._task_worker.pop(("reduce", r), None)
            _event("fetch_failed", kind="net", task=r, map_task=m,
                   worker=wid or None,
                   addr=str(args.get("Addr") or "") or None,
                   requeued_map=requeued_map)
            if requeued_map:
                print(f"coordinator: fetch of mr-{m}-{r} failed "
                      f"(producer server gone); re-executing map {m}",
                      file=sys.stderr)
        return {"Requeued": requeued_map}

    def _absorb_net_locked(self, args: dict) -> None:
        """Fold one completion RPC's per-task net-attribution deltas
        into the job-wide counters.  Caller holds ``self.mu``."""
        found = False
        for wire, key in (("NetFetches", "net_fetches"),
                          ("NetLocal", "net_local_reads"),
                          ("NetRaw", "net_bytes_raw"),
                          ("NetWire", "net_bytes_wire"),
                          ("NetFailures", "net_fetch_failures")):
            v = args.get(wire)
            if v is not None:
                self._net_counters[key] += int(v)
                found = True
        for wire, key in (("NetWait", "net_fetch_wait_s"),
                          ("NetOverlap", "net_overlap_s")):
            v = args.get(wire)
            if v is not None:
                self._net_counters[key] = round(
                    self._net_counters[key] + float(v), 6)
                found = True
        v = args.get("NetWindow")
        if v is not None:
            self._net_counters["net_prefetch_window"] = max(
                self._net_counters["net_prefetch_window"], int(v))
            found = True
        if found:
            wire_n = self._net_counters["net_bytes_wire"]
            self._net_counters["net_ratio"] = round(
                self._net_counters["net_bytes_raw"] / wire_n, 3) \
                if wire_n else 0.0

    # ---- shard-scheduler RPC handlers (shard mode, mr/shards.py) ----

    def request_shard(self, args: dict) -> dict:
        """Assign a shard attempt: an untouched/re-queued shard first
        (primary or takeover — a takeover carries a resume hint at the
        best known checkpoint chain), else a speculative BACKUP attempt
        of the stalest suspect shard (Dean & Ghemawat §3.6), else
        WAITING/DONE."""
        wid = str(args.get("WorkerId") or "")
        addr = str(args.get("Addr") or "")
        reply: dict = {"TaskStatus": int(TaskStatus.WAITING)}
        now = time.monotonic()
        with self.mu:
            if self.shard_plan is None:
                return {"TaskStatus": int(TaskStatus.DONE)}
            if wid:
                self._touch(wid)
                if addr:
                    self._net_addrs[wid] = addr
            if self.job_failed or all(
                    self._shard_resolved(shard)
                    for shard in self._shards.values()):
                reply["TaskStatus"] = int(TaskStatus.DONE)
                return reply
            assignment = None
            sid = self._pop_untouched_shard(wid)
            if sid is not None:
                shard = self._shards[sid]
                kind = "takeover" if shard["attempts"] else "primary"
                assignment = self._new_attempt(sid, wid, kind, now)
            if assignment is None:
                pick = self._pop_untouched_sub()
                if pick is not None:
                    return self._assign_sub(pick[0], pick[1], wid, now)
            if assignment is None and self.config.spec_resplit \
                    and not self.net:
                # Re-split is a shared-directory optimization: its
                # sub-range merge reads committed files in place.  Net
                # mode covers stragglers with whole-range backups
                # (first-commit-wins is location-agnostic).
                pick = self._maybe_resplit(wid, now)
                if pick is not None:
                    return self._assign_sub(pick[0], pick[1], wid, now)
            if assignment is None and self.config.spec_backup:
                assignment = self._maybe_backup(wid, now)
            if assignment is None:
                return reply
            sid, aid, shard, att = assignment
            spec = shard["spec"]
            reply.update({
                "TaskStatus": int(TaskStatus.SHARD), "Shard": sid,
                "Attempt": aid, "Start": spec.start, "End": spec.end,
                "Files": self.files, "NShards": self.n_shards,
                "ResumeFrom": att["resume_from"],
                "Knobs": self.shard_opts.get("knobs", {}),
                "CkptRoot": self._shard_ckpt_root(),
                "OutPart": self._shard_part_path(sid, aid),
            })
            if self.net:
                # Share-nothing: the partial and the checkpoint chain
                # both resolve RELATIVE to the worker's private cwd; a
                # resume hint only restores when the chain is local
                # (adopt_chain fails soft otherwise — exactly the case
                # the locality preference above works to hit).
                reply["Net"] = True
                reply["OutPart"] = os.path.basename(reply["OutPart"])
                reply["CkptRoot"] = ".shards"
            _event("assign", kind="shard", task=sid, attempt=aid,
                   attempt_kind=att["kind"], worker=wid or None,
                   resume_from=att["resume_from"])
        return reply

    def shard_progress(self, args: dict) -> dict:
        """Attempt heartbeat: refreshes liveness (the watchdog's
        presumed-dead signal is *progress* silence, not RPC silence) and
        carries the attempt's confirmed-step count, its durable
        checkpoint count (the resume-hint ranking), and — once, after a
        takeover/backup restore — the resume cursor the differential
        harness asserts on.  The reply's ``Cancel`` tells a loser to
        stop and reap (first-commit-wins)."""
        wid = str(args.get("WorkerId") or "")
        sid = int(args.get("Shard", -1))
        aid = int(args.get("Attempt", -1))
        sub = int(args.get("Sub", -1))
        now = time.monotonic()
        with self.mu:
            if wid:
                self._touch(wid)
            shard = self._shards.get(sid)
            owner = shard
            if shard is not None and sub >= 0:
                owner = (shard["subs"] or {}).get(sub)
            att = owner["attempts"].get(aid) if owner is not None else None
            if att is None:
                return {"Cancel": True}
            att["last_progress"] = now
            att["confirmed"] = int(args.get("Confirmed", 0) or 0)
            att["ckpts"] = int(args.get("Ckpts", 0) or 0)
            # The attempt's LIVE confirmed-byte cursor (reported from
            # the first retired step, not only after a checkpoint) —
            # the re-split trigger cuts the remainder from here.
            att["cursor"] = int(args.get("Cursor", 0) or 0)
            # "Progressed" means REAL steps retired, not merely an RPC:
            # the first advance slice pays the engine's jax compiles,
            # and the setup-grace window must cover exactly that.
            if att["confirmed"] > 0 or att["ckpts"] > 0:
                att["progressed"] = True
            rc = args.get("ResumeCursor")
            if rc and not att["resume_cursor"]:
                att["resume_cursor"] = int(rc)
                self._spec["resumed_attempts"] += 1
                key = f"{sid}.s{sub}.a{aid}" if sub >= 0 else f"{sid}.a{aid}"
                self._spec["resume_cursors"][key] = int(rc)
            cancel = att["cancelled"] or owner["committed"] is not None \
                or self._shard_resolved(shard)
            return {"Cancel": bool(cancel)}

    def commit_shard(self, args: dict) -> dict:
        """FIRST-COMMIT-WINS, under the lock: the first attempt to
        report a durably written partial wins — its file is renamed to
        the shard's final output, the commit record (shard + attempt +
        CRC32) is journaled, and every other live attempt is flagged
        for cancellation.  Later commits are told they lost and reap
        their partials; a dead-presumed attempt that was actually just
        slow may still win (liveness never gates commits).

        Re-split arbitration (``Sub >= 0`` commits a SUB-range): each
        sub-range is its own first-commit-wins race journaled as a
        ``subshard`` record; once EVERY sub has committed the shard is
        resolved "split" and the full-range straggler is cancelled.
        Conversely a full-range commit landing while any sub is still
        open WINS the whole shard (the straggler outran the split) and
        every sub is cancelled and its outputs reaped — either way
        exactly one committed copy of every byte survives."""
        wid = str(args.get("WorkerId") or "")
        sid = int(args.get("Shard", -1))
        aid = int(args.get("Attempt", -1))
        sub = int(args.get("Sub", -1))
        crc = int(args.get("Crc", 0) or 0)
        with self.mu:
            if wid:
                self._touch(wid)
            shard = self._shards.get(sid)
            if shard is None:
                return {"Win": False}
            if sub >= 0:
                return self._commit_sub_locked(shard, sid, sub, aid, crc,
                                               wid)
            if shard["committed"] is None and self._split_resolved(shard):
                # The subs got there first: the full-range straggler
                # lost to the split as a whole.
                self._spec["commit_losses"] += 1
                _event("shard_commit_lose", kind="shard", task=sid,
                       attempt=aid, winner="split",
                       worker=wid or None)
                return {"Win": False}
            if shard["committed"] is not None:
                self._spec["commit_losses"] += 1
                if shard["committed"][0] == aid:
                    # The winner re-reporting would double-journal:
                    # MUST stay 0 (the harness gates on it).
                    self._spec["duplicate_commits"] += 1
                _event("shard_commit_lose", kind="shard", task=sid,
                       attempt=aid, winner=shard["committed"][0],
                       worker=wid or None)
                return {"Win": False}
            if self.net:
                # Net mode: the winner's bytes stay in ITS private
                # spool; the coordinator records the location (addr +
                # spool name + CRC) and the driver fetches them over
                # the stream transport — the §3.1 contract where the
                # master tracks locations, never the bytes.  Losers
                # reap their own partials (private dirs; nobody else
                # can).
                net_addr = str(args.get("Addr") or "")
                net_name = str(args.get("Name") or "")
                if not net_addr or not net_name:
                    return {"Win": False,
                            "Error": "net commit needs Addr+Name"}
                shard["net_loc"] = (net_addr, net_name)
            else:
                part = self._shard_part_path(sid, aid)
                final = self._shard_out_path(sid)
                try:
                    os.replace(part, final)
                    fsync_dir(os.path.dirname(final) or ".")
                except OSError as e:
                    _event("shard_commit_missing", kind="shard",
                           task=sid, attempt=aid, error=str(e))
                    return {"Win": False,
                            "Error": f"partial missing: {e}"}
            if self._journal is not None:
                self._journal.record_shard(sid, aid, crc)
            shard["committed"] = (aid, crc)
            shard["status"] = LOG_COMPLETED
            self._spec["commits"] += 1
            if not self.net:
                # Reap sibling partials: an attempt killed between its
                # durable partial write and its commit RPC can never
                # report again, and its orphan .part must not outlive
                # the shard.
                prefixes = (os.path.basename(final) + ".a",
                            os.path.basename(final) + ".s")
                try:
                    for name in os.listdir(os.path.dirname(final)
                                           or "."):
                        if name.startswith(prefixes) \
                                and name.endswith(".part"):
                            os.remove(os.path.join(
                                os.path.dirname(final), name))
                except OSError:
                    pass
            for oaid, oatt in shard["attempts"].items():
                if oaid != aid:
                    oatt["cancelled"] = True
            if shard["subs"]:
                # The straggler outran its own split: cancel every sub
                # attempt and reap any sub output already renamed — the
                # full-range file is now THE copy of these bytes.
                for k, sd in shard["subs"].items():
                    sd["status"] = LOG_COMPLETED  # no further dispatch
                    for satt in sd["attempts"].values():
                        satt["cancelled"] = True
                    for p in (self._sub_out_path(sid, k),):
                        try:
                            os.remove(p)
                        except OSError:
                            pass
                _event("resplit_overrun", kind="shard", task=sid,
                       attempt=aid,
                       subs=sorted(shard["subs"]))
            att = shard["attempts"].get(aid)
            if att is not None:
                now = time.monotonic()
                att["last_progress"] = now
                # The slow-progress backup trigger's reference: how
                # long a NORMAL shard takes, assignment to commit.
                self._commit_walls.append(now - att["assigned"])
            _event("shard_commit", kind="shard", task=sid, attempt=aid,
                   crc=crc, worker=wid or None,
                   resume_cursor=att["resume_cursor"] if att else 0)
            get_registry().set_gauge("dsi_shard_commits",
                                     self._spec["commits"])
            return {"Win": True}

    def shard_failed(self, args: dict) -> dict:
        """An attempt reporting it cannot finish (host-path routing,
        engine error): mark it dead and re-queue the shard with a
        resume hint — bounded by ``shard_max_attempts``."""
        wid = str(args.get("WorkerId") or "")
        sid = int(args.get("Shard", -1))
        aid = int(args.get("Attempt", -1))
        sub = int(args.get("Sub", -1))
        with self.mu:
            if wid:
                self._touch(wid)
            shard = self._shards.get(sid)
            owner = shard
            if shard is not None and sub >= 0:
                owner = (shard["subs"] or {}).get(sub)
            att = owner["attempts"].get(aid) if owner is not None else None
            if att is not None and not att["dead"] and not att["cancelled"]:
                att["dead"] = True
                self._spec["failed_attempts"] += 1
                _event("shard_failed", kind="shard", task=sid,
                       attempt=aid, worker=wid or None,
                       sub=(sub if sub >= 0 else None),
                       reason=str(args.get("Reason", "") or ""))
                if sub >= 0:
                    self._requeue_sub_locked(sid, sub)
                else:
                    self._requeue_shard_locked(sid)
        return {}

    def spec_stats(self) -> dict:
        """Speculation-counter snapshot — the differential harness's
        and the bench row's evidence surface."""
        with self.mu:
            out = {k: (dict(v) if isinstance(v, dict) else v)
                   for k, v in self._spec.items()}
            out["shards"] = self.n_shards
            out["job_failed"] = self.job_failed
            out["committed"] = sum(
                1 for shard in self._shards.values()
                if shard["committed"] is not None)
            out["total_attempts"] = sum(
                shard["next_aid"] for shard in self._shards.values())
            out["winning_attempts"] = {
                str(sid): shard["committed"][0]
                for sid, shard in self._shards.items()
                if shard["committed"] is not None}
            out["subshards"] = sum(
                len(shard["subs"] or {})
                for shard in self._shards.values())
            out["split_shards"] = sum(
                1 for shard in self._shards.values()
                if shard["committed"] is None
                and self._split_resolved(shard))
            out["resolved"] = sum(
                1 for shard in self._shards.values()
                if self._shard_resolved(shard))
        return out

    def final_outputs(self) -> List[str]:
        """The job's committed output files in stream order: each
        shard's full-range file, or — for a shard resolved by re-split
        — its sub-range files in sub order (sub ranges partition the
        shard in order, so concatenation order is preserved).  Only
        complete once :meth:`done` is True."""
        with self.mu:
            out: List[str] = []
            for sid in sorted(self._shards):
                shard = self._shards[sid]
                if shard["committed"] is not None:
                    out.append(self._shard_out_path(sid))
                elif shard["subs"]:
                    out.extend(self._sub_out_path(sid, k)
                               for k in sorted(shard["subs"]))
            return out

    # ---- net-plane driver surface (dsi_tpu/net, ISSUE 17) ----

    def output_locations(self) -> Dict[int, tuple]:
        """Classic net mode: reduce task → (addr, name, crc) of every
        committed ``mr-out-<r>`` so far — the driver fetches these over
        the stream transport as they appear."""
        with self.mu:
            return dict(self._out_locs)

    def final_locations(self) -> Dict[int, tuple]:
        """Shard net mode: sid → (addr, name, crc) of every committed
        shard output so far (the net twin of :meth:`final_outputs`)."""
        with self.mu:
            out: Dict[int, tuple] = {}
            for sid, shard in self._shards.items():
                if shard["committed"] is not None \
                        and shard.get("net_loc"):
                    aid, crc = shard["committed"]
                    a, name = shard["net_loc"]
                    out[sid] = (a, name, crc)
            return out

    def refetch_reduce(self, r: int) -> bool:
        """Driver-side re-fetch-from-replacement: the committed
        ``mr-out-<r>``'s server died before the driver could fetch it.
        Forget the completion — ``c_reduce`` drops, ``done()`` flips
        back, and a live worker re-runs the reduce (its inputs are
        re-fetchable; a lost PRODUCER resurfaces as that re-run's own
        ``FetchFailed``).  Returns True if re-queued."""
        with self.mu:
            if not (0 <= r < self.n_reduce) \
                    or self.reduce_log[r] != LOG_COMPLETED:
                return False
            self.reduce_log[r] = LOG_UNTOUCHED
            self.c_reduce -= 1
            heapq.heappush(self._reduce_ready, r)
            self._out_locs.pop(r, None)
            self._net_counters["net_refetches"] += 1
            _event("refetch", kind="reduce", task=r)
            print(f"coordinator: output mr-out-{r} unreachable; "
                  f"re-executing reduce {r}", file=sys.stderr)
        return True

    def refetch_shard(self, sid: int) -> bool:
        """Shard-mode re-fetch-from-replacement: the committed copy's
        server died.  Forget the commit and re-queue the shard — a NEW
        attempt id runs the first-commit-wins race afresh, so
        ``duplicate_commits`` (same-attempt double commit) stays
        structurally 0.  Returns True if re-queued."""
        with self.mu:
            shard = self._shards.get(sid)
            if shard is None or shard["committed"] is None:
                return False
            aid, _crc = shard["committed"]
            shard["committed"] = None
            shard.pop("net_loc", None)
            shard["status"] = LOG_UNTOUCHED
            for att in shard["attempts"].values():
                att["cancelled"] = True  # every old attempt is stale
            heapq.heappush(self._shard_ready, sid)
            self._net_counters["net_refetches"] += 1
            self._spec["requeues"] += 1
            _event("refetch", kind="shard", task=sid,
                   lost_attempt=aid)
            print(f"coordinator: shard {sid} output (attempt a{aid}) "
                  f"unreachable; re-executing", file=sys.stderr)
        return True

    def net_stats(self) -> dict:
        """Net-plane counter snapshot (schema-pinned keys) plus the
        location-registry sizes — the net harness's and bench row's
        evidence surface."""
        with self.mu:
            out = dict(self._net_counters)
            out["map_locations"] = len(self._map_locs)
            out["output_locations"] = len(self._out_locs)
        return out

    # ---- internals ----

    def _touch(self, wid: str) -> None:
        """Refresh a worker's heartbeat and record the contact gap into
        its histogram.  Caller holds ``self.mu``."""
        now = time.monotonic()
        prev = self._worker_seen.get(wid)
        if prev is not None:
            self._hb_hist.setdefault(
                wid, LatencyHistogram()).record(now - prev)
        self._worker_seen[wid] = now

    def _classify(self, wid: str, now: float):
        """``(heartbeat_age_s, p99_s, presumed)`` for a worker —
        percentile-aware: silence beyond 2x the worker's OWN p99
        contact gap reads as a dead worker (its cadence stopped);
        silence still within cadence norms reads as a slow task — the
        case the backup dispatcher should split rather than abandon.
        No gap data yet → unknown, never a guess.  Caller holds
        ``self.mu``."""
        seen = self._worker_seen.get(wid)
        hb_age = round(now - seen, 3) if seen is not None else None
        h = self._hb_hist.get(wid)
        hb_p99 = (round(h.percentile(0.99), 3)
                  if h is not None and h.count else None)
        presumed = "unknown"
        if hb_age is not None and hb_p99 is not None:
            presumed = "dead" if hb_age > 2 * hb_p99 else "slow-task"
        return hb_age, hb_p99, presumed

    # ---- shard-scheduler internals (caller holds self.mu) ----

    def _shard_ckpt_root(self) -> str:
        return (self.shard_opts.get("ckpt_root")
                or os.path.join(os.path.abspath(self.config.workdir),
                                ".shards"))

    def _shard_out_path(self, sid: int) -> str:
        return os.path.join(os.path.abspath(self.config.workdir),
                            f"mr-shard-out-{sid}")

    def _shard_part_path(self, sid: int, aid: int) -> str:
        return self._shard_out_path(sid) + f".a{aid}.part"

    def _pop_untouched_shard(self, wid: str = "") -> Optional[int]:
        if self.net and wid:
            # Locality preference (net mode): a re-queued shard whose
            # best checkpoint chain was written by THIS worker resumes
            # from that chain only here — everywhere else the chain is
            # unreachable (private workdirs) and the attempt restarts
            # from zero.  Prefer it; the stale heap entry is lazily
            # invalidated like any other.
            for sid in sorted(self._shards):
                shard = self._shards[sid]
                if shard["status"] != LOG_UNTOUCHED:
                    continue
                best = self._best_resume_from(shard)
                if best is not None \
                        and shard["attempts"][best]["worker"] == wid:
                    self._net_counters["locality_hits"] += 1
                    _event("locality_hit", kind="shard", task=sid,
                           worker=wid)
                    return sid
        while self._shard_ready:
            sid = heapq.heappop(self._shard_ready)
            if self._shards[sid]["status"] == LOG_UNTOUCHED:
                return sid
        return None

    # ---- net-plane internals (caller holds self.mu) ----

    def _preferred_host(self, r: int) -> Optional[str]:
        """The address holding the largest share of reduce partition
        ``r``'s input bytes (ties: least-loaded first, then address
        order) — Dean & Ghemawat §3.1 step 4's "takes the location of
        the input into account" applied to the shuffle."""
        share: Dict[str, int] = {}
        for m, a in self._map_locs.items():
            sizes = self._map_sizes.get(m)
            n = sizes[r] if sizes and r < len(sizes) else 0
            share[a] = share.get(a, 0) + n
        if not share:
            return None
        load: Dict[str, int] = {}
        for w in self._task_worker.values():
            a = self._net_addrs.get(w)
            if a:
                load[a] = load.get(a, 0) + 1
        addr, top = max(share.items(),
                        key=lambda kv: (kv[1], -load.get(kv[0], 0),
                                        kv[0]))
        return addr if top > 0 else None

    def _pick_reduce_locked(self, addr: str) -> Optional[int]:
        """Locality-aware reduce assignment: among the untouched reduce
        tasks prefer one whose preferred host IS the requester — its
        largest input share becomes local spool reads instead of wire
        bytes (``locality_hits`` counts these).  Falls back to the
        reference's lowest-index order; the ready heap's stale entry
        for a preferred pick is lazily invalidated."""
        if addr:
            for r in range(self.n_reduce):
                if self.reduce_log[r] != LOG_UNTOUCHED:
                    continue
                if self._preferred_host(r) == addr:
                    self._net_counters["locality_hits"] += 1
                    _event("locality_hit", kind="reduce", task=r,
                           addr=addr)
                    return r
        return self._pop_untouched(self._reduce_ready, self.reduce_log)

    def _new_attempt(self, sid: int, wid: str, kind: str, now: float):
        """Create + arm one attempt; takeovers/backups carry the best
        known checkpoint chain as their resume hint."""
        shard = self._shards[sid]
        aid = shard["next_aid"]
        shard["next_aid"] = aid + 1
        att = {"worker": wid, "kind": kind, "assigned": now,
               "last_progress": now, "progressed": False, "confirmed": 0,
               "ckpts": 0, "cursor": 0, "resume_cursor": 0, "dead": False,
               "cancelled": False,
               "resume_from": (self._best_resume_from(shard)
                               if kind != "primary" else None)}
        shard["attempts"][aid] = att
        shard["status"] = LOG_IN_PROGRESS
        self._arm_shard_timeout(sid, aid)
        return sid, aid, shard, att

    @staticmethod
    def _best_resume_from(shard: dict) -> Optional[int]:
        """The attempt whose chain a new attempt should adopt: most
        durable checkpoints wins (dead attempts count — their chains
        are on disk; that is the whole point of resuming a killed
        shard), newest attempt breaking ties."""
        best = None
        for aid, att in shard["attempts"].items():
            if att["ckpts"] <= 0:
                continue
            if best is None or (att["ckpts"], aid) > best[1]:
                best = (aid, (att["ckpts"], aid))
        return best[0] if best is not None else None

    def _setup_grace_s(self) -> float:
        """Grace for an attempt that has never progressed: it is still
        paying engine setup (jax init + first compiles), and N cold
        attempts SERIALIZE their compiles when workers share few cores
        — so the expected setup wall is N times the single-attempt
        grace.  Scaling by the live never-progressed attempt count is
        self-correcting: as attempts start progressing the count (and
        the grace) shrinks back to ``spec_setup_s``."""
        n_setup = 0
        for shard in self._shards.values():
            for atts in ([shard["attempts"]]
                         + [s["attempts"] for s in
                            (shard["subs"] or {}).values()]):
                for a in atts.values():
                    if (not a["dead"] and not a["cancelled"]
                            and not a["progressed"]):
                        n_setup += 1
        return self.config.spec_setup_s * max(1, n_setup)

    def _maybe_backup(self, wid: str, now: float):
        """Speculative dispatch: hand this idle worker a BACKUP attempt
        of the worst suspect shard.  Two triggers, both percentile-
        aware (§3.6 — back up remaining in-progress work when it is
        abnormally SILENT or abnormally SLOW):

        * **silent** — the newest live attempt's progress-RPC silence
          exceeds ``max(spec_k * p99(its worker's contact gaps),
          spec_floor_s)``; an attempt that has never reported progress
          is still in engine setup (jax init + compiles) and gets at
          least ``spec_setup_s`` of grace;
        * **slow** — the attempt is heartbeating but its total age
          exceeds ``spec_k`` times the LONGEST committed shard's
          assignment→commit wall (only armed once a reference wall
          exists — early in the job nothing is "abnormal" yet).

        At most two live attempts per shard; never backs a worker up
        with itself."""
        ref_wall = max(self._commit_walls) if self._commit_walls else None
        best = None
        best_age = 0.0
        best_reason = ""
        for sid, shard in self._shards.items():
            if shard["committed"] is not None \
                    or shard["status"] != LOG_IN_PROGRESS:
                continue
            if shard["subs"]:
                # A re-split shard's remaining work is its subs: a
                # whole-range backup would redo bytes the subs own.
                continue
            live = [(aid, a) for aid, a in shard["attempts"].items()
                    if not a["dead"] and not a["cancelled"]]
            if not live or len(live) >= 2:
                continue
            if shard["next_aid"] >= self.config.shard_max_attempts:
                continue
            aid_f, freshest = max(live,
                                  key=lambda kv: kv[1]["last_progress"])
            if freshest["worker"] == wid:
                continue
            age = now - freshest["last_progress"]
            total_age = now - freshest["assigned"]
            h = self._hb_hist.get(freshest["worker"])
            p99 = h.percentile(0.99) if h is not None and h.count else 0.0
            thr = max(self.config.spec_k * p99, self.config.spec_floor_s)
            if not freshest["progressed"]:
                thr = max(thr, self._setup_grace_s())
            silent = age > thr
            slow = (ref_wall is not None and freshest["progressed"]
                    and total_age > self.config.spec_k * ref_wall)
            if not (silent or slow):
                continue
            if total_age > best_age:
                best, best_age = (sid, aid_f, freshest), total_age
                best_reason = "silent" if silent else "slow"
        if best is None:
            return None
        sid, aid_f, freshest = best
        shard = self._shards[sid]
        assignment = self._new_attempt(sid, wid, "backup", now)
        shard["backups"] += 1
        self._spec["backup_dispatches"] += 1
        hb_age, hb_p99, presumed = self._classify(freshest["worker"], now)
        get_registry().set_gauge("dsi_shard_backup_dispatches",
                                 self._spec["backup_dispatches"])
        _event("backup_dispatch", kind="shard", task=sid,
               attempt=assignment[1], straggler_attempt=aid_f,
               straggler_worker=freshest["worker"] or None,
               backup_worker=wid or None, reason=best_reason,
               attempt_age_s=round(best_age, 3),
               heartbeat_age_s=hb_age, heartbeat_p99_s=hb_p99,
               presumed=presumed,
               resume_from=assignment[3]["resume_from"])
        print(f"coordinator: backup dispatch shard {sid}: attempt "
              f"a{aid_f} (worker={freshest['worker'] or '?'}) "
              f"{best_reason} for {best_age:.3f}s presumed={presumed}; "
              f"backup a{assignment[1]} -> {wid or '?'} resume_from="
              f"{assignment[3]['resume_from']}", file=sys.stderr)
        return assignment

    def _requeue_shard_locked(self, sid: int) -> None:
        """Back to the ready heap with a resume hint — unless a live
        attempt remains (a backup is still running: it IS the retry),
        the shard already committed, or the attempt budget is spent
        (job fails loudly rather than looping a poisoned shard)."""
        shard = self._shards[sid]
        if shard["committed"] is not None:
            return
        if shard["subs"]:
            # The subs partition the whole range: they ARE the retry of
            # a re-split shard; never re-queue the full range.
            return
        if any(not a["dead"] and not a["cancelled"]
               for a in shard["attempts"].values()):
            return
        if shard["next_aid"] >= self.config.shard_max_attempts:
            self.job_failed = True
            _event("shard_exhausted", kind="shard", task=sid,
                   attempts=shard["next_aid"])
            print(f"coordinator: shard {sid} failed "
                  f"{shard['next_aid']} attempts; job failed",
                  file=sys.stderr)
            return
        # The resume hint is recomputed at assignment time
        # (_new_attempt → _best_resume_from), so requeueing records
        # nothing here beyond readiness.
        shard["status"] = LOG_UNTOUCHED
        heapq.heappush(self._shard_ready, sid)
        self._spec["requeues"] += 1
        get_registry().set_gauge("dsi_shard_requeues",
                                 self._spec["requeues"])

    def _arm_shard_timeout(self, sid: int, aid: int) -> None:
        """Progress-based deadline for one attempt: the watchdog
        re-arms while progress RPCs keep landing, and presumes the
        attempt dead only after ``shard_timeout_s`` of silence.
        Caller holds ``self.mu``."""
        entry = (time.monotonic() + self.config.shard_timeout_s,
                 "shard", sid, aid)
        heapq.heappush(self._deadlines, entry)
        if self._deadlines[0] is entry:
            self._deadline_cv.notify()

    # ---- re-split internals (caller holds self.mu) ----

    @staticmethod
    def _split_resolved(shard: dict) -> bool:
        """Every sub-range of a re-split shard committed — the split as
        a whole resolved the shard."""
        subs = shard.get("subs")
        return bool(subs) and all(s["committed"] is not None
                                  for s in subs.values())

    def _shard_resolved(self, shard: dict) -> bool:
        """A shard needs no further work: its full range committed, or
        its re-split's sub-ranges all committed."""
        return shard["committed"] is not None \
            or self._split_resolved(shard)

    def _sub_out_path(self, sid: int, k: int) -> str:
        return self._shard_out_path(sid) + f".s{k}"

    def _sub_part_path(self, sid: int, k: int, aid: int) -> str:
        return self._sub_out_path(sid, k) + f".a{aid}.part"

    def _make_subs(self, sid: int, ranges, parent_chain) -> None:
        """Materialize a re-split's sub-shard state and queue every
        sub for dispatch.  ``parent_chain`` names the straggler attempt
        whose checkpoint chain sub 0 (the prefix covering the
        straggler's confirmed progress) adopts."""
        shard = self._shards[sid]
        subs = {}
        for k, (s, e) in enumerate(ranges):
            subs[k] = {"spec": (int(s), int(e)),
                       "status": LOG_UNTOUCHED, "attempts": {},
                       "next_aid": 0, "committed": None,
                       "parent_chain": (parent_chain if k == 0 else None)}
            heapq.heappush(self._sub_ready, (sid, k))
        shard["subs"] = subs

    def _pop_untouched_sub(self) -> Optional[tuple]:
        while self._sub_ready:
            sid, k = heapq.heappop(self._sub_ready)
            shard = self._shards[sid]
            if shard["committed"] is not None:
                continue  # the full-range commit overran the split
            sub = (shard["subs"] or {}).get(k)
            if sub is not None and sub["status"] == LOG_UNTOUCHED:
                return sid, k
        return None

    def _assign_sub(self, sid: int, k: int, wid: str, now: float) -> dict:
        """Create one sub-shard attempt and build its assignment reply:
        ``Start``/``End`` are the sub-range the attempt READS;
        ``TagStart``/``TagEnd`` are the parent shard's range — the
        checkpoint-chain identity tag sub 0 needs to adopt the
        straggler's chain (a chain's cursors are range-relative, and
        the parent's prefix IS sub 0's stream)."""
        shard = self._shards[sid]
        sub = shard["subs"][k]
        aid = sub["next_aid"]
        sub["next_aid"] = aid + 1
        att = {"worker": wid, "kind": "sub", "assigned": now,
               "last_progress": now, "progressed": False, "confirmed": 0,
               "ckpts": 0, "cursor": 0, "resume_cursor": 0, "dead": False,
               "cancelled": False,
               "resume_from": (self._best_resume_from(sub)
                               if sub["attempts"] else None)}
        sub["attempts"][aid] = att
        sub["status"] = LOG_IN_PROGRESS
        self._arm_sub_timeout(sid, k, aid)
        self._spec["subshard_dispatches"] += 1
        spec = shard["spec"]
        s, e = sub["spec"]
        reply = {"TaskStatus": int(TaskStatus.SHARD), "Shard": sid,
                 "Sub": k, "Attempt": aid, "Start": s, "End": e,
                 "TagStart": spec.start, "TagEnd": spec.end,
                 "Files": self.files, "NShards": self.n_shards,
                 "ResumeFrom": att["resume_from"],
                 "ParentChain": sub["parent_chain"],
                 "Knobs": self.shard_opts.get("knobs", {}),
                 "CkptRoot": self._shard_ckpt_root(),
                 "OutPart": self._sub_part_path(sid, k, aid)}
        if self.net:  # same share-nothing shape as the full-range reply
            reply["Net"] = True
            reply["OutPart"] = os.path.basename(reply["OutPart"])
            reply["CkptRoot"] = ".shards"
        _event("assign", kind="subshard", task=sid, sub=k,
               attempt=aid, worker=wid or None, start=s, end=e,
               resume_from=att["resume_from"],
               parent_chain=sub["parent_chain"])
        return reply

    def _arm_sub_timeout(self, sid: int, k: int, aid: int) -> None:
        entry = (time.monotonic() + self.config.shard_timeout_s,
                 "sub", sid, k, aid)
        heapq.heappush(self._deadlines, entry)
        if self._deadlines[0] is entry:
            self._deadline_cv.notify()

    def _maybe_resplit(self, wid: str, now: float) -> Optional[tuple]:
        """Dynamic re-split — the elastic alternative to a whole-range
        backup: when a shard's single live attempt trips the same
        percentile-aware silent/slow triggers as ``_maybe_backup``, cut
        the REMAINDER of its range (from the attempt's live reported
        cursor, newline-aligned) into sub-shards, journal the split,
        and hand the first sub to this idle worker.  The straggler is
        NOT cancelled: it keeps racing its own split, and
        first-commit-wins arbitrates (``commit_shard``).  Returns a
        dispatchable ``(sid, k)`` or None — None also when the
        remainder is too small to amortize an engine setup
        (``spec_resplit_min_bytes``), in which case the caller's backup
        path still covers the shard.  ONE split level: a sub-shard is
        never re-split, only re-queued."""
        from dsi_tpu.mr.shards import split_remaining
        from dsi_tpu.obs import span

        ref_wall = max(self._commit_walls) if self._commit_walls else None
        best = None
        best_age = 0.0
        best_reason = ""
        for sid, shard in self._shards.items():
            if shard["committed"] is not None or shard["subs"] \
                    or shard["status"] != LOG_IN_PROGRESS:
                continue
            live = [(aid, a) for aid, a in shard["attempts"].items()
                    if not a["dead"] and not a["cancelled"]]
            if len(live) != 1:
                continue  # a backup already races it; don't also split
            aid_f, freshest = live[0]
            if freshest["worker"] == wid:
                continue
            age = now - freshest["last_progress"]
            total_age = now - freshest["assigned"]
            h = self._hb_hist.get(freshest["worker"])
            p99 = h.percentile(0.99) if h is not None and h.count else 0.0
            thr = max(self.config.spec_k * p99, self.config.spec_floor_s)
            if not freshest["progressed"]:
                thr = max(thr, self._setup_grace_s())
            silent = age > thr
            slow = (ref_wall is not None and freshest["progressed"]
                    and total_age > self.config.spec_k * ref_wall)
            if not (silent or slow):
                continue
            if total_age > best_age:
                best, best_age = (sid, aid_f, freshest), total_age
                best_reason = "silent" if silent else "slow"
        if best is None:
            return None
        sid, aid_f, freshest = best
        shard = self._shards[sid]
        ranges = split_remaining(
            self.files, shard["spec"], freshest["cursor"],
            self.config.spec_resplit_ways,
            self.config.spec_resplit_min_bytes)
        if ranges is None:
            return None
        if self._journal is not None:
            # Journaled BEFORE any dispatch: a crash between this record
            # and the first sub assignment replays into exactly this
            # sub-shard state, never a half-split shard.
            self._journal.record_resplit(sid, ranges)
        parent = aid_f if freshest["ckpts"] > 0 else None
        self._make_subs(sid, ranges, parent_chain=parent)
        self._spec["resplits"] += 1
        hb_age, hb_p99, presumed = self._classify(freshest["worker"], now)
        get_registry().set_gauge("dsi_shard_resplits",
                                 self._spec["resplits"])
        with span("resplit", lane="control", task=sid):
            _event("resplit_dispatch", kind="shard", task=sid,
                   straggler_attempt=aid_f,
                   straggler_worker=freshest["worker"] or None,
                   reason=best_reason, cursor=freshest["cursor"],
                   ranges=[[int(s), int(e)] for s, e in ranges],
                   parent_chain=parent,
                   attempt_age_s=round(best_age, 3),
                   heartbeat_age_s=hb_age, heartbeat_p99_s=hb_p99,
                   presumed=presumed)
        print(f"coordinator: re-split shard {sid}: attempt a{aid_f} "
              f"(worker={freshest['worker'] or '?'}) {best_reason} for "
              f"{best_age:.3f}s presumed={presumed}; cursor="
              f"{freshest['cursor']} -> {len(ranges)} sub-shards "
              f"{[(int(s), int(e)) for s, e in ranges]}",
              file=sys.stderr)
        return self._pop_untouched_sub()

    def _commit_sub_locked(self, shard: dict, sid: int, k: int,
                           aid: int, crc: int, wid: str) -> dict:
        """First-commit-wins for ONE sub-range (caller holds the lock):
        rename, journal the ``subshard`` record, cancel sub siblings;
        when this was the last open sub, the shard resolves "split" and
        the full-range straggler is cancelled."""
        sub = (shard["subs"] or {}).get(k)
        if sub is None:
            return {"Win": False}
        if shard["committed"] is not None or sub["committed"] is not None:
            self._spec["commit_losses"] += 1
            if sub["committed"] is not None \
                    and sub["committed"][0] == aid:
                self._spec["duplicate_commits"] += 1
            _event("subshard_commit_lose", kind="shard", task=sid,
                   sub=k, attempt=aid, worker=wid or None)
            return {"Win": False}
        part = self._sub_part_path(sid, k, aid)
        final = self._sub_out_path(sid, k)
        try:
            os.replace(part, final)
            fsync_dir(os.path.dirname(final) or ".")
        except OSError as e:
            _event("shard_commit_missing", kind="shard", task=sid,
                   sub=k, attempt=aid, error=str(e))
            return {"Win": False, "Error": f"partial missing: {e}"}
        if self._journal is not None:
            self._journal.record_subshard(sid, k, aid, crc)
        sub["committed"] = (aid, crc)
        sub["status"] = LOG_COMPLETED
        self._spec["subshard_commits"] += 1
        prefix = os.path.basename(final) + ".a"
        try:
            for name in os.listdir(os.path.dirname(final) or "."):
                if name.startswith(prefix) and name.endswith(".part"):
                    os.remove(os.path.join(
                        os.path.dirname(final), name))
        except OSError:
            pass
        for oaid, oatt in sub["attempts"].items():
            if oaid != aid:
                oatt["cancelled"] = True
        att = sub["attempts"].get(aid)
        if att is not None:
            att["last_progress"] = time.monotonic()
        resolved = self._split_resolved(shard)
        if resolved:
            shard["status"] = LOG_COMPLETED
            for fatt in shard["attempts"].values():
                fatt["cancelled"] = True
        _event("subshard_commit", kind="shard", task=sid, sub=k,
               attempt=aid, crc=crc, worker=wid or None,
               resolved=bool(resolved))
        get_registry().set_gauge("dsi_subshard_commits",
                                 self._spec["subshard_commits"])
        return {"Win": True}

    def _requeue_sub_locked(self, sid: int, k: int) -> None:
        shard = self._shards[sid]
        sub = (shard["subs"] or {}).get(k)
        if sub is None or sub["committed"] is not None \
                or shard["committed"] is not None:
            return
        if any(not a["dead"] and not a["cancelled"]
               for a in sub["attempts"].values()):
            return
        if sub["next_aid"] >= self.config.shard_max_attempts:
            self.job_failed = True
            _event("shard_exhausted", kind="shard", task=sid, sub=k,
                   attempts=sub["next_aid"])
            print(f"coordinator: shard {sid} sub {k} failed "
                  f"{sub['next_aid']} attempts; job failed",
                  file=sys.stderr)
            return
        sub["status"] = LOG_UNTOUCHED
        heapq.heappush(self._sub_ready, (sid, k))
        self._spec["requeues"] += 1
        get_registry().set_gauge("dsi_shard_requeues",
                                 self._spec["requeues"])

    def _expire_sub_attempt(self, sid: int, k: int, aid: int,
                            now: float) -> None:
        """The sub-shard twin of :meth:`_expire_shard_attempt`: re-arm
        while the sub attempt keeps progressing, else presume it dead
        and re-queue the sub-range."""
        shard = self._shards.get(sid)
        sub = (shard["subs"] or {}).get(k) if shard is not None else None
        att = sub["attempts"].get(aid) if sub is not None else None
        if (att is None or shard["committed"] is not None
                or sub["committed"] is not None or att["dead"]
                or att["cancelled"]):
            return
        idle = now - att["last_progress"]
        timeout = self.config.shard_timeout_s
        if not att["progressed"]:
            timeout = max(timeout, self._setup_grace_s())
        if idle < timeout:
            entry = (att["last_progress"] + timeout, "sub", sid, k, aid)
            heapq.heappush(self._deadlines, entry)
            return
        att["dead"] = True
        hb_age, hb_p99, presumed = self._classify(att["worker"], now)
        _event("requeue", kind="subshard", task=sid, sub=k,
               attempt=aid, timeout_s=self.config.shard_timeout_s,
               worker=att["worker"] or None, idle_s=round(idle, 3),
               heartbeat_age_s=hb_age, heartbeat_p99_s=hb_p99,
               presumed=presumed,
               reason="no progress past shard_timeout_s")
        print(f"coordinator: requeue shard {sid} sub {k} attempt "
              f"a{aid}: no progress for {idle:.3f}s (worker="
              f"{att['worker'] or '?'} presumed={presumed})",
              file=sys.stderr)
        self._requeue_sub_locked(sid, k)

    @staticmethod
    def _pop_untouched(ready: list[int], log: list[int]) -> Optional[int]:
        """Lowest untouched task index — the reference's first-match linear
        scan order (mr/coordinator.go:50-55) at O(log n).  Stale heap
        entries (task started or finished since pushed) are discarded."""
        while ready:
            i = heapq.heappop(ready)
            if log[i] == LOG_UNTOUCHED:
                return i
        return None

    def _arm_timeout(self, task_id: int, kind: str) -> None:
        """Presumed-dead-by-timeout: after task_timeout_s, if the task is
        still in-progress, reset it to untouched for reassignment
        (mr/coordinator.go:70-77,99-106).  Caller holds ``self.mu``."""
        entry = (time.monotonic() + self.config.task_timeout_s,
                 kind, task_id)
        heapq.heappush(self._deadlines, entry)
        # Wake the watchdog only when this entry becomes the earliest
        # deadline (with a constant timeout that means "heap was empty") —
        # otherwise its current sleep already covers it, and waking it on
        # every assignment would contend for self.mu on the hot path.
        if self._deadlines[0] is entry:
            self._deadline_cv.notify()

    def _watchdog(self) -> None:
        """The single straggler-monitor thread: sleep until the earliest
        armed deadline, then requeue any task still in-progress.

        A requeue is never silent (the reference reassigns without a
        word, and debugging a 10 s stall took strace-level archaeology):
        it logs the reason and the assignee's heartbeat age to stderr
        and the trace's control-plane lane, and republishes the
        per-worker heartbeat-age gauge — the signal speculative
        execution will consume (ROADMAP)."""
        with self._deadline_cv:
            while not self._closing:
                if not self._deadlines:
                    self._deadline_cv.wait()
                    continue
                now = time.monotonic()
                entry = self._deadlines[0]
                due, kind = entry[0], entry[1]
                if due > now:
                    self._deadline_cv.wait(timeout=due - now)
                    continue
                heapq.heappop(self._deadlines)
                if kind == "shard":
                    self._expire_shard_attempt(entry[2], entry[3], now)
                    continue
                if kind == "sub":
                    self._expire_sub_attempt(entry[2], entry[3],
                                             entry[4], now)
                    continue
                task_id = entry[2]
                log = self.map_log if kind == "map" else self.reduce_log
                if log[task_id] == LOG_IN_PROGRESS:
                    log[task_id] = LOG_UNTOUCHED
                    heapq.heappush(
                        self._map_ready if kind == "map"
                        else self._reduce_ready, task_id)
                    wid = self._task_worker.pop((kind, task_id), "")
                    ages = {w: round(now - t, 3)
                            for w, t in self._worker_seen.items()}
                    get_registry().set_gauge(
                        "mr_worker_heartbeat_age_s", ages)
                    # Percentile-aware classification (_classify):
                    # "dead" vs "slow-task" vs "unknown".
                    hb_age, hb_p99, presumed = self._classify(wid, now)
                    get_registry().set_gauge(
                        "mr_worker_heartbeat_hist",
                        {w: hh.snapshot()
                         for w, hh in self._hb_hist.items()})
                    _event("requeue", kind=kind, task=task_id,
                           timeout_s=self.config.task_timeout_s,
                           worker=wid or None, heartbeat_age_s=hb_age,
                           heartbeat_p99_s=hb_p99, presumed=presumed,
                           reason="in-progress past task_timeout_s")
                    print(f"coordinator: requeue {kind} task {task_id}: "
                          f"in-progress past "
                          f"{self.config.task_timeout_s}s (worker="
                          f"{wid or '?'} heartbeat_age="
                          f"{'%.3fs' % hb_age if hb_age is not None else 'n/a'}"
                          f" p99="
                          f"{'%.3fs' % hb_p99 if hb_p99 is not None else 'n/a'}"
                          f" presumed={presumed})",
                          file=sys.stderr)

    def _expire_shard_attempt(self, sid: int, aid: int,
                              now: float) -> None:
        """One popped shard deadline: re-arm while the attempt keeps
        making progress; past ``shard_timeout_s`` of silence, presume
        it dead (percentile-classified) and re-queue the shard with a
        resume hint at its best checkpoint chain — resume-from-
        checkpoint instead of replay-from-zero.  Caller holds
        ``self.mu`` (via the deadline condvar)."""
        shard = self._shards.get(sid)
        att = shard["attempts"].get(aid) if shard is not None else None
        if (att is None or shard["committed"] is not None or att["dead"]
                or att["cancelled"]):
            return
        idle = now - att["last_progress"]
        # An attempt that never retired a step is still paying engine
        # setup (jax init + first compiles): give it the concurrency-
        # scaled setup grace before presuming it dead.
        timeout = self.config.shard_timeout_s
        if not att["progressed"]:
            timeout = max(timeout, self._setup_grace_s())
        if idle < timeout:
            entry = (att["last_progress"] + timeout, "shard", sid, aid)
            heapq.heappush(self._deadlines, entry)
            return
        att["dead"] = True
        hb_age, hb_p99, presumed = self._classify(att["worker"], now)
        _event("requeue", kind="shard", task=sid, attempt=aid,
               timeout_s=self.config.shard_timeout_s,
               worker=att["worker"] or None, idle_s=round(idle, 3),
               heartbeat_age_s=hb_age, heartbeat_p99_s=hb_p99,
               presumed=presumed,
               reason="no progress past shard_timeout_s")
        print(f"coordinator: requeue shard {sid} attempt a{aid}: no "
              f"progress for {idle:.3f}s (worker="
              f"{att['worker'] or '?'} presumed={presumed})",
              file=sys.stderr)
        self._requeue_shard_locked(sid)

    # ---- lifecycle (mr/coordinator.go:121-160) ----

    def serve(self) -> None:
        """Start the RPC server (reference (*Coordinator).server())."""
        methods = {
            "Coordinator.RequestTask": self.request_task,
            # Reference names, [sic] typo preserved as aliases for wire parity:
            "Coordinator.RecieveMapComplete": self.map_complete,
            "Coordinator.RecieveReduceComplete": self.reduce_complete,
            "Coordinator.MapComplete": self.map_complete,
            "Coordinator.ReduceComplete": self.reduce_complete,
            "Coordinator.FetchFailed": self.fetch_failed,
        }
        if self.shard_plan is not None:
            methods.update({
                "Coordinator.RequestShard": self.request_shard,
                "Coordinator.ShardProgress": self.shard_progress,
                "Coordinator.CommitShard": self.commit_shard,
                "Coordinator.ShardFailed": self.shard_failed,
            })
        self._server = rpc.RpcServer(self.config.sock(), methods)
        self._server.start()

    def address(self) -> Optional[str]:
        """The dialable control-plane address, or None before serve()."""
        return self._server.address if self._server is not None else None

    def done(self) -> bool:
        """Job-completion poll (mr/coordinator.go:138-142); in shard
        mode, every shard committed (or the job declared failed)."""
        with self.mu:
            if self.shard_plan is not None:
                return self.job_failed or all(
                    self._shard_resolved(shard)
                    for shard in self._shards.values())
            return self.c_reduce == self.n_reduce

    def worker_heartbeat_ages(self) -> Dict[str, float]:
        """Seconds since each known worker's last RPC — the per-worker
        heartbeat-age gauge (also published to the obs registry at
        requeue time).  The straggler signal the speculative-execution
        item will dispatch backup tasks on."""
        now = time.monotonic()
        with self.mu:
            return {w: round(now - t, 3)
                    for w, t in self._worker_seen.items()}

    def worker_heartbeat_hists(self) -> Dict[str, Dict]:
        """Per-worker contact-gap histogram snapshots (pinned
        ``obs.HIST_SNAPSHOT_KEYS``) — the distribution behind
        :meth:`straggler_suspects`."""
        with self.mu:
            return {w: h.snapshot() for w, h in self._hb_hist.items()}

    def straggler_suspects(self, k: float = 2.0) -> Dict[str, float]:
        """Workers whose current silence exceeds ``max(k · p99(their
        own contact gaps), task_timeout_s)`` — {worker: age_s}.  THE
        armed hook for speculative execution: a backup dispatcher polls
        this instead of re-deriving staleness from raw ages, so its
        decision is percentile-aware per worker (a chatty worker going
        quiet trips far sooner than one that always polled slowly)."""
        now = time.monotonic()
        out: Dict[str, float] = {}
        with self.mu:
            for w, t in self._worker_seen.items():
                age = now - t
                h = self._hb_hist.get(w)
                p99 = h.percentile(0.99) if h is not None and h.count \
                    else 0.0
                if age > max(k * p99, self.config.task_timeout_s):
                    out[w] = round(age, 3)
        return out

    def close(self) -> None:
        with self._deadline_cv:
            self._closing = True
            self._deadline_cv.notify()
        # Join the watchdog (bounded: it wakes on the notify above) so
        # close() returns with no thread still touching coordinator state
        # — daemon-abandonment left a shutdown race window.  join() on a finished thread returns immediately, so
        # repeated close() calls are safe.
        self._monitor.join(timeout=5.0)
        if self._server is not None:
            self._server.close()
            self._server = None
        if self._journal is not None:
            self._journal.close()


def make_coordinator(files: List[str], n_reduce: int,
                     config: JobConfig | None = None) -> Coordinator:
    """Construct state and start the RPC server (mr/coordinator.go:149-160)."""
    c = Coordinator(files, n_reduce, config)
    c.serve()
    return c
