"""The resident serving daemon behind ``mrserve``.

One long-lived process owns the device mesh, the warmed AOT
executables, and a spool directory; tenants submit jobs over the
repo's framed-JSON pull-RPC control plane (``mr/rpc.py`` — the 6.5840
idiom the reference's coordinator already speaks) and the daemon:

* **admits by priority** (``serve/qos.py``): three strict FIFO lanes
  (``mrsubmit --priority``), per-tenant token-bucket rate limits, and a
  bounded queue — an over-rate or over-bound submission is SHED with a
  typed backpressure error carrying a retry-after hint (the client's
  bounded-retry contract), BEFORE any journal write, so shedding never
  loses an accepted job;
* **journals** every accepted submission durably
  (``spool/jobs/<id>.json`` through ``atomicio.write_bytes_durable``)
  BEFORE acking it, so a ``kill -9`` at any instant loses no accepted
  job;
* **packs** word-count tenants into shared device steps
  (``serve/pack.py``: K tenants ≈ 1 dispatch) AND grep tenants into
  shared lane-isolated dispatches (``PackedGrepScheduler``: rows
  grouped by pattern length, one compiled program per length whatever
  a tenant's lines look like) — everything else runs
  as resumable step objects (``parallel/stepobj.py``) on one scheduler
  thread; a single thread owns all jax work;
* **evicts by tail latency**: when the resident set is full and jobs
  wait, the victim is the tenant whose p99 packed-step wall
  (``obs/hist.KeyedHistograms`` fed every step) hurts the pack most —
  the step-quota rule stays as the fallback when no tenant has a
  meaningful tail yet.  Parked tenants resume from their
  delta-checkpoint chains on their next turn (or next submission, which
  re-prioritizes their parked jobs within their own priority lane);
* **resumes after a crash**: on boot every journaled job not marked
  done re-enters the queue with ``resume=True``; per-tenant chains
  restore the accumulators and cursors, and the re-run output is
  byte-identical to an uninterrupted run (the CI smoke kills the
  daemon with ``kill -9`` mid-job and diffs against the sequential
  oracle);
* **reports**: a ``tenants`` section on ``/statusz`` and labeled
  ``dsi_serve_*`` series on ``/metrics`` via the live-telemetry
  section hooks (``obs/live.py``).  Every emitted series is registered
  in ``obs/registry.SERVE_SERIES`` (the dsicheck metric-schema rule
  enforces it), and per-tenant series are CAPPED at
  ``DSI_SERVE_METRICS_TENANTS`` worst-p99 tenants, so a
  thousands-of-tenants soak keeps /metrics bounded.

Spool hygiene at boot: ``.tmp-*`` orphans are reaped across the spool
(``atomicio.reap_tmp_files``), and checkpoint chains of tenants whose
jobs are all done age out after ``retention_s`` — a live (unfinished)
job's chain is never touched, and within a live chain the store's own
chain-aware GC (PR 8) keeps retention safe.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
from typing import Dict, List, Optional

from dsi_tpu.mr.rpc import RpcServer
from dsi_tpu.obs import count as _count, metrics_scope, span as _span
from dsi_tpu.obs.hist import KeyedHistograms
from dsi_tpu.serve import qos
from dsi_tpu.serve.client import default_socket
from dsi_tpu.utils.atomicio import (
    read_bytes_verified,
    reap_tmp_files,
    write_bytes_durable,
)

#: Apps the daemon serves.  ``wc`` rides the packed wave scheduler;
#: ``grep`` rides the packed grep scheduler (lane-isolated rows — see
#: serve/pack.py) unless ``pack_grep`` is off, in which case it runs as
#: a time-multiplexed resumable step object (the bench's control arm).
SERVE_APPS = ("wc", "grep")

_JOB_FIELDS = ("job_id", "tenant", "app", "files", "n_reduce", "out_dir",
               "pattern", "priority", "state", "submitted_ts", "done_ts",
               "error", "stats")

#: Tenant ids become path components (journal names, chain dirs): a
#: plain slug, no separators, no leading dot.
_TENANT_RE = re.compile(r"[A-Za-z0-9_-][A-Za-z0-9._-]{0,63}")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class ServeDaemon:
    """One ``mrserve`` process (module docstring)."""

    def __init__(self, spool: str, socket_path: Optional[str] = None,
                 n_reduce: int = 10, chunk_bytes: int = 1 << 16,
                 devices: Optional[int] = None,
                 max_resident: int = 8, quota_steps: int = 64,
                 checkpoint_every: Optional[int] = 8,
                 retention_s: float = 14 * 86400.0,
                 warm: bool = True,
                 max_queue: int = 1024,
                 rate_limit: Optional[float] = None,
                 rate_burst: int = 4,
                 pack_grep: Optional[bool] = None,
                 evict_min_samples: int = 8,
                 metrics_tenants: Optional[int] = None,
                 clock=time.monotonic,
                 admit_hook=None):
        self.spool = os.path.abspath(spool)
        self.jobs_dir = os.path.join(self.spool, "jobs")
        self.tenants_dir = os.path.join(self.spool, "tenants")
        self.out_dir = os.path.join(self.spool, "out")
        for d in (self.spool, self.jobs_dir, self.tenants_dir,
                  self.out_dir):
            os.makedirs(d, exist_ok=True)
        self.socket_path = socket_path or default_socket(self.spool)
        self.n_reduce = int(n_reduce)
        # One chunk-width truth: the packer rounds to a pow2 >= 256 (the
        # wave program's size contract), so the lanes must cut rows at
        # exactly that width or the batch fill would shape-mismatch.
        self.chunk_bytes = 1 << max(8, int(chunk_bytes - 1).bit_length())
        self.devices = devices
        self.max_resident = max(1, int(max_resident))
        self.quota_steps = max(1, int(quota_steps))
        self.checkpoint_every = checkpoint_every
        self.retention_s = float(retention_s)
        self.warm = warm
        self.max_queue = max(1, int(max_queue))
        self.rate_limit = rate_limit
        self.rate_burst = max(1, int(rate_burst))
        if pack_grep is None:
            pack_grep = os.environ.get("DSI_SERVE_PACK_GREP", "1") != "0"
        self.pack_grep = bool(pack_grep)
        self.evict_min_samples = max(1, int(evict_min_samples))
        if metrics_tenants is None:
            metrics_tenants = _env_int("DSI_SERVE_METRICS_TENANTS", 32)
        self.metrics_tenants = max(1, int(metrics_tenants))
        self._clock = clock
        # Replicated control plane (dsi_tpu/replica): called with the
        # persisted job record BEFORE the local journal write and the
        # ack — it blocks until the admission is majority-replicated,
        # or raises, in which case the submission is NOT admitted (no
        # spool state, typed error to the client).  None = single-node.
        self.admit_hook = admit_hook

        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._stop = threading.Event()
        self.ready = threading.Event()
        self._jobs: Dict[str, Dict] = {}
        self._queue = qos.PriorityQueue()
        self._resident: Dict[str, Dict] = {}
        self._tenants: Dict[str, Dict] = {}
        self._buckets: Dict[str, qos.TokenBucket] = {}
        # Admission/eviction counters.  A plain dict, not an engine
        # metrics scope: these are control-plane events, surfaced as
        # dsi_serve_* series (SERVE_SERIES), not step-pipeline stats.
        self._qos = {"shed": 0, "rate_limited": 0, "evict_p99": 0,
                     "evict_quota": 0}
        # What the daemon's own work costs, beside the packers' scopes:
        # the seconds of its spans and the counts of what it did.  The
        # lanes' checkpoint writers add their ckpt_* keys here too.
        # Every key is set here, so that a reader on an RPC thread
        # (``Status``) copies a dict whose size does not change.
        self.stats = metrics_scope("serve_daemon")
        self.stats.update({"submits": 0, "submit_s": 0.0, "admit_s": 0.0,
                           "evict_s": 0.0, "finish_s": 0.0, "ckpt_s": 0.0,
                           "evictions": 0, "resumes": 0, "jobs_done": 0})
        # Per-tenant packed-step wall distributions — the eviction
        # policy's evidence and the bounded /metrics tenant selector.
        self._hist = KeyedHistograms()
        # Job-completion gap distribution (separate instance: _hist is
        # keyed by tenant and drives EVICTION — a pseudo-key there
        # would become an eviction candidate).  Feeds the measured
        # drain rate behind the queue-full retry-after hint.
        self._drain_hist = KeyedHistograms()
        self._last_done_ts: Optional[float] = None
        self._seq = 0
        self.packer = None
        self.grep_packer = None
        self.boot_reaped = 0
        self.boot_gc_chains = 0

        self._boot_hygiene()
        self._load_journal()
        self._rpc = RpcServer(self.socket_path, {
            "Submit": self._rpc_submit,
            "Status": self._rpc_status,
            "Ping": self._rpc_ping,
            "Shutdown": self._rpc_shutdown,
        })
        self._thread = threading.Thread(target=self._scheduler,
                                        name="dsi-mrserve-scheduler",
                                        daemon=True)

    # ── boot ──

    def _boot_hygiene(self) -> None:
        """Satellite: reap ``.tmp-*`` orphans everywhere a crashed run
        can leave them, and age out dead tenants' checkpoint chains."""
        n = 0
        roots = [self.spool, self.jobs_dir, self.out_dir,
                 self.tenants_dir]
        trace_dir = os.environ.get("DSI_TRACE_DIR")
        if trace_dir:
            roots.append(trace_dir)
        for t in list(os.listdir(self.tenants_dir)):
            tdir = os.path.join(self.tenants_dir, t)
            if os.path.isdir(tdir):
                roots.append(tdir)
                roots.extend(os.path.join(tdir, j)
                             for j in os.listdir(tdir)
                             if os.path.isdir(os.path.join(tdir, j)))
        for d in roots:
            try:
                n += reap_tmp_files(d)
            except OSError:
                pass
        self.boot_reaped = n

    def _gc_aged_chains(self) -> None:
        """Delete whole per-job chain dirs whose job is done (or
        unknown) and untouched past the retention age.  A live chain is
        never a candidate — its base stays protected — and within live
        chains the store's chain-aware GC already bounds growth."""
        now = time.time()
        live = {jid for jid, j in self._jobs.items()
                if j["state"] != "done"}
        for t in list(os.listdir(self.tenants_dir)):
            tdir = os.path.join(self.tenants_dir, t)
            if not os.path.isdir(tdir):
                continue
            for jid in list(os.listdir(tdir)):
                jdir = os.path.join(tdir, jid)
                if not os.path.isdir(jdir) or jid in live:
                    continue
                try:
                    mtimes = [os.path.getmtime(os.path.join(jdir, f))
                              for f in os.listdir(jdir)] or \
                             [os.path.getmtime(jdir)]
                    if now - max(mtimes) > self.retention_s:
                        shutil.rmtree(jdir, ignore_errors=True)
                        self.boot_gc_chains += 1
                except OSError:
                    continue

    def _load_journal(self) -> None:
        """Re-enter every journaled job; unfinished ones re-queue with
        their chains — the crash-resume half of the daemon contract."""
        for name in sorted(os.listdir(self.jobs_dir)):
            if not name.endswith(".json"):
                continue
            raw = read_bytes_verified(os.path.join(self.jobs_dir, name))
            if raw is None:
                continue  # torn journal entry: the submit never acked
            try:
                job = json.loads(raw)
            except ValueError:
                continue
            job.setdefault("priority", qos.DEFAULT_PRIORITY)
            job.setdefault("done_ts", None)
            self._jobs[job["job_id"]] = job
            self._tenant(job["tenant"])["jobs"] += 1
            try:
                self._seq = max(self._seq,
                                int(job["job_id"].rsplit("-", 1)[1]) + 1)
            except (IndexError, ValueError):
                pass
            if job["state"] == "done":
                self._tenant(job["tenant"])["done"] += 1
            elif job["state"] == "failed":
                pass
            else:
                job["state"] = "queued"
                self._queue.push(job["job_id"], job["priority"])
        self._gc_aged_chains()

    # ── bookkeeping ──

    def _tenant(self, tenant: str) -> Dict:
        return self._tenants.setdefault(tenant, {
            "jobs": 0, "done": 0, "steps": 0, "rows": 0,
            "evictions": 0, "resumes": 0, "resume_gap_s": 0.0,
            "hostpath": 0})

    def _persist(self, job: Dict) -> None:
        rec = {k: job.get(k) for k in _JOB_FIELDS}
        write_bytes_durable(
            os.path.join(self.jobs_dir, f"{job['job_id']}.json"),
            json.dumps(rec, sort_keys=True).encode("utf-8"))

    def _drain_jobs_per_sec(self) -> float:
        """The measured service rate behind ``qos.shed_retry_after``:
        the median completion gap inverted (KeyedHistograms evidence,
        same instrument the eviction policy trusts).  0.0 until at
        least two jobs finished — callers fall back to the cold-start
        linear hint."""
        h = self._drain_hist.get("gap")
        if h is None or h.count < 2:
            return 0.0
        p50 = h.percentile(0.5)
        return 1.0 / p50 if p50 > 0.0 else 0.0

    # ── RPC handlers (no jax; scheduler owns the device) ──

    def _rpc_submit(self, args: dict) -> dict:
        """The ``submit`` span: validation, the durable journal write,
        the reply.  RPC threads run this side by side, so each times
        itself and adds its seconds under the lock."""
        took: Dict = {}
        with _span("submit", stats=took, key="submit_s",
                   tenant=str(args.get("tenant") or "default")) as sp:
            reply = self._submit(args)
            sp.set(job=reply.get("job_id"))
        with self._lock:
            self.stats["submits"] += 1
            self.stats["submit_s"] += took["submit_s"]
        return reply

    def _submit(self, args: dict) -> dict:
        tenant = str(args.get("tenant") or "default")
        # The tenant id is spliced into journal filenames and chain
        # paths: a separator or dot-dot would write outside the spool
        # (and dodge the hygiene walks), so the id must be a slug.
        if not _TENANT_RE.fullmatch(tenant):
            return {"error": f"invalid tenant {tenant!r}: want "
                             f"[A-Za-z0-9._-]{{1,64}} with no leading "
                             f"dot"}
        app = str(args.get("app") or "wc")
        files = [os.path.abspath(f) for f in (args.get("files") or [])]
        if app not in SERVE_APPS:
            return {"error": f"unknown app {app!r} (have {SERVE_APPS})"}
        if not files:
            return {"error": "no input files"}
        missing = [f for f in files if not os.path.isfile(f)]
        if missing:
            return {"error": f"missing input files: {missing}"}
        n_reduce = int(args.get("n_reduce") or self.n_reduce)
        if n_reduce != self.n_reduce:
            # The packed step computes partitions on device with the
            # daemon's n_reduce; a per-job degree cannot share it.
            return {"error": f"n_reduce {n_reduce} != daemon's "
                             f"{self.n_reduce} (packing shares one "
                             f"partition degree)"}
        pattern = args.get("pattern")
        if app == "grep" and not pattern:
            return {"error": "grep needs a pattern"}
        priority = args.get("priority")
        if priority is None:
            priority = qos.DEFAULT_PRIORITY
        try:
            priority = int(priority)
        except (TypeError, ValueError):
            return {"error": f"invalid priority {priority!r}"}
        if priority not in qos.PRIORITIES:
            return {"error": f"invalid priority {priority} "
                             f"(want one of {qos.PRIORITIES})"}
        with self._wake:
            # Admission policy, BEFORE the journal write: a shed or
            # rate-limited submission leaves no spool state, so
            # backpressure can never manufacture a lost accepted job.
            if self.rate_limit is not None:
                bucket = self._buckets.get(tenant)
                if bucket is None:
                    bucket = qos.TokenBucket(self.rate_limit,
                                             self.rate_burst,
                                             clock=self._clock)
                    self._buckets[tenant] = bucket
                hint = bucket.take()
                if hint > 0.0:
                    self._qos["rate_limited"] += 1
                    return qos.backpressure_reply(
                        f"tenant {tenant!r} over submit rate "
                        f"({self.rate_limit}/s, burst "
                        f"{self.rate_burst})", hint)
            queued = len(self._queue)
            if queued >= self.max_queue:
                self._qos["shed"] += 1
                # Deeper backlog → longer hint, scaled by the MEASURED
                # drain rate (qos.shed_retry_after): the hint predicts
                # when a slot plausibly opens, not a fixed slope.
                hint = qos.shed_retry_after(queued,
                                            self._drain_jobs_per_sec())
                return qos.backpressure_reply(
                    f"queue full ({queued} >= {self.max_queue})", hint)
            jid = f"{tenant}-{self._seq:06d}"
            self._seq += 1
            job = {"job_id": jid, "tenant": tenant, "app": app,
                   "files": files, "n_reduce": n_reduce,
                   "out_dir": os.path.join(self.out_dir, jid),
                   "pattern": pattern, "priority": priority,
                   "state": "queued",
                   "submitted_ts": round(time.time(), 3),
                   "done_ts": None, "error": None, "stats": {}}
            if self.admit_hook is not None:
                # Replicated admission (dsi_tpu/replica): majority-
                # commit the record BEFORE any local state, so a leader
                # cut off from its group cannot ack a job the group
                # never heard of.  Raises on failure — caught by the
                # replica node's typed-reply wrapper; no spool state
                # was created, same shed contract as above.
                self.admit_hook({k: job.get(k) for k in _JOB_FIELDS})
            self._persist(job)  # durable BEFORE the ack
            self._jobs[jid] = job
            self._tenant(tenant)["jobs"] += 1
            # "Resume on the next submission": the tenant's PARKED jobs
            # move to the front of their own priority lanes, then the
            # new one joins its lane's tail.  Parked only — and never
            # across lanes, so a parked batch job cannot cut ahead of
            # the interactive lane.
            parked = [j for j in self._queue
                      if self._jobs[j]["tenant"] == tenant
                      and self._jobs[j]["state"] == "parked"]
            for j in parked:
                self._queue.remove(j)
            for j in reversed(parked):
                self._queue.push_front(j, self._jobs[j]["priority"])
            self._queue.push(jid, priority)
            self._wake.notify_all()
        return {"job_id": jid, "out_dir": job["out_dir"]}

    def _rpc_status(self, args: dict) -> dict:
        jid = args.get("job_id")
        tenant = args.get("tenant")
        with self._lock:
            if jid:
                job = self._jobs.get(jid)
                if job is None:
                    return {"error": f"no such job {jid!r}"}
                return {"job": {k: job.get(k) for k in _JOB_FIELDS}}
            jobs = [{k: j.get(k) for k in _JOB_FIELDS}
                    for j in self._jobs.values()
                    if tenant is None or j["tenant"] == tenant]
            return {"jobs": jobs,
                    "tenants": {t: dict(s)
                                for t, s in self._tenants.items()},
                    "stats": self._stats_section()}

    def stats_section(self) -> Dict:
        with self._lock:
            return self._stats_section()

    def _stats_section(self) -> Dict:
        """The scheduler's statistics as a client reads them (``Status``
        with no job id; ``mrserve`` prints the same at shutdown): the
        packers' scopes under ``serve`` and ``serve_grep`` once the
        scheduler has built them, and under ``daemon`` this process's own
        spans and counts with the admission counters.  All of it counts
        up from the daemon's start: a reader takes it before and after
        what it measures and subtracts.  Caller holds the lock."""
        out = {"daemon": {**self.stats, **self._qos}}
        if self.packer is not None:
            out["serve"] = dict(self.packer.stats)
        if self.grep_packer is not None:
            out["serve_grep"] = dict(self.grep_packer.stats)
        return out

    def _rpc_ping(self, args: dict) -> dict:
        with self._lock:
            out = {"ok": True, "pid": os.getpid(),
                   "ready": self.ready.is_set(),
                   "queued": len(self._queue),
                   "resident": len(self._resident),
                   "shed": self._qos["shed"],
                   "rate_limited": self._qos["rate_limited"]}
            if self.grep_packer is not None:
                out["grep_packed_steps"] = \
                    self.grep_packer.stats["packed_steps"]
            return out

    def _rpc_shutdown(self, args: dict) -> dict:
        self.stop()
        return {"ok": True}

    # ── statusz / metrics section (obs/live.py hooks) ──

    def _statusz_section(self) -> str:
        with self._lock:
            depths = self._queue.depths()
            lines = [f"  queued={len(self._queue)} "
                     f"depths={'/'.join(map(str, depths))} "
                     f"resident={len(self._resident)} "
                     f"jobs={len(self._jobs)} "
                     f"shed={self._qos['shed']} "
                     f"rate_limited={self._qos['rate_limited']} "
                     f"evict_p99={self._qos['evict_p99']} "
                     f"evict_quota={self._qos['evict_quota']}"]
            if self.packer is not None:
                st = self.packer.stats
                lines.append(
                    f"  wc packed_steps={st['packed_steps']} "
                    f"packed_rows={st['packed_rows']} "
                    f"max_tenants_per_step={st['max_tenants_per_step']} "
                    f"replays={st['replays']}")
            if self.grep_packer is not None:
                st = self.grep_packer.stats
                lines.append(
                    f"  grep packed_steps={st['packed_steps']} "
                    f"packed_rows={st['packed_rows']} "
                    f"max_tenants_per_step={st['max_tenants_per_step']} "
                    f"host_fallbacks={st['host_fallbacks']}")
            for jid, rec in sorted(self._resident.items()):
                job = self._jobs[jid]
                if rec["kind"] in ("wc", "grep"):
                    lane = rec["lane"]
                    live = (f"steps={lane.steps} "
                            f"rows={lane.confirmed_rows} "
                            f"cursor={lane.cursor}")
                else:
                    live = f"steps={rec['advanced']}"
                lines.append(f"  tenant={job['tenant']} job={jid} "
                             f"app={job['app']} "
                             f"prio={job.get('priority')} {live}")
            # The tenant table is capped like /metrics: worst tails
            # first, then the rest in name order up to the cap.
            for t in self._emit_tenants():
                s = self._tenants[t]
                kv = " ".join(f"{k}={v}" for k, v in sorted(s.items()))
                p99 = self._hist.p99_ms(t)
                lines.append(f"  tenant={t} p99_ms={p99} {kv}")
            omitted = len(self._tenants) - \
                len(self._emit_tenants())
            if omitted > 0:
                lines.append(f"  ... {omitted} more tenants (cap "
                             f"{self.metrics_tenants})")
        return "\n".join(lines)

    def _emit_tenants(self) -> List[str]:
        """The capped tenant set for /statusz and /metrics: worst-p99
        tenants first (the ones an operator is hunting), filled with
        the rest in name order up to ``metrics_tenants``.  Caller holds
        the lock."""
        cap = self.metrics_tenants
        picked = [t for t, _p, _n in self._hist.top(cap)
                  if t in self._tenants]
        if len(picked) < cap:
            seen = set(picked)
            for t in sorted(self._tenants):
                if t not in seen:
                    picked.append(t)
                    if len(picked) >= cap:
                        break
        return picked

    def _metrics_section(self) -> str:
        from dsi_tpu.obs.live import _mname

        with self._lock:
            L = [f"dsi_serve_jobs_total {len(self._jobs)}",
                 f"dsi_serve_queued {len(self._queue)}",
                 f"dsi_serve_resident {len(self._resident)}",
                 f"dsi_serve_tenants_total {len(self._tenants)}",
                 f"dsi_serve_shed_total {self._qos['shed']}",
                 f"dsi_serve_rate_limited_total "
                 f"{self._qos['rate_limited']}",
                 f"dsi_serve_evictions_p99_total "
                 f"{self._qos['evict_p99']}",
                 f"dsi_serve_evictions_quota_total "
                 f"{self._qos['evict_quota']}"]
            for p, d in zip(qos.PRIORITIES, self._queue.depths()):
                L.append(f'dsi_serve_queue_depth{{priority="{p}"}} {d}')
            if self.packer is not None:
                st = self.packer.stats
                L.append(f"dsi_serve_packed_steps {st['packed_steps']}")
                L.append(f"dsi_serve_packed_rows {st['packed_rows']}")
            if self.grep_packer is not None:
                st = self.grep_packer.stats
                L.append(f"dsi_serve_grep_packed_steps "
                         f"{st['packed_steps']}")
                L.append(f"dsi_serve_grep_packed_rows "
                         f"{st['packed_rows']}")
            for t in self._emit_tenants():
                s = self._tenants[t]
                lab = f'tenant="{_mname(t)}"'
                for k in ("steps", "rows", "evictions", "resumes",
                          "done"):
                    L.append(f"dsi_serve_tenant_{k}{{{lab}}} {s[k]}")
                L.append(f"dsi_serve_tenant_resume_gap_seconds{{{lab}}} "
                         f"{s['resume_gap_s']}")
                L.append(f"dsi_serve_tenant_p99_ms{{{lab}}} "
                         f"{self._hist.p99_ms(t)}")
        return "\n".join(L)

    # ── scheduler (the one thread that touches jax) ──

    def _admit(self) -> bool:
        """Move queued jobs into the resident set (resuming from their
        chains), highest priority first; returns whether anything was
        admitted.  Caller holds the lock."""
        admitted = False
        while len(self._queue) and \
                len(self._resident) < self.max_resident:
            jid = self._queue.pop()
            job = self._jobs[jid]
            resumed = job["state"] == "parked"
            try:
                with _span("admit", stats=self.stats, key="admit_s",
                           job=jid) as sp:
                    rec = self._make_runner(job)
                    resumed = resumed or bool(rec.get("resume_cursor", 0))
                    sp.set(resumed=resumed)
            except Exception as e:  # noqa: BLE001 — job fails, daemon lives
                job["state"] = "failed"
                job["error"] = f"{type(e).__name__}: {e}"
                job["done_ts"] = round(time.time(), 3)
                self._persist(job)
                continue
            job["state"] = "running"
            self._persist(job)
            self._resident[jid] = rec
            ts = self._tenant(job["tenant"])
            if resumed:
                ts["resumes"] += 1
                self.stats["resumes"] += 1
                _count("resumes")
                ts["resume_gap_s"] = round(
                    ts["resume_gap_s"] + rec.get("resume_gap_s", 0.0), 4)
            admitted = True
        return admitted

    def _make_runner(self, job: Dict) -> Dict:
        ckpt_dir = os.path.join(self.tenants_dir, job["tenant"],
                                job["job_id"])
        if job["app"] == "wc":
            from dsi_tpu.serve.pack import TenantLane

            lane = TenantLane(job, self.chunk_bytes, ckpt_dir,
                              checkpoint_every=self.checkpoint_every,
                              resume=True, stats=self.stats)
            return {"kind": "wc", "lane": lane,
                    "resume_gap_s": lane.resume_gap_s,
                    "resume_cursor": lane.start_offset}
        if self.pack_grep:
            # grep as a packed lane: rows join shared dispatches keyed
            # by pattern length — the ISSUE-19 tentpole.
            from dsi_tpu.serve.pack import GrepLane

            lane = GrepLane(job, self.chunk_bytes, ckpt_dir,
                            checkpoint_every=self.checkpoint_every,
                            resume=True, stats=self.stats)
            return {"kind": "grep", "lane": lane,
                    "resume_gap_s": lane.resume_gap_s,
                    "resume_cursor": lane.start_offset}
        # grep as a resumable step object, time-multiplexed (the
        # packed-vs-tmux bench row's control arm).
        from dsi_tpu.parallel.grepstream import GrepStep
        from dsi_tpu.parallel.streaming import stream_files

        stats: Dict = {}
        step = GrepStep(stream_files(job["files"]), job["pattern"],
                        mesh=self._mesh, checkpoint_dir=ckpt_dir,
                        checkpoint_every=self.checkpoint_every,
                        checkpoint_delta=True, resume=True,
                        pipeline_stats=stats)
        info = step.restore()
        return {"kind": "step", "step": step, "stats": stats,
                "advanced": 0,
                "resume_gap_s": info.get("resume_gap_s", 0.0),
                "resume_cursor": info.get("resume_cursor", 0)}

    def _finish_job(self, jid: str, rec: Dict) -> None:
        """Finalize one retired runner.  Called WITHOUT the daemon lock
        held: the heavy half (host-path recomputation, durable output
        writes) must not freeze the control plane mid-multi-GB job —
        only the final job/tenant bookkeeping takes the lock."""
        job = self._jobs[jid]
        with _span("finish", stats=self.stats, key="finish_s", job=jid):
            self._finalize(job, rec)

    def _finalize(self, job: Dict, rec: Dict) -> None:
        hostpath = False
        stats: Dict = {}
        error = None
        try:
            if rec["kind"] == "wc":
                lane = rec["lane"]
                lane.finalize()
                hostpath = lane.hostpath
                stats = {"steps": lane.steps,
                         "rows": lane.confirmed_rows,
                         "hostpath": lane.hostpath,
                         "resume_gap_s": lane.resume_gap_s}
            elif rec["kind"] == "grep":
                lane = rec["lane"]
                result = lane.finalize()
                hostpath = lane.hostpath
                self._write_grep_result(job, result)
                stats = {"steps": lane.steps,
                         "rows": lane.confirmed_rows,
                         "hostpath": lane.hostpath,
                         "resume_gap_s": lane.resume_gap_s}
            else:
                step = rec["step"]
                result = step.close()
                if result is None:
                    # Host path: the oracle semantics, same output file.
                    from dsi_tpu.parallel.grepstream import \
                        grep_host_oracle
                    from dsi_tpu.parallel.streaming import stream_files

                    result = grep_host_oracle(stream_files(job["files"]),
                                              job["pattern"])
                    hostpath = True
                self._write_grep_result(job, result)
                stats = {"steps": rec["advanced"]}
        except Exception as e:  # noqa: BLE001 — job fails, daemon lives
            error = f"{type(e).__name__}: {e}"
        with self._lock:
            # What the job waited and what it was served for: from its
            # submission to its first row, and from there to here.
            wait = (job.get("stats") or {}).get("queue_wait_s")
            done_ts = time.time()
            if wait is not None:
                stats["queue_wait_s"] = wait
                stats["service_s"] = round(
                    max(0.0, done_ts - job["submitted_ts"] - wait), 4)
            job["stats"] = stats
            job["state"] = "done" if error is None else "failed"
            job["error"] = error
            job["done_ts"] = round(done_ts, 3)
            ts = self._tenant(job["tenant"])
            if hostpath:
                ts["hostpath"] += 1
            if error is None:
                self.stats["jobs_done"] += 1
                ts["done"] += 1
                ts["steps"] += int(stats.get("steps") or 0)
                ts["rows"] += int(stats.get("rows") or 0)
            # Drain-rate evidence: the gap between consecutive job
            # completions (any outcome — a failed job still drained a
            # queue slot) feeds the queue-full retry-after hint.
            now = self._clock()
            if self._last_done_ts is not None:
                self._drain_hist.record("gap",
                                        max(1e-6, now - self._last_done_ts))
            self._last_done_ts = now
        self._persist(job)

    @staticmethod
    def _write_grep_result(job: Dict, result) -> None:
        """One spelling of the grep output file — the packed lane, the
        step object, and the host path must serialize identically (the
        per-tenant byte-parity bar)."""
        os.makedirs(job["out_dir"], exist_ok=True)
        payload = json.dumps(
            {"lines": result.lines, "matched": result.matched,
             "occurrences": result.occurrences,
             "hist": list(result.hist),
             "topk": [list(r) for r in result.topk]},
            sort_keys=True).encode("utf-8")
        write_bytes_durable(
            os.path.join(job["out_dir"], "grep.json"), payload)

    def _rec_steps(self, rec: Dict) -> int:
        return (rec["lane"].steps_since_resume
                if rec["kind"] in ("wc", "grep") else rec["advanced"])

    def _evict_one(self) -> None:
        """Park one resident job so a queued tenant gets a turn —
        checkpoint to its delta chain, drop the runner, re-queue in its
        own priority lane.  Victim choice is TAIL-DRIVEN: among
        residents past a minimum residency, the tenant whose p99
        packed-step wall is worst (its rows stall every pack it rides).
        The step-quota rule is the fallback when no resident has a
        meaningful tail yet.  Caller holds the lock."""
        victim = None
        worst = 0.0
        min_steps = min(self.quota_steps, self.evict_min_samples)
        for jid, rec in self._resident.items():
            if self._rec_steps(rec) < min_steps:
                continue  # too fresh: let it earn a tail first
            h = self._hist.get(self._jobs[jid]["tenant"])
            if h is None or h.count < self.evict_min_samples:
                continue
            p99 = h.percentile(0.99)
            if p99 > worst:
                victim, worst = jid, p99
        reason = "evict_p99"
        if victim is None:
            # Fallback: the original furthest-past-quota rule.
            most = -1
            for jid, rec in self._resident.items():
                steps = self._rec_steps(rec)
                if steps >= self.quota_steps and steps > most:
                    victim, most = jid, steps
            reason = "evict_quota"
        if victim is None:
            return
        with _span("evict", stats=self.stats, key="evict_s", job=victim,
                   how=reason[len("evict_"):]):
            self._park(victim, reason)

    def _park(self, victim: str, reason: str) -> None:
        """Snapshot the victim to its chain, drop its runner, and put the
        job at the back of its priority lane.  Caller holds the lock."""
        rec = self._resident.pop(victim)
        job = self._jobs[victim]
        try:
            if rec["kind"] in ("wc", "grep"):
                rec["lane"].suspend()
            else:
                rec["step"].suspend()
        except Exception as e:  # noqa: BLE001
            job["state"] = "failed"
            job["error"] = f"evict: {type(e).__name__}: {e}"
            job["done_ts"] = round(time.time(), 3)
            self._persist(job)
            return
        job["state"] = "parked"
        self._persist(job)
        self._queue.push(victim, job.get("priority",
                                         qos.DEFAULT_PRIORITY))
        self._tenant(job["tenant"])["evictions"] += 1
        self._qos[reason] += 1
        self.stats["evictions"] += 1
        _count("evictions")

    def _note_first_rows(self, pairs) -> None:
        """A job's ``queue_wait_s``: from its submission to the first row
        a packer took from it.  Kept in the job's ``stats``, which a park
        persists, so a resumed job keeps the wait of its first turn."""
        for jid, lane in pairs:
            job = self._jobs[jid]
            stats = job.setdefault("stats", {})
            if lane.first_take_ts is not None and \
                    "queue_wait_s" not in stats:
                stats["queue_wait_s"] = round(max(
                    0.0, lane.first_take_ts - job["submitted_ts"]), 4)

    def _fail_lanes(self, pairs, e: Exception, what: str) -> None:
        """Fail the jobs riding a packer that threw — the packer error
        takes out its participants, never the daemon."""
        with self._wake:
            for jid, _ln in pairs:
                rec = self._resident.pop(jid, None)
                if rec is None:
                    continue
                job = self._jobs[jid]
                job["state"] = "failed"
                job["error"] = f"{what}: {type(e).__name__}: {e}"
                job["done_ts"] = round(time.time(), 3)
                self._persist(job)

    def _scheduler(self) -> None:
        from dsi_tpu.parallel.shuffle import default_mesh
        from dsi_tpu.serve.pack import (PackedGrepScheduler,
                                        PackedWcScheduler)

        self._mesh = default_mesh(self.devices)
        self.packer = PackedWcScheduler(self._mesh, self.chunk_bytes,
                                        self.n_reduce)
        if self.pack_grep:
            self.grep_packer = PackedGrepScheduler(self._mesh,
                                                   self.chunk_bytes)
        if self.warm:
            self.packer.warm()
        self.ready.set()
        while not self._stop.is_set():
            with self._wake:
                self._admit()
                if len(self._queue):
                    self._evict_one()
                    self._admit()
                resident = dict(self._resident)
            worked = False
            # One packed step across every runnable wc lane.  A packer
            # error fails the participating jobs, never the daemon.
            # The step wall feeds every participant tenant's histogram
            # — the eviction policy's evidence.
            wc_lanes = [(jid, rec["lane"])
                        for jid, rec in resident.items()
                        if rec["kind"] == "wc" and rec["lane"].runnable]
            if wc_lanes:
                t0 = time.perf_counter()
                try:
                    confirmed = self.packer.step(
                        [ln for _, ln in wc_lanes])
                    wall = time.perf_counter() - t0
                    self._note_first_rows(wc_lanes)
                    for ln in confirmed:
                        self._hist.record(ln.tenant, wall)
                    worked = bool(confirmed) or any(
                        not ln.runnable for _, ln in wc_lanes)
                except Exception as e:  # noqa: BLE001
                    self._fail_lanes(wc_lanes, e, "packed step")
                    worked = True
            # One packed grep step over ONE pattern-length group —
            # groups rotate across scheduler iterations.  The packer
            # keeps one step in flight: a call confirms the rows the
            # call before dispatched, and the wall a tenant's histogram
            # is told is that of the turn its row was confirmed in.
            grep_lanes = [(jid, rec["lane"])
                          for jid, rec in resident.items()
                          if rec["kind"] == "grep"
                          and rec["lane"].runnable]
            if grep_lanes:
                t0 = time.perf_counter()
                try:
                    confirmed = self.grep_packer.step(
                        [ln for _, ln in grep_lanes])
                    wall = time.perf_counter() - t0
                    self._note_first_rows(grep_lanes)
                    for ln in confirmed:
                        self._hist.record(ln.tenant, wall)
                    # A call that dispatched and confirmed nothing (the
                    # first of a burst) has left work for the next one.
                    worked = worked or bool(confirmed) \
                        or self.grep_packer.in_flight or any(
                            not ln.runnable for _, ln in grep_lanes)
                except Exception as e:  # noqa: BLE001
                    # A step that failed says whose rows it lost: their
                    # jobs fail, and no others.
                    lost = [p for p in grep_lanes if p[1].lost is not None]
                    self._fail_lanes(lost or grep_lanes, e,
                                     "packed grep step")
                    worked = True
            # A bounded slice of every step-object job — the same
            # ``advance_slice`` primitive the shard workers drive their
            # cursor-range shards with (parallel/stepobj.py).
            for jid, rec in resident.items():
                if rec["kind"] != "step":
                    continue
                step = rec["step"]
                t0 = time.perf_counter()
                try:
                    took = step.advance_slice(8)
                    rec["advanced"] += took
                    if took:
                        self._hist.record(self._jobs[jid]["tenant"],
                                          time.perf_counter() - t0)
                    worked = worked or took > 0
                except Exception as e:  # noqa: BLE001
                    with self._wake:
                        if self._resident.pop(jid, None) is not None:
                            job = self._jobs[jid]
                            job["state"] = "failed"
                            job["error"] = f"{type(e).__name__}: {e}"
                            job["done_ts"] = round(time.time(), 3)
                            self._persist(job)
                    worked = True
            # Retire finished runners: pop under the lock, finalize
            # outside it (the heavy half must not block the RPC plane).
            retired = []
            with self._wake:
                for jid, rec in list(self._resident.items()):
                    finished = (not rec["lane"].runnable
                                if rec["kind"] in ("wc", "grep")
                                else rec["step"].phase != "running")
                    if finished:
                        del self._resident[jid]
                        retired.append((jid, rec))
            for jid, rec in retired:
                self._finish_job(jid, rec)
                worked = True
            with self._wake:
                if not worked and not len(self._queue):
                    self._wake.wait(timeout=0.2)
        # Graceful stop: park every resident job so a restart resumes
        # from fresh chains instead of replaying from the last cadence.
        with self._wake:
            for jid, rec in list(self._resident.items()):
                job = self._jobs[jid]
                try:
                    if rec["kind"] in ("wc", "grep"):
                        rec["lane"].suspend()
                    else:
                        rec["step"].suspend()
                    job["state"] = "parked"
                except Exception as e:  # noqa: BLE001
                    job["state"] = "failed"
                    job["error"] = f"stop: {type(e).__name__}: {e}"
                    job["done_ts"] = round(time.time(), 3)
                self._persist(job)
            self._resident.clear()

    # ── lifecycle ──

    def start(self) -> "ServeDaemon":
        from dsi_tpu.obs import live as _live

        _live.register_section("serve tenants", self._statusz_section,
                               self._metrics_section)
        self._rpc.start()
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._wake:
            self._wake.notify_all()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout=timeout)

    def close(self) -> None:
        from dsi_tpu.obs import live as _live

        self.stop()
        self.join(timeout=60.0)
        self._rpc.close()
        _live.unregister_section("serve tenants")
