"""Unified tracer: nested spans, counters, Perfetto/JSONL export.

The paper ships counters and a live status page as first-class framework
features (Dean & Ghemawat §4.7–4.8); until this module the repo's
equivalent was four ad-hoc stats dicts with no per-step timeline and no
control-plane visibility.  :class:`Tracer` is the one timeline every
layer writes into:

* **spans** — ``with tracer.span("upload", step=n): ...`` times a named
  region.  Spans nest: every record carries an ``id``, the ``parent``
  that enclosed it on its thread (an explicit ``parent=`` where work is
  handed to another thread) and its ``depth``; a span that names a
  ``kind`` and a ``task`` (``worker.map``, ``worker.reduce``) hands both
  to everything opened under it, so the records of one task share an
  identifier.  They are thread-safe (the buffer append is the only
  shared write, under one lock), and are ~free when tracing is
  disabled: a pure span returns a shared no-op singleton (zero
  allocation), and a span carrying a ``stats``/``key`` sink degenerates
  to exactly the two ``perf_counter`` calls the engines' hand-rolled
  phase timing already paid — the sink write IS the phase accounting,
  so the span totals and the ``stream_phases``-style registry values
  cannot disagree.
* **one clock with the device trace** — while tracing is enabled and
  ``jax`` is already imported (this module never imports it: the
  launchers must stay off JAX), a span also enters
  ``jax.profiler.TraceAnnotation("dsi:<name>")``, so a profiler trace
  taken of the process holds the program's spans beside the device's
  ops; a ``dsi.clock`` annotation carrying ``time.time_ns()`` is emitted
  when tracing is enabled, at most once a second under a root span, and
  at flush, and the JSONL head carries ``wall0_ns``: the offset between
  this file's clock and the profiler's is recoverable from either.
* **the starvation account** — whether the chip had work queued, cut at
  the span boundaries of a job's main thread (below, "The starvation
  account"): it runs with tracing off, in the spans that have a sink.
* **events** — ``tracer.event("requeue", ...)`` instant records (the
  control-plane lane).
* **counters** — ``tracer.count("steps")`` monotonic counters, emitted
  as Chrome ``"C"`` samples.

Everything buffers in memory (bounded by ``DSI_TRACE_BUFFER_EVENTS``,
drops counted — a silent cap would read as "covered everything") and
:meth:`Tracer.flush` writes two artifacts through
``utils/atomicio.write_bytes_durable`` (temp + fsync + rename + CRC32
sidecar — the checkpoint store's torn-write discipline, so a trace
survives the same crashes the checkpoints do):

* ``<basename>.jsonl`` — one JSON record per event, head record carries
  process metadata, counters, and the metrics-registry snapshot;
* ``<basename>.json``  — Chrome/Perfetto ``traceEvents``: one lane
  (tid) per pipeline stage (materialize/upload/dispatch/kernel/pull/
  merge/replay/fold/sync/widen/ckpt) plus the control-plane lane; load
  it at https://ui.perfetto.dev or chrome://tracing.

## The starvation account

A job is waiting for its chip, or the chip for the job.  The device's
profiler tells which, from one traced job and slowed by tracing it; the
program can tell in every job, because every device program it enqueues
passes through a handful of sites, the device runs them in order, and
``is_ready()`` on the newest one's result says without blocking whether
anything is left.

* **what is in flight** — a site that enqueues a device program whose
  result the host does not at once block on calls :func:`enqueued` with
  an array that program produces.  One slot, the newest: its result
  ready means every earlier program's is (on a mesh, ready on every
  chip).  ``StepPipeline`` keeps each step's own beside its record and
  asks it at ``finish`` (:meth:`Tracer.landed`, the ``results_ready``
  counter).
* **the cut** — a root ``job`` span opens the account on its thread and
  closes it.  At the enter and the exit of every span that records on
  that thread (with tracing off: every span with a ``stats`` sink) the
  piece since the previous boundary is charged to the innermost span
  that was open through it, as ``dry`` (nothing was in flight when it
  began and nothing was enqueued in it), ``fed`` (something was in flight
  at both ends) or ``ran dry`` (the chip ran out inside it, or began
  it with nothing).  A span of ``registry.DEVICE_BLOCKED`` is fed by
  definition and is not asked about.  Other threads' spans are not cut:
  the account is that of the thread that feeds the chip; their
  ``enqueued`` calls count, whoever feeds it.
* **what it costs** — nothing is asked while nothing is in flight, and
  a result once seen ready is not asked again: a stretch paced by the
  host costs one ``is_ready()`` a dispatch.  Asking has a budget,
  ``_LOOK_SHARE`` of the wall: every look is timed, and after one that
  took ``d`` seconds the next is not before ``d / _LOOK_SHARE`` later
  (a look costs 0.25 us where the backend has the answer at hand and
  tens of us where it has to make it: PERF.md §6), whoever asked, the
  account or ``StepPipeline``.  At a boundary at which something is
  in flight and no look is due, the chip is taken for busy without
  being seen; a piece taken for fed with neither end seen is counted
  in ``starved_unseen_s``: the seconds the account vouches for on
  trust alone, next to nothing where a look is cheap and most of a
  step loop where it is dear.
  What it can miss besides is whatever is shorter than the backend
  takes to show a result ready (0.6 ms after the enqueue on a TPU v5e:
  PERF.md §6).  A boundary costs about half a microsecond of Python.
* **what a job gets** — at the root's exit its sink receives
  ``starved_s`` (ran dry + dry), ``starved_dry_s``, ``starved_by``
  (span name → seconds), ``starved_groups`` (``registry.
  STARVED_GROUPS``) and ``starved_unseen_s``; under the root the pieces
  sum to ``job_s``, and the chip's idle time as the host can see it
  lies between ``starved_dry_s`` and ``starved_s`` +
  ``starved_unseen_s``.  With tracing on, a span record carries
  ``dry``, its own starved seconds.

The process-global tracer (:func:`get_tracer`) is enabled by
``DSI_TRACE_DIR=<dir>`` (buffer + durable flush at exit — how
``mrrun --trace-dir`` reaches its child coordinator/workers; their
``main`` builds it at entry, so its epoch precedes the first task) or by
:func:`configure` (the CLIs' ``--trace-dir``; ``enabled=True`` alone is
the bench's in-memory rollup mode).  ``ckpt/fault.py`` flushes it right
before ``os._exit``, so traces survive injected crashes.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import dsi_tpu.obs.hist as _hist
from dsi_tpu.obs.registry import DEVICE_BLOCKED, STARVED_GROUPS

#: Span names recorded into the live stage histograms when the
#: telemetry plane is active (obs/hist.py owns the pinned set).
_HOT_STAGES = frozenset(_hist.HIST_STAGES)

#: The lane taxonomy: every span/event lands in one of these Perfetto
#: lanes (a span's lane defaults to its name).  Pipeline stages first in
#: display order, then the device-service lanes, then the control plane.
LANES = (
    "materialize", "upload", "dispatch", "kernel", "pull", "merge",
    "replay", "shuffle", "fold", "sync", "widen", "ckpt", "plan",
    "net", "replica", "host", "launch", "control", "counters",
)

#: The pinned span-name schema: every span opened anywhere in the repo
#: draws its name from this set (lanes double as span names for the
#: simple stages; the rest are the documented sub-stages).  The
#: ``span-discipline`` rule in ``dsi_tpu/analysis`` enforces it
#: statically, and ``scripts/tracecat.py``'s flame/straggler tables key
#: on these names — an off-schema span would silently fall out of every
#: rollup, so adding one is a schema change and belongs here first.
SPAN_NAMES = frozenset(LANES) | frozenset((
    "wait", "finish", "drain", "append", "hist_fold", "hist_pull",
    "ckpt_capture", "ckpt_commit", "ckpt_save", "ckpt_restore", "task",
    "decode", "stage_commit", "resplit", "stage_overlap",
    # the plan layer's device relay (device/relay.py)
    "relay_append", "relay_spill",
    # the batch plane's tasks and what a task and a launch consist of
    "worker.map", "worker.reduce", "read", "write", "rpc", "d2h",
    "finalize", "probe", "backend_init",
    # the serving daemon (serve/daemon.py, serve/pack.py): a submission,
    # an admission to the resident set, a row cut for a packed step, a
    # park to the checkpoint chain ("ckpt" and "finish" are above)
    "submit", "admit", "take_row", "evict",
    # a stream command's main thread (cli/wcstream.py, cli/grepstream.py):
    # the root span of a job and its start (everything before the first
    # step); inside a step's "dispatch", the call of the step program
    # and its async copy starts; inside "merge", "finalize" or "sync", a
    # compaction of the host accumulator (parallel/merge.py); inside
    # "write", a partition's CPU work and its durable commit
    "job", "start", "enqueue", "compact", "format", "commit",
    # the accumulator's caller held by the compaction of a full window
    # that is still in flight on its merger thread (parallel/merge.py)
    "merge_wait",
    # the indexer's postings table grouped into the index, once a job
    # (parallel/merge.py PostingsTable.finalize_packed)
    "group",
    # a packed index walk's packer, a wave at a time on the producer
    # thread, inside "materialize" (parallel/grepstream.py pack_chunk)
    "pack",
    # the same thread held by a document of the wave that the reader
    # threads have not read yet, before "pack" and outside it
    # (utils/ioread.py ReadAheadDocs)
    "read_wait",
    # the sort chain (parallel/sortstream.py): the sampling pre-pass over
    # the record files, and the host blocked on the device's ordering of
    # the resident store
    "sample", "order",
    # the join chain (parallel/joinstream.py): its two phases, each around
    # its own steps: the build side into the ordered table on the device,
    # the probe side past it into the merged table of groups
    "join_build", "join_probe",
    # planrun's root (cli/planrun.py): what the job says to stderr and
    # writes beside mr-out-* (the stage lines, named(), plan-*.json)
    "report",
    # an explicit compile's two halves (backends/aotcache.py)
    "lower", "compile",
))

#: The share of the wall that asking whether a result is ready may cost
#: (module docstring, "what it costs"): ISSUE 51's budget is 1 % of the
#: shortest step for the whole account, and the boundaries take the
#: other half of it.
_LOOK_SHARE = 0.005
_BLOCKED = frozenset(DEVICE_BLOCKED)
_GROUP_OF = {name: group for group, names in STARVED_GROUPS
             for name in names}

_BUFFER_ENV = "DSI_TRACE_BUFFER_EVENTS"
_BUFFER_DEFAULT = 500_000


class _NoopSpan:
    """The disabled-mode fast path: one shared instance, no allocation,
    no clock reads."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **fields) -> None:
        """Fields known only once the work is done: nowhere to go."""


_NOOP_SPAN = _NoopSpan()


class _Account:
    """The starvation account of one job's main thread (module
    docstring).  Its clock is the spans': a boundary is the ``_t0`` or
    the end a span has just read."""

    __slots__ = ("tr", "thread", "stack", "top", "t_last", "drained",
                 "seen", "seq", "by", "dry_s", "unseen_s")

    def __init__(self, tr: "Tracer"):
        self.tr = tr
        self.thread = None   # armed by the root's enter
        self.stack: list = []
        self.top = None      # the innermost open span's entry
        self.by: Dict[str, float] = {}
        self.dry_s = self.unseen_s = 0.0

    def _cut(self, now: float, blocked: bool) -> None:
        """Charge the piece that ends now to the innermost open span.
        In a blocked one the host was held by the device: fed, whatever
        follows, and nothing is asked.  Where something is in flight
        and no look is due, the chip is taken for busy, unseen."""
        tr = self.tr
        seq, arr = tr._noted
        seen = True
        if seq <= tr._ready_seq:
            drained = True
        elif blocked:
            drained = False
        elif now >= tr._look_at:
            drained = tr._look(seq, arr)
        else:
            drained = seen = False
        if not blocked:
            if drained or self.drained:
                piece = now - self.t_last
                self.top[1] += piece
                if drained and self.drained and seq == self.seq:
                    self.dry_s += piece
            elif not (seen or self.seen):
                self.unseen_s += now - self.t_last
        self.drained, self.seen = drained, seen
        self.t_last, self.seq = now, seq

    def enter(self, name: str, lane: str, now: float) -> Optional[list]:
        tr = self.tr
        if self.thread is None:  # the root: nothing before it to charge
            self.thread = threading.get_ident()
            self.t_last, self.seq = now, tr._noted[0]
            self.drained = self.seen = self.seq <= tr._ready_seq
            tr._acct = self
        elif tr._acct is not self:
            return None  # a span that outlived its job's root
        else:
            self._cut(now, False)
        self.top = entry = [name, 0.0, (name, lane) in _BLOCKED]
        self.stack.append(entry)
        return entry

    def exit(self, entry: Optional[list], now: float,
             stats: Optional[dict]) -> float:
        """Close ``entry``; returns its own starved seconds.  The root's
        exit closes the account and hands ``stats`` the job's."""
        if self.tr._acct is not self:
            return 0.0  # as in ``enter``
        self._cut(now, entry[2])
        stack = self.stack
        stack.pop()
        own = entry[1]
        if own:
            self.by[entry[0]] = self.by.get(entry[0], 0.0) + own
        if stack:
            self.top = stack[-1]
        else:
            self._close(stats)
        return own

    def _close(self, stats: Optional[dict]) -> None:
        """The root has closed: hand ``stats`` the job's account."""
        tr = self.tr
        tr._acct = None
        # the job's last array: let go of it, and let the next job begin
        # with nothing in flight that it could ask about
        seq = tr._noted[0]
        tr._noted = (seq, None)
        tr._ready_seq = seq
        if stats is None:
            return
        groups = dict.fromkeys((g for g, _ in STARVED_GROUPS), 0.0)
        for name, secs in self.by.items():
            groups[_GROUP_OF.get(name, "tail")] += secs
        groups = {g: round(v, 4) for g, v in groups.items()}
        stats["starved_groups"] = groups
        stats["starved_s"] = round(sum(groups.values()), 4)
        stats["starved_dry_s"] = round(self.dry_s, 4)
        stats["starved_unseen_s"] = round(self.unseen_s, 4)
        stats["starved_by"] = {n: round(v, 4) for n, v in
                               sorted(self.by.items()) if v >= 5e-5}


class _Span:
    """One live span.  ``tr`` is None when only the stats sink is wanted
    (tracing disabled but the engine still needs its phase seconds);
    ``acct`` is the starvation account it is a boundary of, if any."""

    __slots__ = ("_tr", "name", "lane", "_stats", "_key", "_fields",
                 "_t0", "_depth", "elapsed_s", "id", "_up", "_prev",
                 "_task", "_ann", "_acct", "_entry")

    def __init__(self, tr: Optional["Tracer"], name: str, lane: str,
                 stats: Optional[dict], key: Optional[str],
                 fields: Optional[dict], parent=None,
                 acct: Optional[_Account] = None):
        self._tr = tr
        self.name = name
        self.lane = lane
        self._stats = stats
        self._key = key
        self._fields = fields
        self.elapsed_s = 0.0
        self.id = None
        self._acct = acct
        # An explicit parent counts only if it is itself being recorded.
        self._up = parent if getattr(parent, "id", None) is not None \
            else None

    def set(self, **fields) -> None:
        """Add fields known only once the work is done (bytes read,
        records decoded); recorded with the span at its close."""
        if self._tr is not None:
            if self._fields is None:
                self._fields = fields
            else:
                self._fields.update(fields)

    def __enter__(self) -> "_Span":
        tr = self._tr
        if tr is not None:
            tls = tr._tls
            prev = self._prev = getattr(tls, "cur", None)
            up = self._up = self._up or prev
            self.id = next(tr._ids)
            self._depth = up._depth + 1 if up is not None else 0
            f = self._fields
            if f is not None and "kind" in f and "task" in f:
                self._task = (f["kind"], f["task"])
            else:
                self._task = up._task if up is not None else None
            tls.cur = self
            ann = tr._annotation()
            if ann is None:
                self._ann = None
            else:
                if prev is None and \
                        time.perf_counter() - tr._clock_at > 1.0:
                    tr._clock_mark()
                self._ann = ann("dsi:" + self.name)
                self._ann.__enter__()
        self._t0 = time.perf_counter()
        if self._acct is not None:
            self._entry = self._acct.enter(self.name, self.lane, self._t0)
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        dur = end - self._t0
        self.elapsed_s = dur
        if self._stats is not None:
            self._stats[self._key] = self._stats.get(self._key, 0.0) + dur
        if self._acct is not None:
            dry = self._acct.exit(self._entry, end, self._stats)
            if dry and self._tr is not None:
                self.set(dry=round(dry, 6))
        # Stage histogram recording at span close (the tentpole of the
        # live telemetry plane): one module-attribute load when the
        # plane is off, one dict lookup + O(1) bucket bump when on.
        hs = _hist._active
        if hs is not None:
            hs.record(self.name, dur)
        tr = self._tr
        if tr is not None:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            tr._tls.cur = self._prev
            up = self._up
            tr._record("X", self.name, self.lane, self._t0, dur,
                       self._depth, self._fields, self.id,
                       up.id if up is not None else None, self._task)
        return False


class Tracer:
    """Buffered span/event/counter recorder with durable Perfetto export
    (module docstring for the full contract)."""

    def __init__(self, enabled: bool = False,
                 trace_dir: Optional[str] = None, basename: str = "trace",
                 buffer_cap: Optional[int] = None):
        self._lock = threading.Lock()
        self._tls = threading.local()
        #: (ph, name, lane, t_perf, dur_s, depth, fields, id, parent,
        #: task) tuples; ``task`` is the enclosing task's (kind, task).
        self._events: List[Tuple] = []
        self._ids = itertools.count(1)
        self.dropped = 0
        self.counters: Dict[str, float] = {}
        self._t0 = time.perf_counter()
        self._wall0_ns = time.time_ns()
        self._wall0 = self._wall0_ns / 1e9
        #: ``jax.profiler.TraceAnnotation`` once JAX is imported, and
        #: when the last ``dsi.clock`` mark went out (perf_counter).
        self._ann_cls = None
        self._clock_at = float("-inf")
        #: The starvation account (module docstring): the newest program
        #: enqueued, as (ordinal, an array it produces); the ordinal up
        #: to which results were seen ready; when the next look is due;
        #: the open account, if a job's root span is.
        self._seqs = itertools.count(1)
        self._noted: Tuple = (0, None)
        self._ready_seq = 0
        self._look_at = 0.0
        self._acct: Optional[_Account] = None
        # Construction never DEactivates the histogram plane (another
        # tracer may be feeding it); only an explicit ``enabled=False``
        # assignment does — see the property setter.
        self._enabled = bool(enabled)
        if self._enabled:
            _hist.activate()
        self.trace_dir: Optional[str] = None
        self.basename = basename
        if buffer_cap is None:
            try:
                buffer_cap = int(os.environ.get(_BUFFER_ENV,
                                                str(_BUFFER_DEFAULT)))
            except ValueError:
                buffer_cap = _BUFFER_DEFAULT
        self.buffer_cap = max(1, buffer_cap)
        if trace_dir:
            self.set_trace_dir(trace_dir, basename)

    # ── configuration ──

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, v) -> None:
        """Enabling tracing also activates the stage-histogram plane
        (hot spans record their close latency); disabling deactivates
        it UNLESS the live sampler holds it — statusz must keep its
        percentiles when a bench toggles its in-memory tracer off."""
        self._enabled = bool(v)
        if self._enabled:
            _hist.activate()
            self._clock_mark()
        else:
            _hist.deactivate()

    # ── the device trace's clock ──

    def _annotation(self):
        """``jax.profiler.TraceAnnotation``, or None while the process
        has not imported JAX (it is looked up, never imported: a
        launcher that imported it would take the chip from its
        children)."""
        ann = self._ann_cls
        if ann is None:
            ann = getattr(sys.modules.get("jax.profiler"),
                          "TraceAnnotation", None)
            self._ann_cls = ann
        return ann

    def _clock_mark(self) -> None:
        """One ``dsi.clock`` annotation: the epoch clock (``wall_ns``)
        and this tracer's own (``ts_ns`` since its epoch) at one instant
        of the profiler's."""
        ann = self._annotation()
        if ann is None:
            return
        now = time.perf_counter()
        self._clock_at = now
        with ann("dsi.clock", wall_ns=time.time_ns(),
                 ts_ns=int((now - self._t0) * 1e9)):
            pass

    def set_trace_dir(self, trace_dir: str,
                      basename: Optional[str] = None) -> None:
        """Enable tracing with durable flush into ``trace_dir``.  Reaps
        orphans from a previous writer killed mid-commit — the
        checkpoint store's startup discipline — but only THIS process's
        basename: mrrun's children share one trace dir, and a blanket
        reap could delete a sibling's in-flight temp mid-commit."""
        from dsi_tpu.utils.atomicio import reap_tmp_files

        os.makedirs(trace_dir, exist_ok=True)
        if basename:
            self.basename = basename
        reap_tmp_files(trace_dir, prefix=f".tmp-{self.basename}.")
        self.trace_dir = trace_dir
        self.enabled = True

    # ── recording ──

    def span(self, name: str, /, *, lane: Optional[str] = None,
             stats: Optional[dict] = None, key: Optional[str] = None,
             parent=None, **fields):
        """A context manager timing one region.  With ``stats``/``key``
        the elapsed seconds are ALSO added to ``stats[key]`` (the
        engines' phase dicts — one measurement, two consumers).
        ``parent`` is the span (as ``with ... as sp`` gave it) this one
        belongs under when it runs on another thread than its parent;
        on one thread the enclosing span is found without it.
        Disabled and sink-less returns the shared no-op singleton —
        unless the live histogram plane is active and the span is a hot
        stage, which still needs its close latency recorded (statusz-
        without-tracing mode)."""
        if not self.enabled:
            if stats is not None:
                return _Span(None, name, lane or name, stats,
                             key or (name + "_s"), None, None,
                             self._account(name))
            if _hist._active is not None and name in _HOT_STAGES:
                return _Span(None, name, "", None, None, None)
            return _NOOP_SPAN
        return _Span(self, name, lane or name, stats,
                     (key or (name + "_s")) if stats is not None else None,
                     fields or None, parent, self._account(name))

    # ── the starvation account (module docstring) ──

    def _account(self, name: str) -> Optional[_Account]:
        """The account a span opened now, on this thread, is a boundary
        of: the open one on its own thread; a new one for a root
        ``job`` (armed when the span is entered); else none."""
        acct = self._acct
        if acct is None:
            return _Account(self) if name == "job" else None
        return acct if acct.thread == threading.get_ident() else None

    def enqueued(self, arr) -> None:
        """A device program was enqueued whose result the host does not
        at once block on; ``arr`` is an array it produces (any one: they
        are ready together), the smallest to hand."""
        self._noted = (next(self._seqs), arr)

    @property
    def enqueued_n(self) -> int:
        """Ordinal of the newest program :meth:`enqueued` was told."""
        return self._noted[0]

    def newest(self, since: int) -> Optional[Tuple]:
        """``(ordinal, array)`` of the newest program enqueued, for
        :meth:`landed`; None if none was since ordinal ``since``."""
        seq, arr = self._noted
        return (seq, arr) if seq > since and arr is not None else None

    def _look(self, seq: int, arr) -> bool:
        """Ask ``arr``, the result of program ``seq``, whether it is
        ready, and charge the asking to the budget (``_LOOK_SHARE``).
        An array that was deleted (donated to a later program) cannot
        say: the newest one told answers for it, or nobody."""
        t0 = time.perf_counter()
        try:
            ready = bool(arr.is_ready())
        except RuntimeError:  # "Array has been deleted"
            newest, newest_arr = self._noted
            ready = bool(newest > seq and newest_arr is not None
                         and self._look(newest, newest_arr))
            seq = newest
        t1 = time.perf_counter()
        self._look_at = t1 + (t1 - t0) / _LOOK_SHARE
        if ready and seq > self._ready_seq:
            self._ready_seq = seq
        return ready

    def landed(self, seq: int, arr) -> bool:
        """Has the device run the program told as ``(seq, arr)``?  Never
        blocks; a result known ready is not asked again."""
        return seq <= self._ready_seq or self._look(seq, arr)

    def event(self, name: str, /, *, lane: str = "control",
              **fields) -> None:
        """Record one instant event (control-plane lane by default),
        under the span open on this thread, if any."""
        if not self.enabled:
            return
        cur = getattr(self._tls, "cur", None)
        depth, parent, task = (0, None, None) if cur is None else \
            (cur._depth + 1, cur.id, cur._task)
        self._record("I", name, lane, time.perf_counter(), 0.0, depth,
                     fields or None, None, parent, task)

    def count(self, name: str, /, n: float = 1, *,
              lane: str = "counters") -> None:
        """Bump a monotonic counter; emits a Chrome counter sample."""
        if not self.enabled:
            return
        with self._lock:
            v = self.counters.get(name, 0) + n
            self.counters[name] = v
        self._record("C", name, lane, time.perf_counter(), 0.0, 0,
                     {"value": v})

    def _record(self, ph: str, name: str, lane: str, t_perf: float,
                dur_s: float, depth: int, fields: Optional[dict],
                id_: Optional[int] = None, parent: Optional[int] = None,
                task: Optional[tuple] = None) -> None:
        with self._lock:
            if len(self._events) >= self.buffer_cap:
                self.dropped += 1
                return
            self._events.append((ph, name, lane, t_perf - self._t0,
                                 dur_s, depth, fields, id_, parent, task))

    # ── reading back ──

    def mark(self) -> int:
        """Current buffer position — pass to :meth:`rollup` to scope a
        rollup to the events recorded since."""
        with self._lock:
            return len(self._events)

    def counters_snapshot(self) -> Dict[str, float]:
        """A consistent copy of the counters — readers on other
        threads (the statusz endpoints, the live sampler) must not
        iterate the live dict while :meth:`count` inserts into it."""
        with self._lock:
            return dict(self.counters)

    def rollup(self, since: int = 0) -> Dict[str, dict]:
        """Per-span-name totals over the buffered events:
        ``{name: {"total_s", "count", "max_s", "p50_ms", "p99_ms"}}`` —
        the per-phase span rollup the bench rows publish.  The
        percentiles are EXACT over the buffered durations (the buffer
        holds every one), scoped by ``since`` like the totals — so a
        bench row's rollup carries its own latency distribution, not
        the whole process's."""
        with self._lock:
            evs = self._events[since:]
        out: Dict[str, dict] = {}
        durs: Dict[str, list] = {}
        for ph, name, lane, ts, dur, depth, *_ in evs:
            if ph != "X":
                continue
            r = out.setdefault(name, {"total_s": 0.0, "count": 0,
                                      "max_s": 0.0})
            r["total_s"] += dur
            r["count"] += 1
            if dur > r["max_s"]:
                r["max_s"] = dur
            durs.setdefault(name, []).append(dur)
        for name, r in out.items():
            d = sorted(durs[name])
            n = len(d)
            # Nearest-rank percentiles, index ceil(q*n)-1 — the same
            # rank rule as LatencyHistogram.percentile, so the rollup
            # and the live histograms cannot disagree on definition
            # (p99 of 100 samples is the 99th, NOT the max).
            r["p50_ms"] = round(1e3 * d[(n + 1) // 2 - 1], 4)
            r["p99_ms"] = round(1e3 * d[(99 * n + 99) // 100 - 1], 4)
            r["total_s"] = round(r["total_s"], 4)
            r["max_s"] = round(r["max_s"], 4)
        return out

    # ── export ──

    def _meta(self, counters: Dict, dropped: int) -> dict:
        meta = {"pid": os.getpid(), "wall0": round(self._wall0, 3),
                "wall0_ns": self._wall0_ns, "basename": self.basename,
                "dropped_events": dropped,
                "counters": counters}
        try:
            from dsi_tpu.obs.registry import get_registry

            meta["registry"] = get_registry().snapshot()
        except Exception:
            pass
        return meta

    def flush(self) -> Optional[Tuple[str, str]]:
        """Write ``<basename>.jsonl`` + ``<basename>.json`` durably into
        the trace dir; returns their paths, or None when no dir is
        configured (in-memory tracing: :meth:`rollup` is the consumer).
        Idempotent — each call rewrites the full buffer, so a fault-point
        flush followed by nothing still leaves complete artifacts."""
        if not self.enabled or self.trace_dir is None:
            return None
        from dsi_tpu.utils.atomicio import write_bytes_durable

        self._clock_mark()
        with self._lock:
            evs = list(self._events)
            counters = dict(self.counters)
            dropped = self.dropped
        meta = self._meta(counters, dropped)

        lines = [json.dumps({"type": "meta", **meta}, sort_keys=True)]
        for ph, name, lane, ts, dur, depth, fields, id_, parent, task \
                in evs:
            rec = {"ph": ph, "name": name, "lane": lane,
                   "ts": round(ts, 6), "dur": round(dur, 6),
                   "depth": depth}
            if ph != "C":
                rec["parent"] = parent
                if ph == "X":
                    rec["id"] = id_
            if fields:
                rec.update(fields)
            if task is not None:
                rec.setdefault("kind", task[0])
                rec.setdefault("task", task[1])
            lines.append(json.dumps(rec, sort_keys=True, default=str))
        jsonl_path = os.path.join(self.trace_dir, self.basename + ".jsonl")
        write_bytes_durable(jsonl_path,
                            ("\n".join(lines) + "\n").encode("utf-8"))

        pid = os.getpid()
        lanes = [l for l in LANES if any(e[2] == l for e in evs)]
        lanes += sorted({e[2] for e in evs} - set(lanes))
        tid_of = {l: i for i, l in enumerate(lanes)}
        tev: List[dict] = [{"name": "process_name", "ph": "M", "pid": pid,
                            "tid": 0,
                            "args": {"name": f"dsi {self.basename}"}}]
        for lane, tid in tid_of.items():
            tev.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": lane}})
            tev.append({"name": "thread_sort_index", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"sort_index": tid}})
        for ph, name, lane, ts, dur, depth, fields, id_, parent, task \
                in evs:
            ev = {"name": name, "cat": lane, "pid": pid,
                  "tid": tid_of[lane], "ts": round(ts * 1e6, 3)}
            args = dict(fields) if fields else {}
            if ph == "X":
                ev.update(ph="X", dur=round(dur * 1e6, 3))
                args.update(id=id_, parent=parent)
            elif ph == "C":
                ev.update(ph="C")
            else:
                ev.update(ph="i", s="t")
            if args:
                ev["args"] = args
            tev.append(ev)
        doc = {"traceEvents": tev, "displayTimeUnit": "ms",
               "otherData": meta}
        json_path = os.path.join(self.trace_dir, self.basename + ".json")
        write_bytes_durable(json_path,
                            json.dumps(doc, default=str).encode("utf-8"))
        return jsonl_path, json_path


# ── the process-global tracer ──────────────────────────────────────────

_global_lock = threading.Lock()
_global: Optional[Tracer] = None
_atexit_registered = False


def _register_atexit() -> None:
    """Flush at interpreter exit when a trace dir is configured — how an
    env-inherited child (mrrun's coordinator/workers) commits its
    ``trace-<pid>.json`` without any CLI plumbing of its own."""
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit

    def _flush():
        try:
            if _global is not None:
                _global.flush()
        except Exception:
            pass

    atexit.register(_flush)


def get_tracer() -> Tracer:
    """The process-global tracer, lazily built from the env:
    ``DSI_TRACE_DIR`` enables buffering with a per-process durable
    flush target (``trace-<pid>.*``).  A process that may run tasks
    calls this at the top of its ``main``, so that the tracer's epoch
    precedes the first of them."""
    global _global
    if _global is None:
        with _global_lock:
            if _global is None:
                env_dir = os.environ.get("DSI_TRACE_DIR")
                t = Tracer(enabled=bool(env_dir))
                if env_dir:
                    t.set_trace_dir(env_dir,
                                    basename=f"trace-{os.getpid()}")
                _global = t
                if env_dir:
                    _register_atexit()
    return _global


def configure(trace_dir: Optional[str] = None, basename: str = "trace",
              enabled: Optional[bool] = None) -> Tracer:
    """Configure the global tracer (the CLIs' ``--trace-dir`` entry):
    with ``trace_dir`` the process writes ``trace.json``/``trace.jsonl``
    there at flush; ``enabled=True`` alone turns on in-memory buffering
    (the bench's rollup mode)."""
    t = get_tracer()
    if trace_dir:
        t.set_trace_dir(trace_dir, basename)
        _register_atexit()
    if enabled is not None:
        t.enabled = bool(enabled)
    return t


def span(name: str, /, **kw):
    return get_tracer().span(name, **kw)


def event(name: str, /, **kw) -> None:
    get_tracer().event(name, **kw)


def count(name: str, /, n: float = 1, **kw) -> None:
    get_tracer().count(name, n, **kw)


def enqueued(arr) -> None:
    get_tracer().enqueued(arr)


def flush() -> Optional[Tuple[str, str]]:
    return get_tracer().flush()
