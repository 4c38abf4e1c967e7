"""Reusable dispatch/finish pipeline core for device-step engines.

The PR-1 streaming word-count engine earned its throughput from four
mechanics that have nothing to do with word counting: a background
producer thread feeding a bounded queue (host item construction off the
critical path), a ``depth``-deep in-flight window (dispatch step k+1
before step k synchronizes), deferred per-step checks (a step's flags
are read only when it leaves the window, ``depth-1`` steps late), and a
small rotating host buffer pool (O(depth) allocations however long the
stream).  The TF-IDF wave walk has exactly the same cost shape — build
wave, upload, kernel, scalar check, pull, merge, every wave on the
critical path — so this module extracts the mechanics into one core
both engines consume (``parallel/streaming.py``,
``parallel/tfidf.py``).

The core is deliberately ignorant of devices and results: ``dispatch``
launches whatever async work one item needs and returns an opaque
record; ``finish`` retires the OLDEST in-flight record — that is where
a consumer blocks on flags, replays an overflowed step through its
exactness ladder, and merges confirmed output.  The window invariant
the core owns: records finish in dispatch order, a record finishes
exactly once, and at most ``depth`` records are ever in flight.
``depth=1`` degenerates to the fully synchronous loop — no thread, no
queue, dispatch-then-finish — which is why a consumer's pipelined and
lockstep paths are the same function and can be compared bit-for-bit.

Exceptions propagate both ways: a producer error re-raises in the
consumer thread (stop-aware, so it cannot be lost while the consumer
sits in a long replay), and a consumer exception unwinds through
``run`` with the producer thread shut down and its queue drained.
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
from typing import Callable, Iterator, Optional, Sequence

import numpy as np

from dsi_tpu.obs import get_tracer as _get_tracer
from dsi_tpu.obs import hist as _hist
from dsi_tpu.obs import span as _span


def pipeline_depth(depth: Optional[int] = None) -> int:
    """Resolve an engine's in-flight window: an explicit ``depth`` wins,
    else ``DSI_STREAM_PIPELINE_DEPTH`` (default 2), floored at 1 (the
    synchronous path).  One resolver for every pipeline consumer, so the
    stream and the wave walk cannot read the knob differently."""
    if depth is None:
        try:
            depth = int(os.environ.get("DSI_STREAM_PIPELINE_DEPTH", "2"))
        except ValueError:
            depth = 2
    return max(1, depth)


def fold_source_stats(stats: dict, source) -> None:
    """Fold a block source's ingest counters into an engine's metrics
    scope at release time.  The parallel reader pool
    (``utils/ioread.py ParallelBlocks``) exposes ``ingest_stats()``
    (``ingest_readers``/``ingest_blocks``/``readahead_hit_pct``/
    ``ingest_wait_s`` — all pinned in ``obs/registry.py SCHEMA_KEYS``);
    plain iterables have nothing to report and this is a no-op.  One
    helper for all four engines so the fold — and its
    never-trade-a-result-for-telemetry error policy — cannot drift."""
    fn = getattr(source, "ingest_stats", None)
    if not callable(fn):
        return
    try:
        stats.update(fn())
    except Exception:
        pass


class BufferPool:
    """Small rotating pool of reusable fixed-shape host buffers.

    ``take`` hands out a free buffer, allocating only when the pool is
    dry (startup, or the consumer still holds every buffer in its
    in-flight window); ``give`` returns one for reuse.  Never blocks —
    the pipeline's bounded queue provides the backpressure; the pool
    only removes the per-item ``np.zeros`` allocation + page-fault churn
    from the steady state.  ``allocs`` counts real allocations, so a
    caller can assert reuse (a stream of any length allocates O(depth)
    buffers).
    """

    def __init__(self, shape: Sequence[int], retain: int,
                 dtype=np.uint8):
        self._shape = tuple(shape)
        self._dtype = dtype
        self._free: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._retain = retain
        self.allocs = 0

    def take(self) -> np.ndarray:
        with self._lock:
            if self._free:
                return self._free.popleft()
            self.allocs += 1
        return np.zeros(self._shape, dtype=self._dtype)

    def give(self, buf: Optional[np.ndarray]) -> None:
        # Only host buffers re-enter the pool: a device-resident batch
        # (the plan layer's stage handoff feeds jax.Arrays through the
        # same dispatch/finish path) must never be handed to a writer.
        if not isinstance(buf, np.ndarray) or buf.shape != self._shape:
            return
        with self._lock:
            if len(self._free) < self._retain:
                self._free.append(buf)


class CommitWorker:
    """Single background worker draining submitted thunks FIFO — the
    consumer-side twin of the producer thread above, shared by the
    async checkpoint writer (``ckpt/writer.py``).

    The discipline mirrors the pipeline's: bounded in-flight work
    (``submit`` blocks while ``max_pending`` submissions are
    outstanding — the "barrier only when the NEXT save would overrun
    the one still draining" rule; the wait is returned so the caller
    can attribute it), strict submission order (one worker), and
    errors that cannot be lost — a thunk's exception is re-raised at
    the next ``submit``/``drain`` in the submitting thread, never
    swallowed while the pipeline keeps stepping.
    """

    def __init__(self, name: str = "dsi-commit-worker",
                 max_pending: int = 1):
        self._q: "queue.Queue" = queue.Queue()
        # The in-flight bound must count the thunk the worker is
        # RUNNING, not just queued ones (a bounded queue alone would
        # admit one running + one queued = max_pending + 1): a slot is
        # taken at submit and released only when the thunk finishes.
        self._slots = threading.BoundedSemaphore(max(1, max_pending))
        self._name = name
        self._thread: Optional[threading.Thread] = None
        self._err: Optional[BaseException] = None
        self._done = threading.Event()

    def _loop(self) -> None:
        while True:
            thunk = self._q.get()
            try:
                if thunk is None:
                    return
                if self._err is None:  # after an error: drain, don't run
                    thunk()
            except BaseException as e:
                self._err = e
            finally:
                self._q.task_done()
                if thunk is not None:
                    self._slots.release()

    def _ensure_thread(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True, name=self._name)
            self._thread.start()

    def _raise_pending(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def submit(self, thunk: Callable[[], None]) -> float:
        """Enqueue one thunk; returns the seconds spent blocked waiting
        for an in-flight slot (0.0 when one was free).  Re-raises a
        prior thunk's error instead of enqueueing more work on a dead
        run."""
        self._raise_pending()
        self._ensure_thread()
        t0 = time.perf_counter()
        self._slots.acquire()
        self._q.put(thunk)
        waited = time.perf_counter() - t0
        return waited if waited > 1e-4 else 0.0

    def drain(self) -> float:
        """Wait until every submitted thunk finished; re-raise the first
        error.  Returns the seconds spent waiting."""
        if self._thread is None:
            self._raise_pending()
            return 0.0
        t0 = time.perf_counter()
        self._q.join()
        self._raise_pending()
        return time.perf_counter() - t0

    def shutdown(self) -> None:
        """Stop the worker after the queue drains, silently (for
        ``finally`` blocks already unwinding another exception — a
        pending commit error stays stored and surfaces if ``drain`` is
        called first on the success path)."""
        if self._thread is None:
            return
        self._q.put(None)
        self._thread.join(timeout=60.0)
        self._thread = None


class _StallWatchdog(threading.Thread):
    """Flags the head-of-line step when its RETIRE age — seconds since
    it became the oldest in-flight record (i.e. since the previous
    finish completed), not since its own dispatch — exceeds
    ``max(k · p99(finish), floor)``.  The percentile-aware straggler
    signal (Dean & Ghemawat §3.6 make backup dispatch a tail-latency
    decision; a flat timeout can't tell "slow step" from "stuck
    step").  Head-of-line age is the right clock: dispatch→finish age
    includes ~``depth-1`` steps of NORMAL window residency, so at
    depth > k it exceeds ``k·p99`` on perfectly healthy pipelines —
    the retire age is depth-independent (steady state ≈ one step
    wall).  One daemon thread per running pipeline, started ONLY when
    the telemetry plane is active (``obs/hist.py``) — the default run
    has zero watchdog threads.

    The p99 comes from the live ``finish`` stage histogram once it has
    ``DSI_STALL_MIN_SAMPLES`` (default 8) steps; before that only the
    floor gates, so early-run compile stalls don't self-trigger.
    Knobs: ``DSI_STALL_K`` (default 4), ``DSI_STALL_FLOOR_S`` (default
    5 s), ``DSI_STALL_CHECK_S`` (default floor/4 capped at 1 s).

    A stalled step is flagged EXACTLY ONCE: a loud stderr line, a
    ``stall`` event in the trace's control lane (step, retire + since-
    dispatch ages, threshold, p99), the ``pipeline_stall`` registry
    gauge, and a ``stalls`` bump in the engine's stats scope.  The
    step may still finish — the flag means "a backup dispatcher should
    be looking", not "dead".
    """

    def __init__(self, pipe: "StepPipeline",
                 hists: "_hist.StageHistograms"):
        super().__init__(name="dsi-stall-watchdog", daemon=True)
        self._pipe = pipe
        self._hists = hists
        self._halt = threading.Event()
        self._flagged: set = set()
        envf = _hist.env_float
        self.k = envf("DSI_STALL_K", 4.0)
        self.floor_s = envf("DSI_STALL_FLOOR_S", 5.0)
        self.check_s = envf("DSI_STALL_CHECK_S",
                            max(0.02, min(1.0, self.floor_s / 4)))
        self.min_samples = int(envf("DSI_STALL_MIN_SAMPLES", 8))

    def threshold_s(self) -> float:
        # THIS pipeline's finish distribution, not the process-global
        # stage histogram: in one bench process the stream row's ~ms
        # finishes would otherwise calibrate the tfidf row's ~s waves
        # (every healthy wave flagged) and vice versa.
        h = self._pipe._finish_hist
        p99 = (h.percentile(0.99)
               if h is not None and h.count >= self.min_samples else 0.0)
        return max(self.k * p99, self.floor_s)

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:
        import sys

        from dsi_tpu.obs import event as _event, get_registry

        while not self._halt.wait(self.check_s):
            oldest = self._pipe.oldest_inflight()
            if oldest is None:
                continue
            step, ts = oldest
            if step in self._flagged:
                continue
            now = time.perf_counter()
            # Retire age: since this record reached the head of the
            # line (the later of its dispatch and the previous finish
            # completing) — depth-independent, unlike now - ts.
            age = now - max(ts, self._pipe._last_retire_t)
            thr = self.threshold_s()
            if age <= thr:
                continue
            self._flagged.add(step)
            h = self._hists.get("finish")
            p99_s = round(h.percentile(0.99), 4) if h is not None else 0.0
            engine = self._pipe._engine or "?"
            info = {"engine": engine, "step": step,
                    "age_s": round(age, 3),
                    "inflight_age_s": round(now - ts, 3),
                    "threshold_s": round(thr, 3),
                    "p99_s": p99_s}
            self._pipe._stats["stalls"] = \
                self._pipe._stats.get("stalls", 0) + 1
            _event("stall", lane="control", **info)
            get_registry().set_gauge("pipeline_stall", info)
            print(f"obs: STALL {engine} step {step}: in flight "
                  f"{age:.1f}s > max({self.k:g}*p99={self.k * p99_s:.1f}s,"
                  f" floor={self.floor_s:g}s)", file=sys.stderr)


class StepPipeline:
    """``depth``-deep dispatch/finish window over a produced item stream.

    ``dispatch(item)`` launches one step's async work and returns an
    opaque in-flight record (or None to skip the item); ``finish(record)``
    retires the oldest record — deferred flag check, replay, merge all
    live in the consumer.  ``stats`` receives ``produce_key`` (seconds
    building items — in the producer thread at depth > 1, inline at
    depth 1), ``wait_key`` (consumer starvation on the queue),
    ``inflight_key`` (peak window occupancy, bounded by ``depth``),
    ``dispatch_s`` and ``retire_s`` (the ``dispatch`` and ``finish``
    spans below: with the wait, the whole of the thread that pumps) and
    ``results_ready``: the steps whose programs the device had run
    before the host came to retire them.  A ``dispatch`` tells the
    tracer what it enqueued (``obs.enqueued``); the newest array it told
    stays beside the record, and ``finish`` asks it, without blocking,
    before the consumer's own reads.  One look a step, whatever it
    costs (tens of microseconds on a TPU: PERF.md §6): an engine whose
    step is shorter than a hundred looks passes ``count_ready=False``
    and prints no ``results_ready``.

    Tracing (``dsi_tpu/obs``) is instrumented HERE once for all four
    engines: every produced item, dispatch, and finish is a span —
    ``materialize``/``dispatch``/``finish`` carrying the step ordinal
    and the ``engine`` label — so a traced run gets its per-step
    timeline from the core, and the engines only add their
    phase-specific child spans (upload/kernel/pull/merge/replay) inside
    ``finish``.  The spans double as the stats accumulators (the
    ``stats``/``key`` sink), so the trace totals and the phase dict are
    the same measurement.
    """

    def __init__(self, *, depth: int,
                 dispatch: Callable, finish: Callable,
                 stats: dict,
                 produce_key: str = "batch_s",
                 wait_key: str = "batch_wait_s",
                 inflight_key: str = "max_inflight_chunks",
                 thread_name: str = "dsi-pipeline-producer",
                 engine: str = "", count_ready: bool = True):
        self.depth = max(1, int(depth))
        self._count_ready = count_ready
        self._dispatch = dispatch
        self._finish = finish
        self._stats = stats
        self._produce_key = produce_key
        self._wait_key = wait_key
        self._inflight_key = inflight_key
        self._thread_name = thread_name
        self._engine = engine or getattr(stats, "engine", "")
        stats.setdefault(produce_key, 0.0)
        stats.setdefault(wait_key, 0.0)
        stats.setdefault(inflight_key, 0)
        if count_ready:
            stats.setdefault("results_ready", 0)
        # Live telemetry state (obs/live.py statusz + the stall
        # watchdog): (ordinal, dispatch-perf_counter) per in-flight
        # record, plus monotonic dispatched/finished counters.  Plain
        # attribute writes on the hot path — a deque append and two int
        # bumps per step, read from other threads without locks (deque
        # ops are atomic; readers tolerate a racy oldest).
        self._inflight: collections.deque = collections.deque()
        self.dispatched = 0
        self.finished = 0
        #: perf_counter of the most recent finish completing (run start
        #: before any) — the watchdog's head-of-line age baseline.
        self._last_retire_t = 0.0
        #: THIS run's finish-wall histogram (fresh per run, telemetry-
        #: active runs only) — the watchdog's p99 source; the process-
        #: global stage histograms aggregate across engines/runs and
        #: would cross-calibrate their thresholds.
        self._finish_hist: Optional["_hist.LatencyHistogram"] = None

    # ── live telemetry read side ──

    def oldest_inflight(self) -> Optional[tuple]:
        """(step ordinal, dispatch perf_counter) of the oldest record
        still in flight, or None — the watchdog's probe."""
        try:
            return self._inflight[0]
        except IndexError:
            return None

    def live_state(self) -> dict:
        """One JSON-ready line of in-flight window state — what
        ``/statusz`` reports per running pipeline."""
        oldest = self.oldest_inflight()
        now = time.perf_counter()
        return {"engine": self._engine,
                "dispatched": self.dispatched,
                "finished": self.finished,
                "inflight": len(self._inflight),
                "depth": self.depth,
                "step": max(0, self.dispatched - 1),
                "oldest_step": oldest[0] if oldest else None,
                "oldest_age_s": (round(now - oldest[1], 3)
                                 if oldest else 0.0)}

    # ── item feed: inline at depth=1, background thread otherwise ──

    def _producer(self, make_items: Callable[[], Iterator],
                  out_q: queue.Queue, stop: threading.Event) -> None:
        gen = make_items()
        i = 0
        try:
            while True:
                with _span("materialize", stats=self._stats,
                           key=self._produce_key, step=i,
                           engine=self._engine):
                    try:
                        item = next(gen)
                    except StopIteration:
                        break
                i += 1
                while not stop.is_set():
                    try:
                        out_q.put(("item", item), timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            out_q.put(("done", None))
        except BaseException as e:  # surfaced to the consumer thread
            # Stop-aware retry, like the item put above: a fixed timeout
            # could drop the error while the consumer sits in a long
            # replay (a cold compile can take minutes), leaving it blocked
            # forever on a queue that will never produce the sentinel.
            while not stop.is_set():
                try:
                    out_q.put(("err", e), timeout=0.2)
                    break
                except queue.Full:
                    continue

    def _feed(self, make_items, out_q, stop,
              started: list) -> Iterator:
        if self.depth == 1:
            gen = make_items()
            i = 0
            while True:
                with _span("materialize", stats=self._stats,
                           key=self._produce_key, step=i,
                           engine=self._engine):
                    try:
                        item = next(gen)
                    except StopIteration:
                        return
                i += 1
                yield item
            return
        thread = threading.Thread(
            target=self._producer, args=(make_items, out_q, stop),
            daemon=True, name=self._thread_name)
        started.append(thread)
        thread.start()
        while True:
            with _span("wait", lane="materialize", stats=self._stats,
                       key=self._wait_key, engine=self._engine):
                kind, item = out_q.get()
            if kind == "done":
                return
            if kind == "err":
                raise item
            yield item

    # ── the window: incremental API ──
    #
    # ``run`` used to own the whole loop; the resumable step objects
    # (``parallel/stepobj.py``) and the serving daemon need to drive it
    # one step at a time, so the loop is split into four primitives —
    # ``begin`` (arm the feed/watchdog), ``pump`` (dispatch the next
    # item, retiring the oldest record when the window is full),
    # ``drain`` (retire everything in flight — the confirmed-boundary
    # maker for forced checkpoints and eviction), and ``end`` (tear the
    # producer/watchdog down, idempotent).  ``run`` is exactly
    # begin → pump* → drain with ``end`` in a finally, so its semantics
    # — dispatch/finish interleaving included — are unchanged.

    def begin(self, make_items: Callable[[], Iterator]) -> None:
        """Arm the pipeline over ``make_items()``'s items.  Must be
        balanced by :meth:`end` (any number of ``pump``/``drain`` calls
        in between)."""
        #: each in-flight record beside what its dispatch enqueued
        #: last, as ``Tracer.newest`` gives it, or None
        self._pending: collections.deque = collections.deque()
        self._inflight.clear()
        self._last_retire_t = time.perf_counter()
        self._stop_evt = threading.Event()
        self._out_q: queue.Queue = queue.Queue(maxsize=self.depth + 1)
        self._started: list = []
        self._idx = 0
        self._ended = False
        # The stall watchdog rides only telemetry-active runs: the
        # default path starts zero extra threads.
        self._watchdog: Optional[_StallWatchdog] = None
        hists = _hist.active_histograms()
        if hists is not None:
            self._finish_hist = _hist.LatencyHistogram()
            self._watchdog = _StallWatchdog(self, hists)
            self._watchdog.start()
        _hist.register_pipeline(self)
        self._feed_iter: Optional[Iterator] = self._feed(
            make_items, self._out_q, self._stop_evt, self._started)

    def _finish_oldest(self) -> None:
        # The per-step trace span: its wall IS the step's retire cost
        # (deferred flag wait + merge or replay) — the unit the
        # straggler table in scripts/tracecat.py ranks and the
        # ``finish`` histogram the watchdog thresholds on.
        step, _ts = self._inflight[0]
        with _span("finish", lane="dispatch", stats=self._stats,
                   key="retire_s", step=step, engine=self._engine) as sp:
            rec, told = self._pending.popleft()
            if told is not None and _get_tracer().landed(*told):
                self._stats["results_ready"] += 1
            self._finish(rec)
        self._inflight.popleft()
        self.finished += 1
        self._last_retire_t = time.perf_counter()
        if self._finish_hist is not None:
            self._finish_hist.record(sp.elapsed_s)

    def pump(self) -> bool:
        """One turn of the crank: dispatch the next produced item,
        retiring the oldest in-flight record first when the window is
        full.  Returns False when the item stream is exhausted (records
        may still be in flight — ``drain`` retires them)."""
        try:
            item = next(self._feed_iter)
        except StopIteration:
            return False
        tracer = _get_tracer()
        before = tracer.enqueued_n
        with _span("dispatch", stats=self._stats, key="dispatch_s",
                   step=self._idx, engine=self._engine):
            rec = self._dispatch(item)
        self._idx += 1
        self.dispatched = self._idx
        if rec is None:
            return True
        self._pending.append(
            (rec, tracer.newest(before) if self._count_ready else None))
        self._inflight.append((self._idx - 1, time.perf_counter()))
        if len(self._pending) > self._stats[self._inflight_key]:
            self._stats[self._inflight_key] = len(self._pending)
        if len(self._pending) >= self.depth:
            self._finish_oldest()
        return True

    def drain(self) -> None:
        """Retire every in-flight record (FIFO).  After this the
        pipeline sits at a CONFIRMED boundary — everything dispatched
        has passed its deferred checks and merged — which is what a
        forced checkpoint or a tenant eviction needs."""
        while self._pending:
            self._finish_oldest()

    @property
    def inflight(self) -> int:
        return len(self._pending)

    def end(self) -> None:
        """Tear down the producer thread and watchdog.  Idempotent, and
        safe mid-stream (an eviction abandons unread items; the resume
        re-reads them from the durable cursor)."""
        if getattr(self, "_ended", True):
            return
        self._ended = True
        _hist.unregister_pipeline(self)
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog.join(timeout=5.0)  # fast: stop() wakes its wait
            self._watchdog = None
        if self._started:
            self._stop_evt.set()
            thread = self._started[0]
            # Unblock a producer stuck on a full queue; bounded — a
            # producer mid-build exits at its next stop check.
            deadline = time.monotonic() + 5.0
            while (thread.is_alive()
                   and time.monotonic() < deadline):
                try:
                    self._out_q.get_nowait()
                except queue.Empty:
                    thread.join(0.05)
        self._feed_iter = None

    def run(self, make_items: Callable[[], Iterator]) -> None:
        """Drive the full pipeline over ``make_items()``'s items: keep up
        to ``depth`` dispatched records in flight, finish each in FIFO
        order as the window fills, drain the window at stream end.  Any
        exception (producer or consumer) unwinds with the producer thread
        stopped and its queue drained."""
        self.begin(make_items)
        try:
            while self.pump():
                pass
            self.drain()
        finally:
            self.end()
