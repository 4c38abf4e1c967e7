"""Multi-tenant step packing: K tenants' chunks in ONE device dispatch.

The serving daemon's whole economic argument is amortization: a small
word-count job costs one or two device steps, so running each tenant's
job through its own engine pays a full dispatch (and a result pull)
per tenant per step.  This module
batches them: up to ``n_dev`` pending chunks from DIFFERENT tenants
fill the rows of one ``[n_dev, chunk_bytes]`` batch and run through one
compiled program, so K tenants cost ~1 dispatch instead of K.

The demux problem — and why the packed step is the TF-IDF wave
program.  The word-count step (``shuffle.mapreduce_step``) shuffles
rows across devices INSIDE the kernel (map → all_to_all → reduce), so
a device's output table mixes words from every input row: two tenants
sharing a batch would sum their counts for a shared word, and nothing
in the output says whose count is whose.  The wave program
(``tfidf._wave_fn``) already solved this for documents: every shuffled
row carries a ``doc`` payload lane.  Packing therefore treats each
tenant's chunk as a *document* — the doc lane IS the tenant lane — and
the host demuxes the pulled rows by that column into per-tenant
accumulators.  The ``tf`` payload is the word's in-chunk count and the
``part`` payload its reduce partition, so a demuxed row drops straight
into the tenant's :class:`~dsi_tpu.parallel.merge.PackedCounts` in the
packed-table layout the delta-checkpoint format already speaks
(``ckpt/delta.py``).  Counts are content-sums, independent of chunking,
so per-tenant output is byte-identical to the tenant running alone —
the parity bar the daemon's tests and bench row enforce.

Exactness discipline: the shared sticky rung (capacity / word window /
token frac) widens for the whole batch exactly as the wave
walk's ladder does — a replay re-runs the batch, every lane benefits,
and the cleared rung sticks.  Per-lane failures do NOT abort the batch:
a lane whose chunk carries non-ASCII bytes (or a >64-byte word) is
marked for the host path, its row zeroed, and the batch re-dispatched —
the surviving lanes' rows are demuxed normally and the dead tenant's
whole job re-runs on the host oracle path (correctness never depends on
the kernel, the ``backends/tpu.py`` contract).

Per-tenant state is host-side and checkpointable at every confirmed
packed step: the accumulator snapshot plus the input-byte cursor, saved
through the engines' own :class:`~dsi_tpu.ckpt.CheckpointWriter` as a
delta CHAIN (``HostDeltaLog`` of demuxed step payloads, periodic full
re-base) — which is what makes tenant eviction cheap and a daemon
``kill -9`` resumable with byte-identical output.

Grep packing (ISSUE 19) is the EASY demux case: the grep step program
(``parallel/grepstream._grep_step_device``) runs per device row under
``shard_map`` with no collectives, and each row carries its OWN pattern
operand — so K tenants' rows never mix and each output row (histogram
extension, top-k candidates, scalars) already belongs to exactly one
lane.  :class:`PackedGrepScheduler` therefore groups runnable
:class:`GrepLane` s by pattern length — rows sharing the one compiled
program of that shape — and fills one ``[n_dev, chunk_bytes]`` dispatch
round-robin across the group's tenants.  The step program keeps no
per-line buffer, so a tenant's line lengths change nothing: every row of
a dispatch is confirmed with the step that carried it, in take order
(which is byte-range order).  Per-lane line-number bases are assigned
host-side at row-take time, so per-tenant output stays byte-identical
to the tenant running alone — the same parity bar the wc lanes carry.
One packed step is in flight: a call of :meth:`PackedGrepScheduler.step`
dispatches the next step, whose results start for the host behind the
program, and then confirms the step the call before dispatched, whose
copies have landed under the host's work in between (the stream
engine's ``step_call`` rule).  A lane that is suspended or finalized
with a row in flight has the scheduler confirm that step first, so a
snapshot's cursor and a result always stand at a confirmed row.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from dsi_tpu.ckpt import (
    CheckpointPolicy,
    CheckpointStore,
    CheckpointWriter,
    DeltaSteps,
    HostDeltaLog,
    drain_packed_steps,
    fault_point,
    skip_stream,
)
from dsi_tpu.obs import (count as _count, enqueued as _enqueued,
                         get_tracer as _get_tracer, metrics_scope,
                         span as _span)
from dsi_tpu.ops.wordcount import rung0_cap
from dsi_tpu.parallel.merge import PackedCounts
from dsi_tpu.parallel.shuffle import write_partitioned_output


def host_wordcount(files, n_reduce: int) -> Dict[str, tuple]:
    """The host-path word count (the ``wcstream`` fallback semantics):
    ``apps.wc.Map`` tokens + ``ihash %% n_reduce`` partitions — the same
    result the device path produces, by the oracle's definition."""
    from dsi_tpu.apps import wc
    from dsi_tpu.mr.worker import ihash

    counts: Dict[str, int] = {}
    for f in files:
        with open(f, "rb") as fh:
            text = fh.read().decode("utf-8", errors="replace")
        for kv in wc.Map(f, text):
            counts[kv.key] = counts.get(kv.key, 0) + 1
    return {w: (c, ihash(w) % n_reduce) for w, c in counts.items()}


class TenantLane:
    """One tenant job's lane in the packed scheduler: a row stream cut
    from its input files, a host accumulator, and a per-tenant
    delta-checkpoint chain.

    ``resume=True`` (the default the daemon uses) loads the newest
    valid chain when one exists — a fresh job's empty directory simply
    starts fresh, so admission and crash-resume are the same code.
    """

    def __init__(self, job: Dict, chunk_bytes: int, ckpt_dir: str,
                 checkpoint_every: Optional[int] = None,
                 resume: bool = True, delta: bool = True,
                 stats: Optional[Dict] = None):
        from dsi_tpu.parallel.streaming import batch_stream, stream_files

        self.job = job
        self.tenant = job["tenant"]
        self.n_reduce = int(job["n_reduce"])
        self.chunk_bytes = int(chunk_bytes)
        self.acc = PackedCounts()
        self.offsets: List[int] = []
        self.rows_taken = 0
        self.confirmed_rows = 0
        self.steps = 0                # confirmed packed steps ridden
        self.steps_since_resume = 0   # the eviction-quota clock
        self.hostpath = False
        self.input_done = False
        self.resume_gap_s = 0.0
        # Where this lane's checkpoint seconds and counts go: the
        # daemon's scope, which outlives the lane, or a dict of its own.
        self.stats: Dict = {} if stats is None else stats
        self.first_take_ts: Optional[float] = None
        self._pending: List[int] = []  # end offsets of unconfirmed rows
        ident = {"tenant": self.tenant,
                 "files": [[os.path.basename(f), os.path.getsize(f)]
                           for f in job["files"]],
                 "n_reduce": self.n_reduce,
                 "chunk_bytes": self.chunk_bytes}
        self.store = CheckpointStore(ckpt_dir, "serve-wc", ident)
        self.writer = CheckpointWriter(self.store, self.stats,
                                       async_=False, delta=delta)
        self.policy = CheckpointPolicy(checkpoint_every)
        self.delta_log = HostDeltaLog()
        start = 0
        if resume:
            t0 = time.perf_counter()
            loaded = self.store.load_latest_chain()
            if loaded is not None:
                meta, arrays, deltas = loaded
                eff = deltas[-1][0] if deltas else meta
                start = int(eff["cursor"])
                self.confirmed_rows = int(eff["rows"])
                self.acc.restore({k[4:]: v for k, v in arrays.items()
                                  if k.startswith("acc_")})
                for _, darr in deltas:
                    # Ordered deltas re-ingest through the host drain
                    # path — content-exact, the chain-restore argument.
                    drain_packed_steps(self.acc, darr)
                self.resume_gap_s = round(time.perf_counter() - t0, 4)
        else:
            self.store.reset()
        self.start_offset = start
        self.cursor = start
        blocks = stream_files(job["files"])
        feed = skip_stream(blocks, start) if start else blocks
        # One row per "batch": the lane's chunk stream is its document
        # stream — the packer assigns each row a doc id (= its batch
        # slot) and demuxes by it after the shuffle.
        self._rows = batch_stream(feed, 1, self.chunk_bytes,
                                  offsets=self.offsets)

    # ── the packer-facing surface ──

    @property
    def runnable(self) -> bool:
        return not (self.hostpath or self.input_done)

    def take_row(self) -> Optional[np.ndarray]:
        """The next ``[chunk_bytes]`` row, pending until
        :meth:`confirm_step` (or abandoned on a host-path flip).  None
        at end of input or when a >row-wide token forces the host
        path."""
        from dsi_tpu.parallel.streaming import _TokenTooLong

        try:
            batch = next(self._rows)
        except StopIteration:
            self.input_done = True
            return None
        except _TokenTooLong:
            self.to_hostpath()
            return None
        if self.first_take_ts is None:
            self.first_take_ts = time.time()
        off = self.start_offset + self.offsets[self.rows_taken]
        self.rows_taken += 1
        self._pending.append(off)
        return batch[0]

    def to_hostpath(self) -> None:
        """This tenant's input needs the host path: the lane leaves the
        device batch (its rows are excluded at demux) and the whole job
        re-runs on the host oracle at finalize."""
        self.hostpath = True
        self._pending.clear()

    def merge_rows(self, rows: np.ndarray, kk: int) -> None:
        """One packed step's demuxed rows for this tenant, in the
        packed-table layout (kk key lanes + len/count/part)."""
        if not len(rows):
            return
        self.acc.add(rows[:, :kk], rows[:, kk],
                     rows[:, kk + 1].astype(np.int64), rows[:, kk + 2])
        self.delta_log.append(rows[None], np.array([len(rows)],
                                                   dtype=np.int64))

    def confirm_step(self) -> None:
        """Every pending row of this lane was confirmed by one packed
        step: advance the durable cursor, count, maybe checkpoint."""
        if self._pending:
            self.cursor = self._pending[-1]
            self.confirmed_rows += len(self._pending)
            self._pending.clear()
        self.steps += 1
        self.steps_since_resume += 1
        self.policy.note_step()
        if self.policy.due():
            self.save_ckpt()
            self.policy.reset()

    def save_ckpt(self) -> None:
        """One snapshot at the current confirmed boundary: a delta of
        the demuxed step payloads since the last save when the chain
        allows it, else a full accumulator image (the engines'
        want_delta/re-base discipline, one writer)."""
        meta = {"cursor": self.cursor, "rows": self.confirmed_rows}
        kind, parts = "full", None
        with _span("ckpt", stats=self.stats, key="ckpt_s",
                   tenant=self.tenant) as sp:
            if self.writer.want_delta():
                entries = self.delta_log.take()
                if entries is not None:
                    parts, kind = [("", DeltaSteps(entries))], "delta"
            if parts is None:
                self.delta_log.reset()
                parts = [("acc_", self.acc.snapshot())]
            self.writer.commit(parts, meta, kind=kind)
            sp.set(bytes=self.store.last_payload_bytes)
        _count("ckpt_saves")

    def suspend(self) -> None:
        """Evict: one forced durable snapshot; the object is dead after
        (a fresh construction resumes the chain)."""
        if not self.hostpath:
            self.save_ckpt()
        self.writer.drain()
        self.writer.shutdown()

    def finalize(self) -> Dict[str, tuple]:
        """Job complete: the exact result (host path for a hostpath
        lane), ``mr-out-<r>`` files written to the job's out dir."""
        if self.hostpath:
            res = host_wordcount(self.job["files"], self.n_reduce)
        else:
            res = self.acc.finalize()
        out_dir = self.job["out_dir"]
        os.makedirs(out_dir, exist_ok=True)
        write_partitioned_output(res, self.n_reduce, out_dir)
        self.writer.drain()
        self.writer.shutdown()
        return res


class PackedWcScheduler:
    """Shared device-step packer over :class:`TenantLane` rows (module
    docstring).  One instance per daemon — it owns the sticky dispatch
    rung and the warmed wave executables; :meth:`step` is one shared
    dispatch over every runnable lane."""

    def __init__(self, mesh=None, chunk_bytes: int = 1 << 16,
                 n_reduce: int = 10, u_cap: int = 1 << 12):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dsi_tpu.parallel.shuffle import AXIS, default_mesh

        if mesh is None:
            mesh = default_mesh()
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        # The wave program's size contract: a power of two, >= 256.
        self.chunk_bytes = 1 << max(8, int(chunk_bytes - 1).bit_length())
        self.n_reduce = int(n_reduce)
        self.state = {"cap": rung0_cap(self.chunk_bytes, u_cap),
                      "mwl": 16, "frac": 4}
        self.stats = metrics_scope("serve")
        self.stats.update({"packed_steps": 0, "packed_rows": 0,
                           "replays": 0, "upload_s": 0.0, "kernel_s": 0.0,
                           "pull_s": 0.0, "merge_s": 0.0, "take_s": 0.0,
                           "max_tenants_per_step": 0})
        self._sh_chunk = NamedSharding(mesh, P(AXIS, None))
        self._sh_ids = NamedSharding(mesh, P(AXIS))
        self._jax = jax

    def warm(self) -> None:
        """Compile (or load the persisted executable of) the
        sticky-rung wave program from shape structs — the daemon's
        boot-time warm, paid once for every tenant after it."""
        import jax
        import jax.numpy as jnp

        from dsi_tpu.parallel.tfidf import _wave_fn

        sds = jax.ShapeDtypeStruct
        examples = (sds((self.n_dev, self.chunk_bytes), jnp.uint8),
                    sds((self.n_dev,), jnp.int32))
        _wave_fn(examples, n_dev=self.n_dev, n_reduce=self.n_reduce,
                 max_word_len=self.state["mwl"], u_cap=self.state["cap"],
                 size=self.chunk_bytes, mesh=self.mesh,
                 t_cap_frac=self.state["frac"])

    # ── one packed step ──

    def _wave_call(self, chunk_np, ids_np, mwl, cap, frac, batch):
        from dsi_tpu.device.table import _quiet_unusable_donation
        from dsi_tpu.parallel.tfidf import _wave_fn

        with _span("upload", stats=self.stats, key="upload_s", **batch):
            chunk = self._jax.device_put(chunk_np, self._sh_chunk)
            ids = self._jax.device_put(ids_np, self._sh_ids)
        fn = _wave_fn((chunk, ids), n_dev=self.n_dev,
                      n_reduce=self.n_reduce, max_word_len=mwl,
                      u_cap=cap, size=self.chunk_bytes, mesh=self.mesh,
                      t_cap_frac=frac)
        with _quiet_unusable_donation():
            return fn(chunk, ids)

    def _dispatch_ladder(self, chunk_np, ids_np, picks, batch):
        """The synchronous exactness ladder for ONE packed batch — the
        wave walk's replay discipline, with per-lane host-path
        attribution instead of rung aborts: a poisoned lane (non-ASCII,
        or a >64-byte word at the widest rung) is marked, its row
        zeroed, and the batch re-dispatched, so the other lanes'
        exactness flags are judged on clean input."""
        state = self.state
        cap, mwl = state["cap"], state["mwl"]
        while True:
            for frac in (4, 2):
                with _span("kernel", stats=self.stats,
                           key="kernel_s", **batch):
                    rows, scal = self._wave_call(chunk_np, ids_np, mwl,
                                                 cap, frac, batch)
                    scal_np = np.asarray(scal)
                if not scal_np[:, 4].any():
                    break
            dead = [int(d) for d in np.flatnonzero(scal_np[:, 3])
                    if int(d) < len(picks) and not picks[int(d)].hostpath]
            if int(scal_np[:, 2].max()) > 64:
                dead += [int(d) for d in np.flatnonzero(scal_np[:, 2] > 64)
                         if int(d) < len(picks)
                         and not picks[int(d)].hostpath]
            if dead:
                for d in dead:
                    picks[d].to_hostpath()
                    chunk_np[d, :] = 0
                self.stats["replays"] += 1
                continue
            if int(scal_np[:, 2].max()) > mwl:
                mwl = 64  # a word overflowed the packed window: widen
                self.stats["replays"] += 1
                continue
            if int(scal_np[:, 1].max()) > cap:
                cap *= 4  # uniques <= tokens <= size/2: terminates
                self.stats["replays"] += 1
                continue
            break
        state.update(cap=cap, mwl=mwl, frac=frac)
        return rows, scal_np, mwl // 4

    def step(self, lanes: List[TenantLane]) -> List[TenantLane]:
        """Pack up to ``n_dev`` pending rows from ``lanes`` (round-robin
        across tenants; a lone tenant may fill every row, so
        single-tenant throughput matches the engine path) into ONE wave
        dispatch, demux by the doc lane, merge per tenant, confirm.
        Returns the lanes whose rows were confirmed."""
        from dsi_tpu.parallel.shuffle import occupied_prefix

        picks: List[TenantLane] = []
        chunk_np = np.zeros((self.n_dev, self.chunk_bytes), np.uint8)
        while len(picks) < self.n_dev:
            progressed = False
            for lane in list(lanes):
                if len(picks) >= self.n_dev:
                    break
                if not lane.runnable:
                    continue
                with _span("take_row", stats=self.stats, key="take_s",
                           tenant=lane.tenant, bytes=self.chunk_bytes):
                    row = lane.take_row()
                if row is None:
                    continue
                chunk_np[len(picks), :] = row
                picks.append(lane)
                progressed = True
            if not progressed:
                break
        if not picks:
            return []
        # Doc id = batch slot: rides every shuffled row, so the pull
        # demuxes exactly.  Idle rows are all-zero chunks (no tokens).
        ids_np = np.arange(self.n_dev, dtype=np.int32)
        n_tenants = len({ln.tenant for ln in picks})
        batch = {"rows": len(picks), "tenants": n_tenants}  # span fields
        rows, scal_np, kk = self._dispatch_ladder(chunk_np, ids_np, picks,
                                                  batch)
        fault_point("post-dispatch")
        m = int(scal_np[:, 0].max())
        if m:
            with _span("pull", stats=self.stats, key="pull_s", **batch):
                mp = occupied_prefix(m, rows.shape[1])
                rows_np = np.asarray(rows[:, :mp])
            with _span("merge", stats=self.stats, key="merge_s", **batch):
                for d in range(self.n_dev):
                    nr = int(scal_np[d, 0])
                    if not nr:
                        continue
                    r = rows_np[d, :nr]
                    doc = r[:, kk + 2]
                    for slot, lane in enumerate(picks):
                        if lane.hostpath:
                            continue  # dead lane: its rows are dropped
                        sub = r[doc == slot]
                        if len(sub):
                            # Drop the doc column: kk keys + len + tf
                            # + part, the packed-table layout.
                            arr = np.concatenate(
                                [sub[:, :kk + 2], sub[:, kk + 3:kk + 4]],
                                axis=1)
                            lane.merge_rows(arr, kk)
        fault_point("mid-fold")
        confirmed = []
        for lane in dict.fromkeys(picks):
            if lane.hostpath:
                continue
            lane.confirm_step()
            confirmed.append(lane)
        self.stats["packed_steps"] += 1
        self.stats["packed_rows"] += len(picks)
        _count("packed_steps")
        _count("packed_rows", len(picks))
        if n_tenants > self.stats["max_tenants_per_step"]:
            self.stats["max_tenants_per_step"] = n_tenants
        return confirmed


# ── grep lanes (module docstring: the easy demux case) ─────────────────


class _GrepRow(NamedTuple):
    """One taken-but-unconfirmed lane row: the bytes, their valid
    length, the host line count, the stream offset just past the row,
    and the GLOBAL number of its first line, assigned once at take
    time (the top-k key is built from it on the device)."""

    row: np.ndarray
    dlen: int
    n_lines: int
    end_off: int
    base: int


class _Flight(NamedTuple):
    """One dispatched packed step that is not confirmed yet: its rows in
    take order, the three device arrays whose copies to the host are
    under way, what its spans say of it, and the program as the tracer
    was told it (:meth:`~dsi_tpu.obs.trace.Tracer.newest`)."""

    picks: List[Tuple["GrepLane", _GrepRow]]
    outs: tuple
    batch: Dict
    told: Optional[Tuple]


class GrepLane:
    """One tenant grep job's lane in :class:`PackedGrepScheduler`: a
    newline-aligned row stream cut from its input files, host-side
    whole-stream accumulators (totals, histogram, exact top-k) and a
    per-tenant checkpoint chain.

    The accumulators fold per-ROW kernel outputs, so they are
    snapshot-small (``bins`` ints + ``topk`` pairs): checkpoints are
    full images, no delta log needed.  A non-literal pattern flips the
    lane to the host path at construction — the daemon finalizes it on
    :func:`~dsi_tpu.parallel.grepstream.grep_host_oracle` without the
    lane ever joining a pack.
    """

    def __init__(self, job: Dict, chunk_bytes: int, ckpt_dir: str,
                 checkpoint_every: Optional[int] = None,
                 resume: bool = True, bins: Optional[int] = None,
                 topk: Optional[int] = None,
                 stats: Optional[Dict] = None):
        from dsi_tpu.ops.grepk import is_literal_pattern
        from dsi_tpu.parallel.grepstream import (DEFAULT_TOPK, GREP_BINS,
                                                 batch_lines)
        from dsi_tpu.parallel.streaming import stream_files

        self.job = job
        self.tenant = job["tenant"]
        self.pattern = str(job["pattern"])
        self.pat = self.pattern.encode("ascii", errors="replace")
        self.m = len(self.pat)
        self.chunk_bytes = int(chunk_bytes)
        self.bins = int(bins if bins is not None else GREP_BINS)
        self.topk = int(topk if topk is not None else DEFAULT_TOPK)
        self.lines = 0
        self.matched = 0
        self.occurrences = 0
        self.hist = [0] * self.bins
        self.cands: List[Tuple[int, int]] = []
        self.offsets: List[int] = []
        self.rows_taken = 0           # index into self.offsets
        self.confirmed_rows = 0
        self.steps = 0
        # The eviction-quota clock: packed steps ridden, the one in
        # flight too, so a residency holds as many rows as it did when
        # a step was confirmed in the call that dispatched it.
        self.steps_since_resume = 0
        self.hostpath = not (self.m and is_literal_pattern(self.pattern)
                             and self.m <= self.chunk_bytes)
        self.input_done = False
        self.resume_gap_s = 0.0
        self.stats: Dict = {} if stats is None else stats  # as TenantLane
        self.first_take_ts: Optional[float] = None
        self._next_base = 0
        #: the scheduler that took this lane's rows, once it has
        self._sched: Optional["PackedGrepScheduler"] = None
        #: what a step that carried a row of this lane failed with: the
        #: row's results are gone, so the job fails and nothing more of
        #: the lane is folded or saved
        self.lost: Optional[Exception] = None
        ident = {"tenant": self.tenant, "pattern": self.pattern,
                 "files": [[os.path.basename(f), os.path.getsize(f)]
                           for f in job["files"]],
                 "chunk_bytes": self.chunk_bytes,
                 "bins": self.bins, "topk": self.topk}
        self.store = CheckpointStore(ckpt_dir, "serve-grep", ident)
        self.writer = CheckpointWriter(self.store, self.stats,
                                       async_=False, delta=False)
        self.policy = CheckpointPolicy(checkpoint_every)
        start = 0
        if resume and not self.hostpath:
            t0 = time.perf_counter()
            loaded = self.store.load_latest_chain()
            if loaded is not None:
                # Full images, no deltas.  (A chain written before
                # PR 28 also carries a ``rung``; it is read past.)
                meta, arrays, _deltas = loaded
                start = int(meta["cursor"])
                self.lines = int(meta["lines"])
                self.matched = int(meta["matched"])
                self.occurrences = int(meta["occurrences"])
                self.confirmed_rows = int(meta["rows"])
                self.hist = [int(v) for v in arrays["g_hist"]]
                self.cands = [(int(r[0]), int(r[1]))
                              for r in arrays["g_cand"]]
                self._next_base = self.lines
                self.resume_gap_s = round(time.perf_counter() - t0, 4)
        elif not resume:
            self.store.reset()
        self.start_offset = start
        self.cursor = start
        blocks = stream_files(job["files"])
        feed = skip_stream(blocks, start) if start else blocks
        self._rows = batch_lines(feed, 1, self.chunk_bytes,
                                 offsets=self.offsets)

    # ── the packer-facing surface ──

    @property
    def runnable(self) -> bool:
        return not (self.hostpath or self.input_done
                    or self.lost is not None)

    def take_row(self) -> Optional[_GrepRow]:
        """The next row, pulled (and base-numbered) from the stream.
        None at end of input or on a host-path flip (a line wider than
        one row)."""
        from dsi_tpu.parallel.grepstream import _LineTooLong

        try:
            batch, lens, row_lines = next(self._rows)
        except StopIteration:
            self.input_done = True
            return None
        except _LineTooLong:
            self.to_hostpath()
            return None
        if self.first_take_ts is None:
            self.first_take_ts = time.time()
        end = self.start_offset + self.offsets[self.rows_taken]
        self.rows_taken += 1
        info = _GrepRow(batch[0], int(lens[0]), int(row_lines[0]), end,
                        self._next_base)
        self._next_base += info.n_lines
        return info

    def to_hostpath(self) -> None:
        self.hostpath = True

    def confirm_row(self, info: _GrepRow, hist_row: np.ndarray,
                    cand_pairs: List[Tuple[int, int]], matched: int,
                    occurrences: int) -> None:
        """Fold one row's kernel outputs and advance the durable
        cursor to the row's end offset."""
        from dsi_tpu.parallel.grepstream import merge_topk

        self.cursor = info.end_off
        self.confirmed_rows += 1
        self.lines += info.n_lines
        self.matched += int(matched)
        self.occurrences += int(occurrences)
        for b in range(self.bins):
            self.hist[b] += int(hist_row[b])
        if cand_pairs:
            self.cands = list(merge_topk(self.cands + cand_pairs,
                                         self.topk))

    def note_step(self) -> None:
        """One packed step confirmed rows for this lane: count it and
        maybe checkpoint (the wc lanes' cadence discipline)."""
        self.steps += 1
        self.policy.note_step()
        if self.policy.due():
            self.save_ckpt()
            self.policy.reset()

    def save_ckpt(self) -> None:
        meta = {"cursor": self.cursor, "lines": self.lines,
                "matched": self.matched,
                "occurrences": self.occurrences,
                "rows": self.confirmed_rows}
        with _span("ckpt", stats=self.stats, key="ckpt_s",
                   tenant=self.tenant) as sp:
            cand = np.array(self.cands or np.zeros((0, 2)), dtype=np.int64)
            parts = [("g_", {"hist": np.array(self.hist, dtype=np.int64),
                             "cand": cand.reshape(-1, 2)})]
            self.writer.commit(parts, meta, kind="full")
            sp.set(bytes=self.store.last_payload_bytes)
        _count("ckpt_saves")

    def _settle(self) -> None:
        """Stand at a confirmed row: the scheduler confirms the step in
        flight now if it carries a row of this lane.  A lane that lost a
        row raises what the step failed with."""
        if self._sched is not None:
            self._sched.drain(self)
        if self.lost is not None and not self.hostpath:
            raise self.lost

    def suspend(self) -> None:
        """Evict: one forced durable snapshot, at the end of the last
        row taken; dead after."""
        self._settle()
        if not self.hostpath:
            self.save_ckpt()
        self.writer.drain()
        self.writer.shutdown()

    def finalize(self):
        """Job complete: the exact :class:`GrepStreamResult` (host
        oracle for a hostpath lane — correctness never depends on the
        kernel)."""
        from dsi_tpu.parallel.grepstream import (GrepStreamResult,
                                                 grep_host_oracle,
                                                 merge_topk)
        from dsi_tpu.parallel.streaming import stream_files

        self._settle()
        if self.hostpath:
            res = grep_host_oracle(stream_files(self.job["files"]),
                                   self.pattern, bins=self.bins,
                                   topk=self.topk)
        else:
            res = GrepStreamResult(self.lines, self.matched,
                                   self.occurrences, tuple(self.hist),
                                   merge_topk(self.cands, self.topk))
        self.writer.drain()
        self.writer.shutdown()
        return res


def _lose(picks: List[Tuple[GrepLane, _GrepRow]], e: Exception) -> None:
    """The step that carried ``picks`` failed with ``e``: their results
    are gone, so their lanes' jobs fail, and no others."""
    for lane, _info in picks:
        lane.lost = e


class PackedGrepScheduler:
    """Shared grep-step packer over :class:`GrepLane` rows (module
    docstring).  One instance per daemon; :meth:`step` is one shared
    dispatch over ONE pattern-length group — groups take
    turns round-robin, so mixed pattern lengths interleave fairly
    instead of the shortest length starving the rest."""

    def __init__(self, mesh=None, chunk_bytes: int = 1 << 16,
                 bins: Optional[int] = None, topk: Optional[int] = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dsi_tpu.parallel.grepstream import DEFAULT_TOPK, GREP_BINS
        from dsi_tpu.parallel.shuffle import AXIS, default_mesh

        if mesh is None:
            mesh = default_mesh()
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        self.chunk_bytes = int(chunk_bytes)
        self.bins = int(bins if bins is not None else GREP_BINS)
        self.topk = int(topk if topk is not None else DEFAULT_TOPK)
        self.stats = metrics_scope("serve_grep")
        self.stats.update({"packed_steps": 0, "packed_rows": 0,
                           "results_ready": 0, "settles": 0,
                           "host_fallbacks": 0, "upload_s": 0.0,
                           "kernel_s": 0.0, "pull_s": 0.0,
                           "merge_s": 0.0, "take_s": 0.0,
                           "max_tenants_per_step": 0})
        self._sh_chunk = NamedSharding(mesh, P(AXIS, None))
        self._rr = 0
        self._flight: Optional[_Flight] = None
        self._jax = jax

    def warm(self, m: int) -> None:
        """Compile (or load) one pack shape ahead of need — the boot
        warm for the common pattern length; every other length pays its
        cold compile once, persisted."""
        from dsi_tpu.parallel.grepstream import grep_pack_fn

        grep_pack_fn(self.n_dev, self.chunk_bytes, int(m),
                     bins=self.bins, k=self.topk, mesh=self.mesh)

    # ── one packed step ──

    def _pick_group(self, lanes: List[GrepLane]) -> List[GrepLane]:
        """The next pattern-length group, round-robin over the sorted
        lengths — a deterministic turn order under churn."""
        groups: Dict[int, List[GrepLane]] = {}
        for lane in lanes:
            if lane.runnable:
                groups.setdefault(lane.m, []).append(lane)
        if not groups:
            return []
        keys = sorted(groups)
        key = keys[self._rr % len(keys)]
        self._rr += 1
        return groups[key]

    def _dispatch(self, chunk_np, pats_np, lens_np, bases_np, m, batch):
        """Put and enqueue: one ``device_put`` of the step's operands,
        the program's call, and the starts of its results' copies to the
        host, right behind it on the device's queue.  Returns the three
        device arrays and reads nothing: :meth:`_confirm` does, one step
        later, when they are finished copies.  ``batch`` is what the
        step's spans say of it (``rows``, ``tenants``)."""
        from dsi_tpu.device.table import (_copy_to_host_async,
                                          _quiet_unusable_donation)
        from dsi_tpu.parallel.grepstream import grep_pack_fn, step_meta
        from dsi_tpu.utils.jaxcompat import enable_x64

        with _span("upload", stats=self.stats, key="upload_s", **batch):
            with enable_x64(True):   # keep the u64 bases u64 through it
                chunk, pats, meta = self._jax.device_put(
                    (chunk_np, pats_np, step_meta(lens_np, bases_np)),
                    (self._sh_chunk,) * 3)
        fn = grep_pack_fn(self.n_dev, self.chunk_bytes, m,
                          bins=self.bins, k=self.topk, mesh=self.mesh)
        with _span("kernel", stats=self.stats, key="kernel_s", **batch):
            with _quiet_unusable_donation():
                outs = fn(chunk, pats, meta)   # (hist_ext, cand, scal)
            _enqueued(outs[2])
            for arr in outs:
                _copy_to_host_async(arr)
        return outs

    def _launch(self, picks: List[Tuple[GrepLane, _GrepRow]],
                m: int) -> _Flight:
        """Fill one batch with ``picks`` and dispatch it; a dispatch
        that fails loses their rows (:func:`_lose`)."""
        chunk_np = np.zeros((self.n_dev, self.chunk_bytes), np.uint8)
        pats_np = np.zeros((self.n_dev, m), np.uint8)
        lens_np = np.zeros(self.n_dev, dtype=np.int32)
        bases_np = np.zeros(self.n_dev, dtype=np.int64)
        # Idle rows carry slot-0's pattern over an all-zero chunk: a
        # printable-ASCII pattern cannot match zero padding, so they
        # contribute nothing (the kernel's padding argument).
        pats_np[:] = np.frombuffer(picks[0][0].pat, dtype=np.uint8)
        for slot, (lane, info) in enumerate(picks):
            chunk_np[slot, :len(info.row)] = info.row
            pats_np[slot] = np.frombuffer(lane.pat, dtype=np.uint8)
            lens_np[slot] = info.dlen
            bases_np[slot] = info.base
            lane._sched = self
        n_tenants = len({ln.tenant for ln, _i in picks})
        batch = {"rows": len(picks), "tenants": n_tenants}
        tracer = _get_tracer()
        before = tracer.enqueued_n
        try:
            outs = self._dispatch(chunk_np, pats_np, lens_np, bases_np, m,
                                  batch)
        except Exception as e:
            _lose(picks, e)
            raise
        for lane in dict.fromkeys(ln for ln, _info in picks):
            lane.steps_since_resume += 1
        if n_tenants > self.stats["max_tenants_per_step"]:
            self.stats["max_tenants_per_step"] = n_tenants
        return _Flight(picks, outs, batch, tracer.newest(before))

    def _confirm(self, rec: _Flight) -> List[GrepLane]:
        """Read one dispatched step's results and fold them: per-row
        demux in take order, which IS each lane's byte-range order, so
        every lane's cursor advances monotonically.  Returns the lanes
        that confirmed rows.  A read that fails loses the step's rows
        (:func:`_lose`)."""
        if rec.told is not None and _get_tracer().landed(*rec.told):
            self.stats["results_ready"] += 1
        try:
            with _span("pull", stats=self.stats, key="pull_s",
                       **rec.batch):
                hist_np, cand_np, scal_np = (np.asarray(a)
                                             for a in rec.outs)
        except Exception as e:
            _lose(rec.picks, e)
            raise
        with _span("merge", stats=self.stats, key="merge_s", **rec.batch):
            for slot, (lane, info) in enumerate(rec.picks):
                if lane.lost is not None:
                    continue  # it lost an earlier row: nothing more folds
                n_cand = int(scal_np[slot, 0])
                pairs = [((int(cand_np[slot, i, 0]) << 32)
                          | int(cand_np[slot, i, 1]),
                          int(cand_np[slot, i, 3]))
                         for i in range(n_cand)]
                lane.confirm_row(info, hist_np[slot], pairs,
                                 int(scal_np[slot, 3]),
                                 int(scal_np[slot, 4]))
        confirmed = list(dict.fromkeys(
            lane for lane, _info in rec.picks if lane.lost is None))
        fault_point("mid-fold")
        for lane in confirmed:
            lane.note_step()
        self.stats["packed_steps"] += 1
        self.stats["packed_rows"] += len(rec.picks)
        _count("packed_steps")
        _count("packed_rows", len(rec.picks))
        return confirmed

    def drain(self, lane: GrepLane) -> None:
        """Confirm the step in flight now, ahead of its turn, if it
        carries a row of ``lane``: what a lane asks before it is
        snapshotted for an eviction or finalized."""
        rec = self._flight
        if rec is not None and any(ln is lane for ln, _info in rec.picks):
            self._flight = None
            self.stats["settles"] += 1
            self._confirm(rec)

    @property
    def in_flight(self) -> bool:
        """Whether a dispatched step waits for the next call of
        :meth:`step` to confirm it."""
        return self._flight is not None

    def step(self, lanes: List[GrepLane]) -> List[GrepLane]:
        """Pack up to ``n_dev`` pending rows from ONE shape group
        (round-robin across its tenants; a lone tenant may fill every
        row) into one dispatch, then confirm the step the call before
        dispatched, whose results have reached the host meanwhile.
        Returns the lanes that confirmed rows: none for the first call of
        a burst, and the last step's for a call that found no row to
        take."""
        group = self._pick_group(lanes)
        picks: List[Tuple[GrepLane, _GrepRow]] = []
        while group and len(picks) < self.n_dev:
            progressed = False
            for lane in group:
                if len(picks) >= self.n_dev:
                    break
                if not lane.runnable:
                    continue
                with _span("take_row", stats=self.stats, key="take_s",
                           tenant=lane.tenant) as sp:
                    info = lane.take_row()
                    sp.set(bytes=info.dlen if info is not None else 0)
                if info is None:
                    if lane.hostpath:
                        self.stats["host_fallbacks"] += 1
                    continue
                picks.append((lane, info))
                progressed = True
            if not progressed:
                break
        new = None
        if picks:
            new = self._launch(picks, group[0].m)
            fault_point("post-dispatch")
        prev, self._flight = self._flight, new
        return self._confirm(prev) if prev is not None else []
