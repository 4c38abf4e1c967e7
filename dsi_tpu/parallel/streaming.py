"""Streaming SPMD word count: corpus size decoupled from device memory.

``wordcount_sharded`` (parallel/shuffle.py) materialises the whole corpus
host-side and pads every device shard to the longest's power of two — fine
at bench scale, structurally incapable of a 10 GB corpus.  This
module is the chunked multi-step redesign:

* the corpus arrives as an **iterator of byte blocks** (files, sockets,
  generators — never required to fit in memory),
* a carry buffer slices it into fixed ``[n_dev, chunk_bytes]`` batches,
  cutting only at non-letter boundaries so no token straddles a chunk
  (same rule as ``shard_text``; the carry makes it exact across batches),
* every batch runs the SAME compiled ``mapreduce_step`` program (static
  shapes: one compile per capacity rung for the whole stream, however
  long),
* per-step per-device grouped counts are merged into a host accumulator
  (``parallel/merge.py`` PackedCounts: raw packed-key tables, numpy
  lexsort + segmented sum, spellings decoded once at the end) — bounded
  by *vocabulary*, not corpus size.

Three scale levers this module owns:

* **sticky adaptive capacity** — ``u_cap`` is only the STARTING per-device
  unique capacity; a step that overflows retries itself wider (the shared
  ``exactness_retry`` ladder) and the capacity that worked is reused for
  every later step, so a low-vocabulary stream never pays for a
  worst-case kernel (the sort inside the step is O(cap log cap)) and a
  high-vocabulary stream widens exactly once,
* **prefix-sliced D2H** — only a prefix of the result tables (a power
  of two, so the slice programs stay bounded) crosses the wire; the
  pull cost tracks vocabulary, not capacity.  The prefix is sticky and
  predicted, like the capacity: it starts at the start rung's capacity,
  a step whose max per-device merged uniques outgrow it raises it to
  their pow2, and the step's pack is enqueued WITH the step, at that
  prefix, before the step's own count is known,
* **vectorized merge** — no per-word Python in the steady state.

And the lever that makes the stream a *pipeline* rather than a lockstep
loop (the serialized batch → upload → kernel → pull → merge
cycle): ``wordcount_streaming`` keeps a
window of ``depth`` steps in flight (default 2, ``DSI_STREAM_PIPELINE_
DEPTH``).  A background batcher thread slices blocks into a bounded
queue; the main thread uploads and dispatches step k+1 without
synchronizing while step k's kernel runs; the overflow-flag check
(``scal[:, 4]`` and friends) is **deferred** until a step leaves the
window, and the host-side merge of a confirmed step overlaps the device
work of the steps behind it.  Deferral is safe because the accumulator
only ever merges a step already proven exact — a late-detected overflow
replays just that step through the shared exactness ladder at the wider
capacity, disturbing nothing merged before it.  ``depth=1`` is the
synchronous path: same function, same ladder, same results dict.

Memory bound, explicitly: device HBM holds at most ``depth`` chunk
buffers (each step's upload is DONATED to its kernel —
`backends/aotcache.cached_compile(donate_argnums=...)` /
``shuffle.mapreduce_step_donate`` — so a window never doubles chunk
residency) plus ``depth`` per-step result sets awaiting their deferred
pull — one packed ``[n_dev, n_dev*u_cap, K+3]`` tensor per in-flight
step under ``aot``, device accumulation and a ``map`` (the four result
tables free as soon as the pack at dispatch consumes them); on the
host-merge path the packed prefix AND the four tables, which a step
that outgrew the prefix is packed from at retirement — plus one
kernel's working buffers.  All of it is
capacity-bounded (scales with ``depth x n_dev^2 x u_cap``, never with
corpus bytes); size ``depth``/``u_cap`` together when HBM is tight.
The host holds a small rotating pool of batch buffers (O(depth)), the
carry (< ``n_dev x chunk_bytes + block``) and the accumulator
(O(uniques) merged table plus a bounded compaction window).

The reference has no analogue (its scaling lever is nMap = #input files on
a shared filesystem, ``mr/coordinator.go:152``); this is that lever
re-designed for a device mesh: nMap becomes "number of stream steps", and
the pipeline is the reference's map/shuffle/reduce-of-different-tasks
concurrency re-created inside one process.

``device_accumulate=True`` moves the cross-step merge itself on-device
(``device/table.py``): a confirmed step's packed reduce output FOLDS into
a persistent device-resident table with one compiled merge program, and
the host pulls the merged table only every ``sync_every`` folds (plus
stream end) — ``ceil(steps/K) + widens`` pulls instead of one per step
(whether that pays on the chip is not measured yet).  Folds lag the deferred-exactness
confirmation window: only steps whose overflow checks passed are folded,
and a replayed step folds its replayed (exact) output — so the
bit-identical depth=1 parity guarantee survives unchanged.  A fold whose
merged uniques overflow the table's capacity rung is a global no-op that
surfaces a widen signal; the service drains the table to the host
accumulator, reallocates at the next rung, and re-folds the orphaned
steps (their packed tensors are kept alive until their fold confirms,
exactly for this).

The window/producer/pool mechanics themselves live in the shared
dispatch/finish pipeline core (``parallel/pipeline.py``); this module
supplies the word-count-specific dispatch (sticky-rung step launch) and
finish (deferred exactness check, merge-or-replay) callbacks.  The
TF-IDF wave walk (``parallel/tfidf.py``) consumes the same core.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Iterable, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dsi_tpu.ckpt import (
    CheckpointPolicy,
    CheckpointStore,
    CheckpointWriter,
    DeltaSteps,
    HostDeltaLog,
    checkpoint_async_default,
    checkpoint_delta_default,
    drain_packed_steps,
    fault_point,
    skip_stream,
)
from dsi_tpu.device.policy import SyncPolicy, mesh_shards_default
from dsi_tpu.device.table import DeviceTable, _quiet_unusable_donation
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.ops.wordcount import exactness_retry, rung0_cap
from dsi_tpu.ops import wirecodec
from dsi_tpu.parallel.merge import PackedCounts
from dsi_tpu.parallel.pipeline import (
    BufferPool,
    StepPipeline,
    fold_source_stats,
    pipeline_depth,
)
from dsi_tpu.parallel.stepobj import EngineStep
from dsi_tpu.parallel.shuffle import (
    AXIS,
    _is_letter_byte,
    _mapreduce_step_impl,
    _slice_pack,
    default_mesh,
    mapreduce_step,
    mapreduce_step_donate,
    occupied_prefix,
)


# A cut never needs to back off further than the longest word the kernels
# can represent (64 bytes, ops/wordcount.py exactness_retry ladder) — if it
# does, the input has a word the device path must hand to the host anyway.
_MAX_BACKOFF = 96

#: jax.jit donate_argnums for the stream step program: the chunk upload is
#: consumed by the kernel.  Shared by the AOT compile, the warmer, and the
#: cache-existence probe so all three agree on the executable's key.
_STEP_DONATE = (0,)


class _TokenTooLong(Exception):
    """A letter run longer than the device word limit spans a cut point."""


class _NeedsHostPath(Exception):
    """A step proved the stream needs the host path (non-ASCII, >64-byte
    word): unwind the pipeline and return None to the caller."""


def _cut_at_boundary(buf, size: int) -> int:
    """Largest c <= size with no letter run crossing buf[c-1]/buf[c]."""
    if len(buf) <= size:
        return len(buf)
    if not (_is_letter_byte(buf[size - 1]) and _is_letter_byte(buf[size])):
        return size  # common case: the natural cut already sits on a gap
    # Back off vectorized: one numpy scan over the candidate window
    # instead of the former per-byte Python loop (~100 interpreter
    # iterations per long-word cut on the hot batching path).
    lo = max(0, size - _MAX_BACKOFF - 1)
    win = np.frombuffer(memoryview(buf)[lo:size + 1], dtype=np.uint8)
    letter = ((win >= 65) & (win <= 90)) | ((win >= 97) & (win <= 122))
    ok = ~(letter[:-1] & letter[1:])  # ok[p] ⇔ cut c = lo+p+1 splits no run
    hits = np.flatnonzero(ok)
    if hits.size:
        return lo + 1 + int(hits[-1])
    if size <= _MAX_BACKOFF:
        return 0  # the whole prefix is one (representable) letter run
    raise _TokenTooLong


def batch_stream(blocks: Iterable[bytes], n_dev: int, chunk_bytes: int,
                 pool: Optional[BufferPool] = None,
                 offsets: Optional[list] = None) -> Iterator[np.ndarray]:
    """Slice a byte-block stream into zero-padded [n_dev, chunk_bytes]
    batches, cutting rows only at non-letter boundaries.

    With ``pool`` (the streaming engine's buffer pool) batches come from a
    small rotating buffer set instead of a fresh ``np.zeros`` per batch;
    the consumer must hand each yielded batch back via ``pool.give`` once
    it no longer reads it (the pipeline returns a buffer when its step is
    confirmed exact).  Rows are always written in full — data then zero
    tail — so a recycled buffer never leaks stale bytes.

    With ``offsets`` (the checkpoint cursor hook), the stream offset
    just past each yielded batch's content is appended per batch —
    appended BEFORE the yield, so the consumer can read ``offsets[i]``
    the moment batch ``i`` arrives.  Batching is a pure function of the
    byte stream, so resuming from ``skip_stream(blocks, offsets[i])``
    reproduces batches ``i+1, i+2, ...`` exactly."""
    carry = bytearray()
    consumed = 0

    def new_batch() -> np.ndarray:
        if pool is not None:
            return pool.take()
        return np.zeros((n_dev, chunk_bytes), dtype=np.uint8)

    batch = new_batch()
    row = 0

    def fill_rows(final: bool):
        nonlocal row, carry, batch, consumed
        while carry and (len(carry) >= chunk_bytes + 1 or final):
            cut = _cut_at_boundary(carry, chunk_bytes)
            if cut == 0:
                # A letter run as wide as the whole row: no cut can make
                # progress at this chunk size, so the word needs the host
                # path.  (The pre-pool code spun forever here, emitting
                # empty rows without ever consuming the carry.)
                raise _TokenTooLong
            view = np.frombuffer(carry, dtype=np.uint8, count=cut)
            batch[row, :cut] = view
            del view           # release the bytearray export before the
            del carry[:cut]    # resize (a live view blocks it)
            consumed += cut
            batch[row, cut:] = 0
            row += 1
            if row == n_dev:
                if offsets is not None:
                    offsets.append(consumed)
                yield batch
                batch = new_batch()
                row = 0

    for block in blocks:
        carry.extend(block)
        yield from fill_rows(final=False)
    yield from fill_rows(final=True)
    if row:
        batch[row:] = 0  # recycled buffer: stale tail rows must not count
        if offsets is not None:
            offsets.append(consumed)
        yield batch      # tail batch; remaining rows are empty chunks
    elif pool is not None:
        pool.give(batch)  # taken but never filled: straight back


def stream_files(paths: Sequence[str],
                 block_bytes: int = 4 << 20) -> Iterator[bytes]:
    """File contents as a block stream, separated by newlines so the last
    word of one file and the first of the next never merge."""
    for i, p in enumerate(paths):
        if i:
            yield b"\n"
        with open(p, "rb") as f:
            while True:
                b = f.read(block_bytes)
                if not b:
                    break
                yield b


def stream_rows(paths: Sequence[str],
                block_bytes: int = 4 << 20) -> Iterator[bytes]:
    """Files of newline-terminated rows as a block stream: nothing between
    the files but the newline a file's last row lacks, so that a row of
    the stream is a row of a file and the rows' count is the newlines'."""
    for p in paths:
        last = b"\n"
        with open(p, "rb") as f:
            while True:
                b = f.read(block_bytes)
                if not b:
                    break
                last = b[-1:]
                yield b
        if last != b"\n":
            yield b"\n"


def _row_batches(blocks: Iterable[bytes], n_dev: int, chunk_bytes: int,
                 pool: Optional[BufferPool] = None,
                 offsets: Optional[list] = None) -> Iterator[np.ndarray]:
    """:func:`batch_stream`'s batches for a stream of rows: cut behind a
    newline (``parallel/grepstream.batch_lines``' cut, in place in the
    incoming block, without its line counts, which nothing here reads),
    so no row straddles a chunk.  A row that no chunk can hold fails the
    job."""
    from dsi_tpu.ops.fieldsum import BadRow
    from dsi_tpu.parallel.grepstream import _LineTooLong, batch_lines

    try:
        for batch, _lens, _lines in batch_lines(blocks, n_dev, chunk_bytes,
                                                pool=pool, offsets=offsets,
                                                count_lines=False):
            yield batch
    except _LineTooLong:
        raise BadRow(f"a row is longer than a chunk's {chunk_bytes} bytes")


def _step_program(*, n_dev: int, n_reduce: int, max_word_len: int,
                  u_cap: int, mesh: Mesh, t_cap_frac: int):
    """The (name, fn) pair for one compiled ``mapreduce_step`` shape —
    single definition shared by the cached-compile path and the warmer,
    so the warmer's key is by construction the key a run compiles."""

    def fn(c):
        return _mapreduce_step_impl(c, n_dev=n_dev, n_reduce=n_reduce,
                                    max_word_len=max_word_len, u_cap=u_cap,
                                    mesh=mesh, t_cap_frac=t_cap_frac)

    name = (f"stream_step_d{n_dev}_r{n_reduce}_w{max_word_len}"
            f"_u{u_cap}_f{t_cap_frac}")
    return name, fn


def _aot_step_fn(example_chunks, donate: bool = True, **kw):
    """Explicitly compiled ``mapreduce_step`` (``backends/aotcache.py``
    memo).  ``example_chunks`` may be a ``ShapeDtypeStruct`` (warming
    compiles without executing).  The chunk argument is donated (the
    pipeline re-uploads per attempt) unless ``donate=False`` — the
    kernel-only bench row's variant, whose HBM-resident chunk must
    survive every rep (a distinct key: donation is part of the
    executable's aliasing config)."""
    from dsi_tpu.backends import aotcache

    name, fn = _step_program(**kw)
    with _quiet_unusable_donation():  # a new shape compiles right here
        return aotcache.cached_compile(
            name, fn, (example_chunks,),
            donate_argnums=_STEP_DONATE if donate else (),
            x64=True)


def _aot_step(chunks, **kw):
    return _aot_step_fn(chunks, **kw)(chunks)


def _pack_program(*, mp: int):
    """(name, fn) for one compiled ``shuffle._slice_pack`` shape — shared
    like :func:`_step_program`."""

    def fn(k, l, c, p):
        return _slice_pack(k, l, c, p, mp=mp)

    return f"stream_pack_m{mp}", fn


def _aot_pack_fn(example_args, *, mp: int):
    """Explicitly compiled ``shuffle._slice_pack`` (as
    :func:`_aot_step_fn`).  ``example_args`` may be shape structs."""
    from dsi_tpu.backends import aotcache

    name, fn = _pack_program(mp=mp)
    return aotcache.cached_compile(name, fn, example_args)


def _stream_examples(n_dev: int, chunk_bytes: int, u_cap: int,
                     max_word_len: int):
    """Shape structs for the step input and pack inputs at one rung."""
    import jax

    sds = jax.ShapeDtypeStruct
    chunks = sds((n_dev, chunk_bytes), jnp.uint8)
    rows = n_dev * u_cap
    kk = max_word_len // 4
    pack_args = (sds((n_dev, rows, kk), jnp.uint32),
                 sds((n_dev, rows), jnp.int32),
                 sds((n_dev, rows), jnp.int32),
                 sds((n_dev, rows), jnp.uint32))
    return chunks, rows, pack_args


def _aot_pack(keys, lens, cnts, parts, *, mp: int):
    return _aot_pack_fn((keys, lens, cnts, parts), mp=mp)(
        keys, lens, cnts, parts)


def warm_stream_aot(mesh: Mesh | None = None, chunk_bytes: int = 1 << 20,
                    n_reduce: int = 10,
                    word_lens: Sequence[int] = (16,),
                    caps: Sequence[int] = (1 << 12, 1 << 14, 1 << 16),
                    fracs: Sequence[int] = (4, 2),
                    device_accumulate: bool = False,
                    mesh_shards: int = 0) -> None:
    """Compile the program shapes
    ``wordcount_streaming(..., aot=True)`` reaches at these parameters,
    from shape structs alone (no data, nothing executed) — so the run
    that follows, and any later process against the same compile cache,
    compiles nothing.

    ``caps`` must cover every capacity rung reachable from the stream's
    ``u_cap`` start for its vocabulary (the default covers the function
    default 1<<12 plus two x4 widenings); ``fracs`` mirrors the step's
    token-capacity ladder.  The 64-byte word-window rung is NOT warmed by
    default — it is reachable only by streams carrying >``max_word_len``
    -byte words; pass ``word_lens=(16, 64)`` if yours can."""
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    for mwl in word_lens:
        for cap in caps:
            chunks, rows, pack_args = _stream_examples(n_dev, chunk_bytes,
                                                       cap, mwl)
            for frac in fracs:
                _aot_step_fn(chunks, n_dev=n_dev, n_reduce=n_reduce,
                             max_word_len=mwl, u_cap=cap, mesh=mesh,
                             t_cap_frac=frac)
            _aot_pack_fn(pack_args, mp=rows)
            if device_accumulate:
                # Fold/clear/pack shapes for the device accumulator at
                # this step rung: the rung-0 table (cap = step rows)
                # plus one x4 widening (device/table.py rung ladder).
                from dsi_tpu.device.table import warm_device_fold

                warm_device_fold(mesh, u_cap=cap, kk=mwl // 4,
                                 table_rungs=2, mesh_shards=mesh_shards)


def warm_kernel_row(mesh: Mesh | None = None, chunk_bytes: int = 1 << 21,
                    n_reduce: int = 10, max_word_len: int = 16,
                    u_cap: int = 1 << 15) -> None:
    """Compile the NON-donated step program the bench's kernel-only row
    runs, from shape structs alone — the rep loop re-executes one program
    on an HBM-resident buffer, so its input cannot be donated, and a
    non-donated program is a distinct cache key from the pipeline's
    donated one."""
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    chunks, _, _ = _stream_examples(n_dev, chunk_bytes, u_cap, max_word_len)
    _aot_step_fn(chunks, donate=False, n_dev=n_dev, n_reduce=n_reduce,
                 max_word_len=max_word_len, u_cap=u_cap, mesh=mesh,
                 t_cap_frac=4)


def stream_kernel_reps(chunk_np: np.ndarray, mesh: Mesh | None = None,
                       n_reduce: int = 10, max_word_len: int = 16,
                       u_cap: int = 1 << 15, reps: int = 5,
                       aot: bool = True):
    """Transfer-independent kernel-only measurement: upload ``chunk_np``
    ONCE, run the stream's ``mapreduce_step`` ``reps`` times on the
    HBM-resident buffer (non-donated program, so the buffer survives
    every rep), blocking on the tiny scalar block per rep.  Returns
    ``(times, exact)`` — per-rep wall seconds (one untimed warm call
    first: executable load + first-dispatch costs stay out of the
    kernel number) and whether every rep's exactness flags were clean
    (a rate for an overflowing kernel must never enter a trend).

    On-chip compute MB/s with exactly one chunk upload and ``reps``
    scalar pulls.
    """
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    sharding = NamedSharding(mesh, PartitionSpec(AXIS, None))
    chunks = jax.device_put(chunk_np, sharding)
    kw = dict(n_dev=n_dev, n_reduce=n_reduce, max_word_len=max_word_len,
              u_cap=u_cap, mesh=mesh, t_cap_frac=4)
    if aot:
        fn = _aot_step_fn(chunks, donate=False, **kw)
    else:
        from dsi_tpu.parallel.shuffle import mapreduce_step

        def fn(c):
            return mapreduce_step(c, **kw)
    exact = True
    times = []
    for rep in range(reps + 1):
        t0 = time.perf_counter()
        keys, lens, cnts, parts, scal = fn(chunks)
        scal_np = np.asarray(scal)  # blocks: the kernel actually ran
        if rep:
            times.append(time.perf_counter() - t0)
        exact = exact and not scal_np[:, 4].any() \
            and int(scal_np[:, 1].max()) <= u_cap \
            and int(scal_np[:, 2].max()) <= max_word_len \
            and not scal_np[:, 3].any()
    return times, exact


class WordcountStep(EngineStep):
    """Resumable step object over the streaming word-count engine: the
    explicit ``{advance, confirm, checkpoint, restore, close}`` state
    machine (``parallel/stepobj.py``) the serving daemon multiplexes.
    Parameters and semantics are exactly :func:`wordcount_streaming`'s
    (now a construct-drive-close wrapper over this class); a
    ``resume=True`` construction restores the newest valid chain BEFORE
    the first dispatch, so device state and sticky rungs exist when the
    window opens.

    ``device_batches`` (the plan layer's stage handoff, ``dsi_tpu/plan``)
    replaces the block stream with an iterator of ready
    ``[n_dev, chunk_bytes]`` batches — jax.Arrays consumed IN PLACE
    (the upstream stage's device-resident output IS this stage's
    upload; no host bytes move) or np.ndarrays (spilled/restored
    buffers, re-uploaded like any batch).  Batch rows must respect the
    engine's cut contract (no token straddles a row's fill point; zero
    tails terminate the last token).  Step programs run NON-donated in
    this mode so a late-detected overflow can replay from the same
    resident buffer; ``checkpoint_dir`` is refused (a byte cursor has
    no meaning over foreign batches — chains commit at stage
    boundaries instead).

    ``map`` (``ops/fieldsum.FieldSum``) makes the engine an aggregation:
    ``blocks`` are newline-terminated rows of delimited fields
    (:func:`stream_rows`), batches are cut behind a newline, a step groups
    the rows' key field and sums their value field (two ``uint32`` lanes
    a sum through the step, the pull and the merge), and the result's
    numbers print as decimals.  A row that cannot be read raises
    ``fieldsum.BadRow`` (the job fails; there is no host path).  The
    host-merge accumulator only: ``aot``, ``device_accumulate``,
    ``mesh_shards``, ``checkpoint_dir``, ``wire_upload`` and
    ``device_batches`` are refused with it.  ``pipeline_stats`` gains
    ``agg_rows``, ``agg_groups`` and ``agg_value_lanes``."""

    def __init__(self, blocks: Iterable[bytes], mesh: Mesh | None = None,
                 n_reduce: int = 10, chunk_bytes: int = 1 << 20,
                 max_word_len: int = 16, u_cap: int = 1 << 12,
                 aot: bool = False, on_attempt=None,
                 depth: Optional[int] = None,
                 pipeline_stats: Optional[dict] = None,
                 device_accumulate: bool = False,
                 sync_every: Optional[int] = None,
                 mesh_shards: Optional[int] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_async: Optional[bool] = None,
                 checkpoint_delta: Optional[bool] = None,
                 resume: bool = False,
                 wire_upload: Optional[bool] = None,
                 device_batches=None,
                 input_range: Optional[Tuple[int, int]] = None,
                 map=None):
        super().__init__()
        _wordcount_setup(self, blocks, mesh, n_reduce, chunk_bytes,
                         max_word_len, u_cap, aot, on_attempt, depth,
                         pipeline_stats, device_accumulate, sync_every,
                         mesh_shards, checkpoint_dir, checkpoint_every,
                         checkpoint_async, checkpoint_delta, resume,
                         wire_upload, device_batches, input_range, map)


def wordcount_streaming(
        blocks: Iterable[bytes], mesh: Mesh | None = None,
        n_reduce: int = 10, chunk_bytes: int = 1 << 20,
        max_word_len: int = 16, u_cap: int = 1 << 12,
        aot: bool = False, on_attempt=None,
        depth: Optional[int] = None,
        pipeline_stats: Optional[dict] = None,
        device_accumulate: bool = False,
        sync_every: Optional[int] = None,
        mesh_shards: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_async: Optional[bool] = None,
        checkpoint_delta: Optional[bool] = None,
        resume: bool = False,
        wire_upload: Optional[bool] = None,
        input_range: Optional[Tuple[int, int]] = None,
) -> Optional[Dict[str, Tuple[int, int]]]:
    """Exact whole-stream word counts with bounded memory, pipelined.

    Returns ``{word: (count, reduce_partition)}``, or None when the stream
    needs the host path (non-ASCII bytes, or a word longer than the device
    limit).  Every step reuses one compiled program per capacity rung; a
    step whose uniques overflow retries itself at a wider capacity without
    disturbing the accumulator (rows are merged only after a step is
    confirmed exact), and the widened capacity — like a widened word
    window — sticks for every later step.

    ``depth`` (default ``DSI_STREAM_PIPELINE_DEPTH``, 2) is the in-flight
    step window.  At ``depth > 1`` a background batcher thread slices
    blocks into a bounded queue while the main thread uploads and
    dispatches ahead without synchronizing; each step's exactness flags
    are checked only when it leaves the window (``depth - 1`` steps
    late), and a failed check replays exactly that step through the
    shared ladder — results are bit-identical to ``depth=1`` because the
    accumulator's inputs (the confirmed per-step tables) are identical.
    ``depth=1`` is fully synchronous: no thread, dispatch then check.

    ``pipeline_stats``, if given, is a dict populated with per-phase wall
    seconds (``batch_s`` build time in the batcher, ``batch_wait_s`` main-
    thread starvation, ``upload_s``, ``kernel_s`` time blocked on step
    flags (not device time), ``pull_s`` and its two parts
    ``device_wait_s`` (blocked until the device has produced the step's
    packed result, which stands right behind the step on its queue) and
    ``d2h_s`` (the copy), ``merge_s``, ``replay_s``,
    ``finalize_s`` the final merge into the result) plus ``depth``,
    ``steps``, ``replays``, ``max_inflight_chunks`` (peak device chunk
    buffers — bounded by ``depth``) and ``batch_allocs`` (host batch
    buffers ever allocated — O(depth), not O(steps), thanks to the pool).

    ``on_attempt(max_word_len, u_cap)``, if given, is called before every
    kernel attempt — observability for the retry ladder (the driver's
    dryrun uses it to evidence that a capacity retry actually ran).

    ``aot=True`` compiles both step and pack programs explicitly
    (``backends/aotcache.py``) and pulls FULL-capacity packed tables (one
    deterministic shape per rung, so ``warm_stream_aot`` can pre-compile
    everything) instead of pow2 prefixes — fewer programs for more
    pulled bytes.  Every path packs a step's table when the step is
    dispatched; the host-merge path packs the sticky prefix
    (``pipeline_stats`` ``pulls_early``: pulls that tensor served;
    ``pulls_late``: a step that outgrew it, or a replay's payload,
    packed at retirement; their sum is ``step_pulls``).

    ``device_accumulate=True`` folds each confirmed step's reduce output
    into a persistent on-device merge table (``device/table.py``) instead
    of pulling + host-merging it; the host sees the merged table only
    every ``sync_every`` folds (default ``DSI_STREAM_SYNC_EVERY``, 8) and
    at stream end.  Results are bit-identical to the host-merge path —
    folds consume exactly the confirmed per-step tables the host merge
    would, replays fold their replayed exact output, and table-capacity
    overflow widens (drain + realloc + re-fold) rather than dropping
    keys.  ``pipeline_stats`` gains ``folds``/``fold_overflows``/
    ``sync_pulls``/``widens``/``table_cap`` counters and ``fold_s``/
    ``sync_s``/``widen_s`` phases; ``step_pulls`` counts per-step D2H
    result pulls in BOTH modes, so a bench can show the amortization
    (steps vs ``ceil(steps/K) + widens``) directly.

    ``mesh_shards`` (default ``DSI_STREAM_MESH_SHARDS``, 0 = off) makes
    the device table MESH-SHARDED (``device/table.py`` module docs): the
    fold program routes every key to shard ``ihash(key) % mesh_shards``
    with an in-program all-to-all before the merge, so each shard holds
    the complete pre-merged state of its hash range, the widen protocol
    goes per-shard (``shard_widens`` — a hot shard drains, reallocs and
    re-folds alone), and sync pulls one hash-balanced pre-merged table
    (``pull_bytes``/``shard_imbalance`` counters).  Implies
    ``device_accumulate``; results stay bit-identical to the
    host-merge path.

    ``checkpoint_dir`` enables crash-resume (``dsi_tpu/ckpt``): every
    ``checkpoint_every`` CONFIRMED steps (``DSI_STREAM_CKPT_EVERY``
    default) the engine writes a durable snapshot — host accumulator,
    a drain-free image of the device table (if live), the sticky rung
    state, and the input-byte cursor of the last confirmed step
    (in-flight/deferred-check steps are excluded, so replay stays
    exactly-once).  ``resume=True`` restores the newest valid
    checkpoint, seeks the block stream to the cursor, and continues;
    the final result is bit-identical to an uninterrupted run.
    ``pipeline_stats`` gains ``ckpt_saves``/``ckpt_s`` and, on resume,
    ``resume_gap_s``/``resume_cursor``.

    ``checkpoint_async`` (default ``DSI_STREAM_CKPT_ASYNC``, off) splits
    each save into capture (at the boundary: dispatch the image pulls,
    snapshot the host accumulators by reference) and commit (a
    background writer waits on the in-flight pulls, serializes, and
    runs the durable-write path) so steps keep flowing while the
    snapshot drains — the engine blocks only when the NEXT save finds
    the previous commit still draining (``ckpt_barrier_s``).
    ``checkpoint_delta`` (default ``DSI_STREAM_CKPT_DELTA``, off) makes
    saves INCREMENTAL: a delta ships only the confirmed step payloads
    appended since the previous save (the store chains ``delta-<seq>``
    manifests; restore = base + ordered deltas re-ingested through the
    host drain path) with a full re-base every
    ``DSI_STREAM_CKPT_REBASE`` saves.  Both default off = bit-identical
    PR-5 behavior; resume parity is unchanged either way.
    ``pipeline_stats`` gains ``ckpt_capture_s``/``ckpt_commit_s``/
    ``ckpt_barrier_s`` and ``ckpt_deltas``/``ckpt_full_bytes``/
    ``ckpt_delta_bytes``.

    ``wire_upload`` (default ``DSI_STREAM_WIRE``, off) compresses each
    chunk upload host-side (``ops/wirecodec.py``: per-batch
    dictionary-nibble code, 7-bit ASCII fallback) and decodes it ON
    DEVICE with a tiny compiled prologue before the step program, so
    PCIe moves 0.63-0.88x the bytes while HBM sees the
    exact same chunk tensors — results are bit-identical with the knob
    on or off (a batch the codec cannot shrink ships raw;
    ``wire_raw_steps`` counts those).  ``pipeline_stats`` gains
    ``wire_steps``/``wire_raw_steps``/``wire_packed_bytes``/
    ``wire_ratio`` and the ``decode_s`` phase (host encode +
    decode-prologue dispatch).

    A block source with an ``ingest_stats()`` hook — the parallel
    mmap reader pool, ``utils/ioread.py`` — additionally reports
    ``ingest_readers``/``ingest_blocks``/``readahead_hit_pct``/
    ``ingest_wait_s`` in ``pipeline_stats``.
    """
    return WordcountStep(
        blocks, mesh=mesh, n_reduce=n_reduce, chunk_bytes=chunk_bytes,
        max_word_len=max_word_len, u_cap=u_cap, aot=aot,
        on_attempt=on_attempt, depth=depth,
        pipeline_stats=pipeline_stats,
        device_accumulate=device_accumulate, sync_every=sync_every,
        mesh_shards=mesh_shards, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        checkpoint_async=checkpoint_async,
        checkpoint_delta=checkpoint_delta, resume=resume,
        wire_upload=wire_upload, input_range=input_range).close()


def _wordcount_setup(step, blocks, mesh, n_reduce, chunk_bytes,
                     max_word_len, u_cap, aot, on_attempt, depth,
                     pipeline_stats, device_accumulate, sync_every,
                     mesh_shards, checkpoint_dir, checkpoint_every,
                     checkpoint_async, checkpoint_delta, resume,
                     wire_upload=None, device_batches=None,
                     input_range=None, map=None):
    """The engine body behind :class:`WordcountStep`: full setup
    (``resume=True`` chain restore included) ending with the pipeline
    armed and the lifecycle hooks attached to ``step``."""
    if map is not None:
        refused = [name for name, on in (
            ("aot", aot), ("device_accumulate", device_accumulate),
            ("mesh_shards", mesh_shards_default(mesh_shards)),
            ("checkpoint_dir", checkpoint_dir),
            ("wire_upload", wirecodec.wire_upload_default(wire_upload)),
            ("device_batches", device_batches is not None)) if on]
        if refused:
            raise ValueError(f"a map ({map}) runs on the host-merge path "
                             f"alone: not with {', '.join(refused)}")
    if device_batches is not None and checkpoint_dir:
        raise ValueError("device_batches and checkpoint_dir are "
                         "exclusive: chained stages commit at stage "
                         "boundaries (dsi_tpu/plan), not byte cursors")
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    depth = pipeline_depth(depth)
    # Sticky dispatch rung: starts where the sync ladder would, and only
    # ever moves toward more headroom (run_step_sync records the rung
    # that cleared) — cap and word window widen, and frac follows the
    # last cleared rung so a stream that consistently token-overflows
    # the optimistic frac (dense 1-letter words) doesn't replay every
    # step forever.
    fracs = (4, 2) if map is None else map.fracs
    state = {"cap": rung0_cap(chunk_bytes, u_cap), "mwl": max_word_len,
             "frac": fracs[0]}
    # Sticky pull prefix: the rows of a step's table the host-merge path
    # packs when the step is DISPATCHED, before anyone knows the step's
    # own occupied count.  Predicted like the rung: it starts at the
    # start rung's capacity and every retired step raises it to its own
    # ``occupied_prefix``; it never falls within a job, so the pack
    # shapes a corpus reaches are the ones its first job compiled.
    state["mp"] = state["cap"]
    sharding = NamedSharding(mesh, PartitionSpec(AXIS, None))
    # The engine's stats dict IS a registry scope (dsi_tpu/obs): the
    # same keys as ever, readable by any consumer as the one documented
    # schema — stream_phases is a view over this, not a fifth dialect.
    stats = metrics_scope("stream")
    stats.update({"depth": depth, "steps": 0, "replays": 0,
                  "max_inflight_chunks": 0, "donate_chunks": True,
                  "step_pulls": 0, "pulls_early": 0, "pulls_late": 0,
                  "device_accumulate": device_accumulate,
                  "device_rows": [0] * n_dev,
                  "batch_s": 0.0, "batch_wait_s": 0.0, "upload_s": 0.0,
                  "kernel_s": 0.0, "pull_s": 0.0, "device_wait_s": 0.0,
                  "d2h_s": 0.0, "merge_s": 0.0, "replay_s": 0.0,
                  "finalize_s": 0.0})
    # The accumulator's compactions and counts land in the same scope.
    acc = PackedCounts(stats=stats,
                       decimals=0 if map is None else map.decimals)
    if map is not None:
        stats.update({"agg_rows": 0, "agg_groups": 0,
                      "agg_value_lanes": map.value_lanes})
    # Compressed chunk uploads (ops/wirecodec.py): encode host-side,
    # ship the packed tensor, decode on device as a map prologue.  Off
    # by default = bit-identical raw uploads; on, a batch the codec
    # cannot shrink still ships raw — the knob only ever changes what
    # crosses the wire, never what HBM (and therefore the result) sees.
    # Device-batch input has no wire to compress (nothing is uploaded).
    wire = (wirecodec.wire_upload_default(wire_upload)
            if device_batches is None else False)
    # Device-resident batches replay from the SAME buffer on a
    # late-detected overflow, so their step programs must not consume
    # it — donation is a host-upload optimization only.
    donate_steps = device_batches is None
    stats["donate_chunks"] = donate_steps
    wire_raw_total = [0]  # raw-equivalent bytes of the packed uploads
    if wire:
        stats.update({"wire_upload": True, "wire_steps": 0,
                      "wire_raw_steps": 0, "wire_packed_bytes": 0,
                      "decode_s": 0.0})
    # Device-resident accumulation: confirmed steps fold on-device, the
    # host pulls every K folds.  The table allocates lazily at the first
    # fold (its key width and capacity come from that step's shapes); the
    # fold-flag lag is the pipeline window, so confirming a fold never
    # blocks on kernels the window still wants in flight.
    mesh_shards = mesh_shards_default(mesh_shards)
    if mesh_shards:
        device_accumulate = True  # the services ARE the sharded state
        stats["device_accumulate"] = True
    table_svc: Optional[DeviceTable] = None
    policy: Optional[SyncPolicy] = None
    if device_accumulate:
        policy = SyncPolicy(sync_every)
        stats["sync_every"] = policy.sync_every
        stats["mesh_shards"] = mesh_shards

    # ── checkpoint/restore (dsi_tpu/ckpt) ──
    ck_store: Optional[CheckpointStore] = None
    ck_policy: Optional[CheckpointPolicy] = None
    ck_writer: Optional[CheckpointWriter] = None
    ck_cursor = {"offset": 0, "steps": 0}  # last CONFIRMED step's end
    offsets: Optional[list] = None
    dispatch_idx = [0]
    start_offset = 0
    ck_async = checkpoint_async_default(checkpoint_async)
    ck_delta = checkpoint_delta_default(checkpoint_delta)
    host_delta = HostDeltaLog()  # non-dacc delta log: trimmed copies of
    # the pulled (packed, nus) steps, bounded like the device logs
    if checkpoint_dir:
        # ``input_range`` (the shard scheduler's cursor range,
        # mr/shards.py) is part of the chain identity: a chain written
        # while driving shard [a, b) must refuse to restore into an
        # attempt driving any other range — cursors are range-relative,
        # so a cross-range restore would silently misalign the stream.
        ident = {"n_dev": n_dev, "n_reduce": n_reduce,
                 "chunk_bytes": chunk_bytes,
                 "device_accumulate": bool(device_accumulate)}
        if input_range is not None:
            ident["input_range"] = [int(input_range[0]),
                                    int(input_range[1])]
        ck_store = CheckpointStore(checkpoint_dir, "wordcount", ident)
        ck_policy = CheckpointPolicy(checkpoint_every)
        offsets = []
        stats.update({"ckpt_saves": 0, "ckpt_s": 0.0,
                      "ckpt_every": ck_policy.every,
                      "ckpt_capture_s": 0.0,
                      "ckpt_async": ck_async, "ckpt_delta": ck_delta})
        ck_writer = CheckpointWriter(ck_store, stats, async_=ck_async,
                                     delta=ck_delta)
        if resume:
            t_res = time.perf_counter()
            loaded = ck_store.load_latest_chain()
            if loaded is not None:
                meta, arrays, deltas = loaded
                # Cursor/rung state is newest-wins: the final delta's
                # meta IS the restore point; the base meta only names
                # the image shape.
                eff = deltas[-1][0] if deltas else meta
                start_offset = int(eff["cursor"])
                ck_cursor.update(offset=start_offset,
                                 steps=int(eff["steps"]))
                state.update({"cap": int(eff["cap"]),
                              "mwl": int(eff["mwl"]),
                              "frac": int(eff["frac"]),
                              "mp": int(eff["cap"])})
                acc.restore({k[4:]: v for k, v in arrays.items()
                             if k.startswith("acc_")})
                if device_accumulate and meta.get("table_cap"):
                    img = {k[6:]: v for k, v in arrays.items()
                           if k.startswith("table_")}
                    same_degree = (int(meta.get("mesh_shards", 0))
                                   == mesh_shards)
                    if deltas or not same_degree:
                        # Chain restore (and the sharding-degree change)
                        # re-enters through the DRAIN path: the image's
                        # merged rows flow into the host accumulator,
                        # the table starts empty, and the resumed folds
                        # rebuild device state.  base + ordered deltas
                        # is content-exact, so the final output stays
                        # bit-identical.
                        DeviceTable.drain_image(acc, img)
                        if not same_degree:
                            stats["resharded_resume"] = int(
                                meta.get("mesh_shards", 0))
                    else:
                        # Re-enter device_accumulate mid-table: the
                        # image's capacity/width win (a pre-crash widen
                        # sticks).
                        table_svc = DeviceTable(
                            mesh, kk=int(meta["table_kk"]),
                            cap=int(meta["table_cap"]), acc=acc, aot=aot,
                            lag=max(0, depth - 1), stats=stats,
                            mesh_shards=mesh_shards)
                        table_svc.restore_state(img)
                        if ck_delta:
                            table_svc.enable_delta()
                    policy.restore(eff.get("sync_since", 0))
                for _, darr in deltas:
                    # Each delta's retained step payloads re-enter the
                    # host accumulator in save order — the same
                    # drain-path argument as the cross-degree resume.
                    drain_packed_steps(acc, darr)
                if aot:
                    # Re-warm the sticky-rung executables now (compile-
                    # cache hits), so the first resumed step dispatches
                    # instead of compiling — the cost lands in
                    # resume_gap_s where it belongs.
                    chunks_sds, rows, pack_args = _stream_examples(
                        n_dev, chunk_bytes, state["cap"], state["mwl"])
                    _aot_step_fn(chunks_sds, n_dev=n_dev,
                                 n_reduce=n_reduce,
                                 max_word_len=state["mwl"],
                                 u_cap=state["cap"], mesh=mesh,
                                 t_cap_frac=state["frac"])
                    _aot_pack_fn(pack_args, mp=rows)
            stats["resume_gap_s"] = round(time.perf_counter() - t_res, 4)
            stats["resume_cursor"] = start_offset
        else:
            ck_store.reset()  # fresh lineage: stale checkpoints must
            # never be resumable into a run that diverged from them

    def fold_confirmed(packed_dev, scal_dev, scal_np) -> None:
        nonlocal table_svc
        if int(scal_np[:, 0].max()) == 0:
            return  # empty step: nothing to fold, nothing to sync for
        if table_svc is None:
            # Rung-0 table capacity: the step's row count (a single fold
            # can never overflow it), unless DSI_DEVICE_TABLE_CAP asks
            # for a smaller start — an HBM lever for low-vocabulary
            # streams (the widen protocol recovers if the guess is
            # wrong), and the test hook that forces mid-stream widens.
            try:
                cap = int(os.environ.get("DSI_DEVICE_TABLE_CAP", "0"))
            except ValueError:
                cap = 0
            table_svc = DeviceTable(
                mesh, kk=int(packed_dev.shape[2]) - 3,
                cap=cap if cap > 0 else int(packed_dev.shape[1]),
                acc=acc, aot=aot, lag=max(0, depth - 1), stats=stats,
                mesh_shards=mesh_shards)
            if ck_delta and ck_store is not None:
                table_svc.enable_delta()
        table_svc.fold(packed_dev, scal_dev, scal_np)
        policy.note_fold()
        if policy.due():
            fault_point("pre-sync")
            table_svc.sync()
            policy.reset()

    def note_rows(rows_per_dev) -> None:
        """Reduce-output rows each device of the mesh produced, summed
        over confirmed steps: evidence that every device held a shard of
        the shuffle, not just that the total came out right."""
        stats["device_rows"] = [a + int(b) for a, b in
                                zip(stats["device_rows"], rows_per_dev)]

    def save_ckpt() -> None:
        """One consistent snapshot at a confirmed-step boundary —
        capture here, commit inline (sync) or in the background writer
        (async; ``ckpt/writer.py``).  The device table is captured
        FIRST: flushing its lagged flags can trigger a widen whose
        drain lands in the host accumulator, and the snapshot must hold
        both sides of that move.  Everything in the in-flight window is
        deliberately absent — those steps were never merged, and resume
        re-processes them from the cursor.  A delta save ships only the
        step payloads retained since the previous save (device log in
        dacc mode, the already-pulled host payloads otherwise); every
        ``DSI_STREAM_CKPT_REBASE``-th save is a full re-base (an
        invalid delta window forces one)."""
        with _span("ckpt", stats=stats, key="ckpt_s",
                   step=ck_cursor["steps"]):
            meta = {"cursor": ck_cursor["offset"],
                    "steps": ck_cursor["steps"],
                    "cap": state["cap"], "mwl": state["mwl"],
                    "frac": state["frac"]}
            kind = "full"
            parts = None
            with _span("ckpt_capture", lane="ckpt", stats=stats,
                       key="ckpt_capture_s"):
                if ck_writer.want_delta():
                    if device_accumulate:
                        entries = (table_svc.take_delta()
                                   if table_svc is not None else [])
                    else:
                        entries = host_delta.take()
                    if entries is not None:
                        parts = [("", DeltaSteps(entries))]
                        kind = "delta"
                        if device_accumulate:
                            meta["mesh_shards"] = mesh_shards
                            meta["sync_since"] = policy.snapshot()
                if parts is None:
                    # Full image — the PR-5 arrays, and a fresh delta
                    # window: payloads recorded before this base are in
                    # the image, so both logs reset here.
                    parts = []
                    if table_svc is not None:
                        parts.append(("table_",
                                      table_svc.checkpoint_capture()))
                        meta["table_cap"] = table_svc.cap
                        meta["table_kk"] = table_svc.kk
                        # The manifest records the image's sharding
                        # degree so a resume onto a different mesh
                        # degree re-shuffles via the drain path instead
                        # of misreading shard ownership.
                        meta["mesh_shards"] = table_svc.mesh_shards
                        meta["sync_since"] = policy.snapshot()
                        if ck_delta:
                            table_svc.take_delta()
                    host_delta.reset()
                    parts.append(("acc_", acc.snapshot()))
            fault_point("mid-capture")
            ck_writer.commit(parts, meta, kind=kind)
    # Live host buffers = out queue (≤ depth+1) + in-flight window
    # (≤ depth) + one being filled + one being finished.
    pool = BufferPool((n_dev, chunk_bytes), retain=2 * depth + 3)

    def step_call(chunks_dev, mwl, cap, frac):
        kw = dict(n_dev=n_dev, n_reduce=n_reduce, max_word_len=mwl,
                  u_cap=cap, mesh=mesh, t_cap_frac=frac)
        if map is not None:
            kw["map"] = map
        with _quiet_unusable_donation():  # first call per rung compiles
            if aot:
                return _aot_step_fn(chunks_dev, donate=donate_steps,
                                    **kw)(chunks_dev)
            if donate_steps:
                return mapreduce_step_donate(chunks_dev, **kw)
            return mapreduce_step(chunks_dev, **kw)

    def to_host(pack, in_pull: bool):
        """One packed step result on the host, in two parts that one
        ``np.asarray`` would lump: ``wait`` until the device has
        produced it (the step program and its pack: device time the
        window did not hide), then ``d2h``, the copy itself.  ``pack``
        gives the device tensor (a pack not dispatched yet is
        dispatched inside the wait).  On the per-step pull path
        (``in_pull``) the two are also phase keys, the parts of
        ``pull_s``; a replay's pull stays inside ``replay_s``."""
        sink = stats if in_pull else None
        with _span("wait", lane="pull", stats=sink, key="device_wait_s"):
            dev = jax.block_until_ready(pack())
        with _span("d2h", lane="pull", stats=sink, bytes=dev.nbytes):
            packed = np.asarray(dev)
        stats["pull_bytes"] = stats.get("pull_bytes", 0) + packed.nbytes
        return packed

    def pull_packed(keys, lens, cnts, parts, scal_np, in_pull=False):
        """One packed host tensor per step (the single-pull D2H shape,
        shuffle._slice_pack) + per-device occupied counts + key width,
        from a pack enqueued NOW: the late pull, a replay's payload or a
        step whose table outgrew the prefix packed at its dispatch.  The
        prefix is the step's own pow2 occupied prefix, which the sticky
        one rises to; under aot it is the full capacity —
        deterministic shapes beat pull volume there (see the aot note
        in the docstring)."""
        m = int(scal_np[:, 0].max())
        if m == 0:
            return None, None, 0
        kk = keys.shape[2]
        stats["pulls_late"] += 1
        if aot:
            packed = to_host(lambda: _aot_pack(
                keys, lens, cnts, parts, mp=keys.shape[1]), in_pull)
        else:
            mp = occupied_prefix(m, keys.shape[1])
            state["mp"] = max(state["mp"], mp)
            packed = to_host(lambda: _slice_pack(
                keys, lens, cnts, parts, mp=mp), in_pull)
        return packed, scal_np[:, 0], kk

    def run_step_sync(chunks_np, device_payload: bool = False):
        """The full exactness ladder for ONE batch — the replay path of a
        deferred-check failure, and the semantics ``depth=1`` reduces to.
        Each attempt re-uploads (the step program donates its input, so a
        device buffer never survives an attempt).  With
        ``device_payload`` the payload returns the cleared attempt's
        DEVICE handles (full-capacity packed tensor + scalars) instead of
        pulling — the replayed step then folds its exact output into the
        device table like any confirmed step."""

        def run(mwl: int, cap: int):
            state["cap"] = cap    # last attempt = the one that succeeded
            state["mwl"] = mwl    # (sticky for later optimistic dispatches)
            if on_attempt is not None:
                on_attempt(mwl, cap)
            for frac in fracs:
                chunks = jax.device_put(chunks_np, sharding)
                keys, lens, cnts, parts, scal = step_call(
                    chunks, mwl, cap, frac)
                scal_np = np.asarray(scal)
                if not scal_np[:, 4].any():
                    break
            state["frac"] = frac  # cleared rung sticks
            if map is not None and scal_np[:, 3].any():
                raise map.bad_row(scal_np, stats["agg_rows"])

            def payload():
                if device_payload:
                    mp = keys.shape[1]
                    packed_dev = (
                        _aot_pack(keys, lens, cnts, parts, mp=mp) if aot
                        else _slice_pack(keys, lens, cnts, parts, mp=mp))
                    return packed_dev, scal, scal_np
                return pull_packed(keys, lens, cnts, parts, scal_np)

            return (bool(scal_np[:, 3].any()), int(scal_np[:, 1].max()),
                    int(scal_np[:, 2].max()), payload)

        return exactness_retry(run, chunk_bytes, state["mwl"], state["cap"])

    def dispatch(buf: np.ndarray):
        """Optimistically launch one step at the sticky rung — upload +
        async kernel dispatch, no synchronization.  The step's pack
        program is dispatched HERE too, directly behind its step: on an
        in-order device stream a pack dispatched at finish time would
        queue behind the NEXT step's kernel, serializing exactly what
        the window exists to overlap — and misattributing that kernel's
        wall to pull_s.  Its shape needs no flags: the full capacity
        under aot, device accumulation and a map, the sticky prefix
        (``state["mp"]``) on the host-merge path."""
        mwl, cap = state["mwl"], state["cap"]
        if on_attempt is not None:
            on_attempt(mwl, cap)
        chunks = None
        if not isinstance(buf, np.ndarray):
            # Device-resident handoff (dsi_tpu/plan): the upstream
            # stage's output IS this step's upload — the batch is
            # already a sharded jax.Array, so nothing crosses the host.
            chunks = buf
        if wire:
            # Host-side encode + packed upload + on-device decode
            # prologue.  The decode output feeds the step exactly where
            # the raw upload would — same tensors in HBM, so depth/
            # dacc/mesh parity is bit-identical by construction.
            with _span("decode", lane="upload", stats=stats,
                       key="decode_s", step=stats["steps"]):
                enc = wirecodec.encode_chunk(buf)
            if enc is None:
                stats["wire_raw_steps"] += 1
            else:
                mode, packed_np, wire_lit = enc
                with _span("upload", stats=stats, key="upload_s",
                           step=stats["steps"]):
                    packed_dev = jax.device_put(packed_np, sharding)
                with _span("decode", lane="upload", stats=stats,
                           key="decode_s", step=stats["steps"]):
                    chunks = wirecodec.decode_chunk_device(
                        packed_dev, n=chunk_bytes, lit_cap=wire_lit,
                        mode=mode, aot=aot)
                del packed_dev  # frees as soon as the prologue consumes it
                stats["wire_steps"] += 1
                stats["wire_packed_bytes"] += int(packed_np.nbytes)
                wire_raw_total[0] += n_dev * chunk_bytes
                stats["wire_ratio"] = round(
                    wire_raw_total[0] / stats["wire_packed_bytes"], 3)
        if chunks is None:
            with _span("upload", stats=stats, key="upload_s",
                       step=stats["steps"]):
                chunks = jax.device_put(buf, sharding)
        # What dispatch costs beside its upload: the call of the step
        # program (and of the eager pack), each of which returns before
        # the device has run it.
        with _span("enqueue", lane="dispatch", stats=stats,
                   step=stats["steps"], program="mapreduce_step"):
            keys, lens, cnts, parts, scal = step_call(
                chunks, mwl, cap, state["frac"])
            rows = keys.shape[1]
            if aot or device_accumulate or map is not None:
                # Only scal + the packed tensor stay referenced: the four
                # result tables free as soon as the pack consumes them, so
                # an in-flight step holds one packed copy, not five
                # tables.  The fold consumes the packed layout at its
                # full-capacity shape; a step with a map settles on a
                # table of half a MiB (PERF.md, PR 49).
                mp, tables = rows, None
            else:
                # Host merge: the predicted prefix.  The tables stay with
                # the record until retirement, for the step whose own
                # count turns out to lie past it (a late pack, once).
                mp, tables = min(state["mp"], rows), (keys, lens, cnts,
                                                      parts)
            packed_dev = (_aot_pack(keys, lens, cnts, parts, mp=mp) if aot
                          else _slice_pack(keys, lens, cnts, parts, mp=mp))
            _enqueued(packed_dev)  # the step's program, and its pack's
            handles = (scal, packed_dev, keys.shape[2], tables)
        stats["steps"] += 1
        rec_offset = 0
        if offsets is not None:
            # Cursor of THIS step: absolute stream offset just past its
            # batch's content (offsets[i] is appended before batch i is
            # queued, so it is always present here).
            rec_offset = start_offset + offsets[dispatch_idx[0]]
            dispatch_idx[0] += 1
        fault_point("post-dispatch")
        return (buf, mwl, cap, rec_offset, handles)

    def finish_one(record) -> None:
        """Retire the oldest in-flight step: deferred exactness check,
        then merge (clean) or replay-at-wider-shape (overflow)."""
        buf, mwl, cap, rec_offset, (scal, packed_dev, kk, tables) = record
        with _span("kernel", stats=stats, key="kernel_s"):
            scal_np = np.asarray(scal)  # blocks until the kernel lands
        if scal_np[:, 3].any():      # non-ASCII: the whole stream is host's
            pool.give(buf)
            if map is not None:      # a row that cannot be read: no one's
                raise map.bad_row(scal_np, stats["agg_rows"])
            raise _NeedsHostPath
        exact = (not scal_np[:, 4].any()
                 and int(scal_np[:, 1].max()) <= cap
                 and int(scal_np[:, 2].max()) <= mwl)
        if exact:
            note_rows(scal_np[:, 0])
            if device_accumulate:
                # Fold instead of pull+merge: the confirmed step's packed
                # output stays on device; the host sees it at the next
                # sync.  This is the lagged-confirmation invariant — a
                # fold happens only HERE, after the exactness flags of
                # its step cleared.
                fold_confirmed(packed_dev, scal, scal_np)
            else:
                with _span("pull", stats=stats, key="pull_s") as sp:
                    m = int(scal_np[:, 0].max())
                    early = m <= packed_dev.shape[1]
                    if m == 0:
                        packed, nus = None, None
                    elif early:
                        # The tensor packed at dispatch holds the step's
                        # rows (those past nus[d] are padding, cut by
                        # the merge): the device made it right behind
                        # the step, so the wait is the step's own.
                        packed = to_host(lambda: packed_dev, True)
                        nus = scal_np[:, 0]
                        stats["pulls_early"] += 1
                    else:  # outgrew the predicted prefix: pack it now
                        packed, nus, kk = pull_packed(*tables, scal_np,
                                                      in_pull=True)
                    if packed is not None:
                        stats["step_pulls"] += 1
                        sp.set(early=early)
                with _span("merge", stats=stats, key="merge_s"):
                    if packed is not None:
                        acc.add_packed_step(packed, nus, kk)
                        if ck_delta and ck_store is not None:
                            # Host-merge delta log: the step's payload,
                            # trimmed+copied (an AOT pull is capacity-
                            # shaped) and window-bounded.
                            host_delta.append(packed, nus)
        else:
            # Late-detected overflow: replay just this step through the
            # ladder.  Exactly-once by construction — the optimistic
            # attempt's tables are dropped unmerged, and the replay's
            # payload merges (or folds) here and nowhere else.
            stats["replays"] += 1
            with _span("replay", stats=stats, key="replay_s"):
                payload = run_step_sync(buf,
                                        device_payload=device_accumulate)
                if payload is None:
                    pool.give(buf)
                    raise _NeedsHostPath
                if device_accumulate:
                    packed_dev, scal_dev, scal_np = payload()
                    note_rows(scal_np[:, 0])
                    fold_confirmed(packed_dev, scal_dev, scal_np)
                else:
                    packed, nus, kk = payload()
                    if packed is not None:
                        note_rows(nus)
                        stats["step_pulls"] += 1
                        acc.add_packed_step(packed, nus, kk)
                        if ck_delta and ck_store is not None:
                            host_delta.append(packed, nus)
        # This step is now CONFIRMED: its output is merged/folded and
        # nothing after it is.  The fault point sits BEFORE the cursor
        # advances — the classic torn-update instant.
        if map is not None:  # a replay reads the same rows: counted once
            stats["agg_rows"] += int(scal_np[:, 5].sum())
        fault_point("mid-fold")
        if ck_store is not None:
            ck_cursor["offset"] = rec_offset
            ck_cursor["steps"] += 1
            ck_policy.note_step()
            if ck_policy.due():
                save_ckpt()
                ck_policy.reset()
        pool.give(buf)

    # ── the window itself: the shared dispatch/finish pipeline core,
    # armed for the step object's {advance, confirm, ...} lifecycle ──
    pipe = StepPipeline(depth=depth, dispatch=dispatch, finish=finish_one,
                        stats=stats, produce_key="batch_s",
                        wait_key="batch_wait_s",
                        inflight_key="max_inflight_chunks",
                        thread_name="dsi-stream-batcher", engine="stream")

    step._pipe = pipe
    step._cursor_ref = ck_cursor
    if device_batches is not None:
        pipe.begin(lambda: iter(device_batches))
    else:
        feed = skip_stream(blocks, start_offset) if start_offset else blocks
        batches = batch_stream if map is None else _row_batches
        pipe.begin(lambda: batches(feed, n_dev, chunk_bytes,
                                   pool=pool, offsets=offsets))
    step._host_excs = (_TokenTooLong, _NeedsHostPath)
    step._save = save_ckpt if ck_store is not None else None
    step._writer = ck_writer
    if resume:
        step._restore_info = {
            "resume_cursor": stats.get("resume_cursor", 0),
            "resume_gap_s": stats.get("resume_gap_s", 0.0)}

    def on_complete():
        # End-of-stream epilogue, exactly the monolithic function's
        # success path: final device drain, async-commit errors
        # surfaced, then the result.
        if table_svc is not None or ck_writer is not None:
            with _span("drain", lane="sync", stats=stats, key="drain_s"):
                if table_svc is not None:
                    fault_point("pre-sync")
                    table_svc.close()  # the "or at stream end" pull
                if ck_writer is not None:
                    # surface async commit errors; counters settle
                    # before the caller reads them
                    ck_writer.drain()
        with _span("finalize", lane="host", stats=stats) as sp:
            step.result = acc.finalize()
            sp.set(keys=len(step.result))
        if map is not None:
            stats["agg_groups"] = len(step.result)

    released = []

    def release():
        if released:  # idempotent: close() after a suspend/fail re-runs it
            return
        released.append(True)
        acc.close()  # a job that failed or fell back leaves no merger
        if ck_writer is not None:
            ck_writer.shutdown()
        fold_source_stats(stats, blocks)
        if pipeline_stats is not None:
            stats["batch_allocs"] = pool.allocs
            for k in ("batch_s", "batch_wait_s", "upload_s", "kernel_s",
                      "pull_s", "device_wait_s", "d2h_s", "merge_s",
                      "replay_s", "finalize_s", "fold_s", "sync_s",
                      "sync_wait_s", "widen_s", "ckpt_s", "ckpt_capture_s",
                      "ckpt_commit_s", "ckpt_barrier_s", "decode_s",
                      "ckpt_compress_s", "dispatch_s", "retire_s",
                      "enqueue_s", "drain_s", "compact_s",
                      "compact_caller_s", "finalize_decode_s"):
                if k in stats:
                    stats[k] = round(stats[k], 4)
            pipeline_stats.update(stats)

    step._on_complete = on_complete
    step._release = release
