"""Streaming grep / indexer engines on the shared pipeline core.

The grep and indexer apps (``apps/tpu_grep.py``, ``apps/tpu_indexer.py``
— the working realizations of the reference's ``mrapps/dgrep.go`` /
``mrapps/indexer.go`` intent) run per-file through the MR framework:
every file pays a full host round-trip, and no cross-step state lives on
device.  This module gives both workloads the treatment word count and
TF-IDF already got — engines that consume the shared dispatch/finish
pipeline core (``parallel/pipeline.py``) with the same contract those
engines honor bit-identically:

* a background producer feeds a bounded queue (``batch_lines``, which
  cuts each row out of the incoming block in place, and ``_wave_chunk``
  materialize off the critical path),
* a ``depth``-deep in-flight window of donated per-step uploads through
  ``aotcache.cached_compile(donate_argnums)``,
* per-step scalar checks DEFERRED until a step leaves the window.  The
  grep step is ONE compiled program per ``(n_dev, chunk_bytes, m, bins,
  k, emit)``: it keeps no per-line buffer (below), so no capacity can
  overflow and no step is ever replayed — the deferred check is the
  host/device line-count comparison that guards the global line
  numbers.  The indexer keeps the word-count engine's capacity ladders
  and their exactly-once replay at sticky rungs,
* cross-step state on device via ``dsi_tpu/device/``: grep folds
  per-line match-count histograms (:class:`DeviceHistogram`) and top-k
  match candidates (:class:`DeviceTopK`), the indexer appends postings
  (:class:`DevicePostings`) and folds per-word document-frequency rows
  into the same top-k table — all lagging the deferred-exactness window
  and syncing under ``SyncPolicy``, so host pulls drop from one-per-step
  to the K-fold cadence plus widens.

Grep semantics, stated exactly (the oracle below implements the same
rules byte-for-byte): the stream is '\\n'-delimited byte lines (a
trailing newline opens no final empty line); a line's match count is the
number of positions where the literal pattern's bytes occur (overlapping
occurrences count); the engine reports total lines / matched lines /
occurrences, a ``bins``-bucket per-line match-count histogram (bucket =
``min(occ, bins-1)``), and the top-k lines by occurrence count (ties to
the earlier line).  Per-(step, device) top-k candidate pruning on device
is EXACT: a line in the global top-k is necessarily in the top-k of its
own step and device under the same (count desc, line asc) order, so the
pruned candidate multiset always contains the global winners.

How the step counts (``_grep_step_device``): every per-line statistic is
read at the line-END positions of the dense ``[chunk_bytes]`` arrays and
never scattered into line slots.  With ``M`` the running sum of the
match flags, the line that ends at ``p`` holds ``M[p] - M[q]``
occurrences, ``q`` the newline before it, and because ``M`` is monotone
``M[q]`` is a running maximum of ``M`` over the newlines: one cumsum and
one cummax.  Histogram buckets and totals are masked sums over the line
ends; the top-k orders the line ends by (count desc, position asc),
which is (count desc, line asc), and reads the winners' line numbers
from the newline cumsum; the emit variant carries a line's count back
over its bytes with one reverse scan.  The cost depends on the chunk
size alone, not on line length or match density.

Indexer semantics: documents are processed in waves of ``n_dev`` (one
per device, ``plan_waves`` sizing), the posting step is the word-count
map prologue with a (tf ≡ 1, doc, part) payload — one posting row per
distinct word per document — shuffled to the partition owner exactly as
in ``parallel/shuffle.py``; the result is ``{word: (part, [doc ids in
wave order])}`` plus the top-k words by document frequency.  Posting
order is an invariant through every path (the per-wave pull path and the
``DevicePostings`` sticky-overflow recovery both preserve it).

Both engines return None only when the input needs the host path (a
non-literal pattern or a line wider than the chunk for grep; non-ASCII
bytes or >64-byte words for the indexer) — correctness never depends on
a kernel (``backends/tpu.py`` contract).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.ckpt import (
    CheckpointPolicy,
    CheckpointStore,
    CheckpointWriter,
    DeltaSteps,
    HostDeltaLog,
    checkpoint_async_default,
    checkpoint_delta_default,
    drain_packed_steps,
    drain_posting_steps,
    fault_point,
    skip_stream,
)
from dsi_tpu.device.policy import SyncPolicy, mesh_shards_default
from dsi_tpu.device.table import (DeviceTable, _copy_to_host_async, _pow2,
                                  _quiet_unusable_donation)
from dsi_tpu.device.topk import DeviceHistogram, DeviceTopK, KeyCounts
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.ops.grepk import is_literal_pattern
from dsi_tpu.ops.wordcount import (
    DOC_SEP,
    _PAD_KEY,
    _shift_left,
    compact_positions,
    rung0_cap,
)
from dsi_tpu.parallel.merge import PackedCounts, PostingsTable
from dsi_tpu.parallel.pipeline import (
    BufferPool,
    StepPipeline,
    fold_source_stats,
    pipeline_depth,
)
from dsi_tpu.parallel.stepobj import EngineStep
from dsi_tpu.parallel.shuffle import (
    AXIS,
    default_mesh,
    map_prologue,
    occupied_prefix,
    shuffle_rows,
)
from dsi_tpu.utils.jaxcompat import enable_x64, shard_map

#: Histogram buckets for per-line match counts: bucket b < bins-1 holds
#: lines with exactly b occurrences, the last bucket everything wider.
GREP_BINS = 8

#: Bench grep-row chunk shape (the STREAM_CHUNK_BYTES discipline).
GREP_CHUNK_BYTES = 1 << 21

#: jax.jit donate_argnums for the grep step program: the chunk upload is
#: consumed by the kernel (pattern/lens/bases survive — the pattern is
#: uploaded once per stream and reused every step).
_GREP_DONATE = (0,)

#: Default top-k candidate rows kept per stream/walk.
DEFAULT_TOPK = 16


class _LineTooLong(Exception):
    """A line wider than one chunk row: the stream needs the host path."""


def _topk_cap_env() -> int:
    """The ``DSI_DEVICE_TOPK_CAP`` override (0 = unset/malformed) — the
    HBM lever for the top-k candidate table's starting rung, and the
    test hook that forces the widen path mid-stream.  One parser for
    both engines, so the knob cannot be read differently."""
    try:
        return max(0, int(os.environ.get("DSI_DEVICE_TOPK_CAP", "0")))
    except ValueError:
        return 0


def _default_topk_cap(n_dev: int, k: int) -> int:
    """Rung-0 capacity for grep's candidate table: enough for ~hundreds
    of folds between widens at the default shapes, overridable by
    ``DSI_DEVICE_TOPK_CAP``."""
    return _topk_cap_env() or _pow2(max(1 << 14, n_dev * k))


# ── line batching ──────────────────────────────────────────────────────


def batch_lines(blocks: Iterable[bytes], n_dev: int, chunk_bytes: int,
                pool: Optional[BufferPool] = None,
                offsets: Optional[list] = None,
                stats: Optional[dict] = None,
                count_lines: bool = True):
    """Slice a byte-block stream into zero-padded ``[n_dev, chunk_bytes]``
    batches, cutting rows only at newline boundaries so no line straddles
    a row.  Yields ``(batch, lens, row_lines)`` — per-row valid byte
    counts and per-row line counts (the host side of the device's line
    accounting: newlines plus an unterminated tail line).

    A row is cut only while more than ``chunk_bytes`` are pending, or at
    the end of input, and then behind the last newline its first
    ``chunk_bytes`` hold.  The rows are cut where the block lies: the
    newline is found by a backward search (``rfind`` returns within a
    line) and the row's bytes go from the block into the batch, their
    only copy.  What a block leaves behind its last cut (under a row)
    waits in ``rem`` as the head of the next row: those bytes alone are
    copied twice, and ``stats["recopied_bytes"]`` counts them where the
    engine hands its ``stats``.

    With ``pool`` batches come from the engine's rotating buffer set;
    the consumer hands each batch back via ``pool.give`` once its step
    is confirmed.  A line wider than ``chunk_bytes`` raises
    :class:`_LineTooLong` — the stream is the host path's then.

    With ``offsets`` (the checkpoint cursor hook, the ``batch_stream``
    contract) the stream offset just past each yielded batch's content
    is appended, before the yield.

    ``count_lines=False`` is for a caller that throws ``row_lines`` away
    (``streaming._row_batches``): the counting pass is skipped and the
    counts stay zero.
    """
    rem = bytearray()  # never past chunk_bytes: linear for 1-byte blocks
    consumed = 0

    def new_batch() -> np.ndarray:
        if pool is not None:
            return pool.take()
        return np.zeros((n_dev, chunk_bytes), dtype=np.uint8)

    batch = new_batch()
    lens = np.zeros(n_dev, dtype=np.int32)
    row_lines = np.zeros(n_dev, dtype=np.int64)
    row = 0

    def take_rem(n: int) -> None:
        # the first n waiting bytes open the row: their second copy
        batch[row, :n] = np.frombuffer(rem, np.uint8, n)
        del rem[:n]
        if stats is not None:
            stats["recopied_bytes"] += n

    def end_row(n: int):
        nonlocal batch, lens, row_lines, row, consumed
        filled = batch[row, :n]
        batch[row, n:] = 0
        lens[row] = n
        if count_lines:
            row_lines[row] = (np.count_nonzero(filled == 10)
                              + (1 if filled[-1] != 10 else 0))
        consumed += n
        row += 1
        if row == n_dev:
            if offsets is not None:
                offsets.append(consumed)
            yield batch, lens, row_lines
            batch = new_batch()
            lens = np.zeros(n_dev, dtype=np.int32)
            row_lines = np.zeros(n_dev, dtype=np.int64)
            row = 0

    for block in blocks:
        if not hasattr(block, "rfind"):
            block = bytes(block)  # a memoryview: search what can
        pos, size = 0, len(block)
        while len(rem) + size - pos > chunk_bytes:
            head = len(rem)
            end = block.rfind(b"\n", pos, pos + chunk_bytes - head) + 1
            if end:  # cut AFTER the last newline that fits
                if head:
                    take_rem(head)
                n = head + end - pos
                batch[row, head:n] = np.frombuffer(block, np.uint8,
                                                   end - pos, pos)
                pos = end
            else:
                # The block's part of the room holds no newline: the
                # remainder (whole lines a block left) is searched on.
                n = rem.rfind(b"\n") + 1
                if not n:
                    raise _LineTooLong
                take_rem(n)
            yield from end_row(n)
        rem += memoryview(block)[pos:]
    if rem:  # the final tail: at most a row, it fits whole
        n = len(rem)
        take_rem(n)
        yield from end_row(n)
    if row:
        batch[row:] = 0  # recycled buffer: stale tail rows must not count
        if offsets is not None:
            offsets.append(consumed)
        yield batch, lens, row_lines
    elif pool is not None:
        pool.give(batch)


# ── the grep step program ──────────────────────────────────────────────


#: Row width of the step's two-stage top-k (below).
_TOPK_ROW = 1024


def _top_positions(vals, k: int):
    """``(values, positions)`` of the ``k`` largest of the non-negative
    ``vals``, equal values at the lower position first: exact, in two
    stages.  ``lax.top_k`` over ``[n]`` lowers to one stable sort of all
    ``n`` rows on the TPU (1.0 ms at 2^20); over a ``[n / 1024, 1024]``
    view it sorts rows of 1,024 (0.14 ms) and leaves ``k`` candidates a
    row, whose row-major order is still (value desc, position asc)
    within a row and position asc across rows, so a second ``top_k``
    over them keeps the tie rule.  A global winner is among the first
    ``k`` of its own row, so nothing is lost.  (My chip runs, PR 27.)"""
    n = vals.shape[0]
    rows = -(-n // _TOPK_ROW)
    # vals >= 0: the -1 padding loses to every real position
    grid = jnp.pad(vals, (0, rows * _TOPK_ROW - n), constant_values=-1) \
        .reshape(rows, _TOPK_ROW)
    row_val, row_col = lax.top_k(grid, min(k, _TOPK_ROW))
    row_pos = row_col + (jnp.arange(rows, dtype=jnp.int32)
                         * _TOPK_ROW)[:, None]
    top_val, top_at = lax.top_k(row_val.reshape(-1), k)
    return top_val, jnp.take(row_pos.reshape(-1), top_at)


def _grep_step_device(chunk, pat, dlen, base, *, bins: int, k: int,
                      emit: bool = False):
    """Per-device step body (runs under shard_map): literal match mask
    (``len(pattern)`` shifted compares, the ``ops/grepk.py`` idiom) →
    per-line occurrence counts (two scans differenced at the line ends,
    the module docs) → histogram, totals, and the top-k candidate rows
    in DeviceTable's
    packed (key lanes, len, count, part) layout with the GLOBAL line
    number (``base`` + local) as the kk=2 key.

    ``emit=True`` (the plan layer's stage handoff, ``dsi_tpu/plan``)
    additionally COMPACTS the matching lines' bytes to the front of a
    ``[n]`` output row (stable partition, zero tail) plus the kept byte
    count — the device-resident intermediate a downstream stage consumes
    without any host round-trip."""
    n = chunk.shape[-1]
    m = pat.shape[-1]
    chunk = chunk.reshape(-1)
    pat = pat.reshape(-1)
    dlen0 = dlen.reshape(())
    base0 = base.reshape(())

    with jax.named_scope("match"):
        match = jnp.ones(n, jnp.bool_)
        for j in range(m):  # static unroll over the (short) pattern
            match &= _shift_left(chunk, j) == pat[j]

    with jax.named_scope("line_ids"):
        pos = jnp.arange(n, dtype=jnp.int32)
        valid = pos < dlen0
        is_nl = (chunk == 10) & valid
        nl_i32 = is_nl.astype(jnp.int32)
        line_id = jnp.cumsum(nl_i32) - nl_i32  # newlines strictly before i
        nl_total = jnp.sum(nl_i32)
        last = jnp.where(dlen0 > 0, chunk[jnp.maximum(dlen0 - 1, 0)],
                         jnp.uint8(10))
        n_lines = nl_total + jnp.where((dlen0 > 0) & (last != 10), 1, 0)

    # Padding bytes are zeros and the pattern is printable ASCII, so a
    # match can neither start in nor extend into padding, nor sit on a
    # newline; occurrences therefore attribute to real lines only.
    #
    # Per-line counts live at the line ENDS of the dense [n] arrays: a
    # line ends at every valid newline and, where the last valid byte is
    # not one, at dlen - 1.  With M the running match count, the line
    # that ends at p holds M[p] - M[q] occurrences, q the newline before
    # it; M is monotone, so M[q] is the running maximum of M over the
    # newlines strictly before p.  Two scans, no per-line buffer, so no
    # line count can overflow anything.
    with jax.named_scope("line_occ"):
        is_end = is_nl | (pos == dlen0 - 1)
        run = jnp.cumsum(match.astype(jnp.int32))
        at_nl = jnp.where(is_nl, run, 0)
        prev = lax.cummax(jnp.pad(at_nl[:-1], (1, 0)))  # strictly before
        occ = jnp.where(is_end, run - prev, 0)
        matched = jnp.sum((occ > 0).astype(jnp.int32))
        occurrences = jnp.sum(occ)

    with jax.named_scope("hist"):
        bucket = jnp.where(is_end, jnp.minimum(occ, bins - 1), bins)
        hist = jnp.sum(
            bucket[None, :] == jnp.arange(bins, dtype=jnp.int32)[:, None],
            axis=1, dtype=jnp.uint32)
        hist_ext = jnp.concatenate(
            [hist,
             jnp.stack([n_lines, matched, occurrences]).astype(jnp.uint32)])

    # Top-k candidates among matched lines, (count desc, line asc): the
    # per-device pruning that keeps candidate folds k rows per step.
    # Line numbers rise with position, so the order over line ends is
    # (count desc, position asc), and the winners' line numbers are
    # read from ``line_id`` at their positions.
    with jax.named_scope("topk"):
        top_occ, top_pos = _top_positions(occ, k)
        top_lid = jnp.take(line_id, top_pos)
        n_cand = jnp.minimum(matched, k)
        cvalid = jnp.arange(k, dtype=jnp.int32) < n_cand
        with enable_x64(True):
            gline = base0 + top_lid.astype(jnp.uint64)
            hi = jnp.where(cvalid, (gline >> 32).astype(jnp.uint32),
                           jnp.uint32(0))
            lo = jnp.where(cvalid, gline.astype(jnp.uint32), jnp.uint32(0))
        cand = jnp.stack(
            [hi, lo,
             jnp.where(cvalid, jnp.uint32(8), jnp.uint32(0)),
             jnp.where(cvalid, top_occ.astype(jnp.uint32), jnp.uint32(0)),
             jnp.zeros(k, jnp.uint32)], axis=1)

    # Pin to int32: under the x64-scoped compile, literal-int promotion
    # would widen these to int64 and drift off the struct-warmed fold
    # program's [n_dev, 5] int32 contract (device/table._step_structs).
    # Lane 2 is reserved (every engine's row has five lanes; the fold
    # reads lane 0 only): always 0, read by nobody.
    scal = jnp.stack([n_cand, n_lines, jnp.int32(0),
                      matched, occurrences]).astype(jnp.int32)
    if not emit:
        return hist_ext[None], cand[None], scal[None]
    # Matching-line compaction: keep every byte whose line matched (the
    # terminating newline included — it is its own line's end).  A
    # byte's line ends at the first line end at or after it; M there is
    # the minimum of M over the line ends from the byte on (M is
    # monotone), one reverse scan, and ``prev`` is already M at the
    # newline before the byte.  Stable-partition kept bytes to the front
    # (sort by (dropped, position) — order-preserving), zero the tail.
    with jax.named_scope("emit"):
        big = jnp.int32(0x7FFFFFFF)
        at_end = lax.cummin(jnp.where(is_end, run, big), reverse=True)
        keep = valid & (at_end > prev)
        keep_inv = jnp.where(keep, jnp.int32(0), jnp.int32(1))
        _, _, comp = lax.sort((keep_inv, pos, chunk), num_keys=2)
        kept_n = jnp.sum(keep.astype(jnp.int32))
        comp = jnp.where(pos < kept_n, comp, 0)
    return (hist_ext[None], cand[None], scal[None], comp[None],
            kept_n.reshape(1))


def _grep_step_impl(chunks, pats, meta, *, bins: int, k: int,
                    mesh: Mesh, emit: bool = False):
    """``meta`` is the step's one small input, ``uint64[n_dev, 2]``: per
    row its valid byte count and its first line's global number
    (:func:`step_meta`).  One array and not two because a host-to-device
    put costs the host about a quarter of a millisecond per ARRAY
    whatever its size (PERF.md §6, PR 29)."""
    lens = meta[:, 0].astype(jnp.int32)
    bases = meta[:, 1]
    body = functools.partial(_grep_step_device, bins=bins, k=k, emit=emit)
    out_specs = (P(AXIS, None), P(AXIS, None, None), P(AXIS, None))
    if emit:
        out_specs += (P(AXIS, None), P(AXIS))
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P(AXIS), P(AXIS)),
        out_specs=out_specs,
    )(chunks, pats, lens, bases)


def step_meta(lens_np: np.ndarray, bases_np: np.ndarray) -> np.ndarray:
    """The grep step's ``meta`` input from per-row byte counts and line
    bases (host side of :func:`_grep_step_impl`)."""
    return np.stack([lens_np, bases_np], axis=1).astype(np.uint64)


def _grep_program(*, n_dev: int, chunk_bytes: int, m: int, bins: int,
                  k: int, mesh: Mesh, emit: bool = False):
    """(name, fn) for the one compiled grep step of a shape — single definition
    shared by the run, the warmer, and the cache-existence probe (the
    ``streaming._step_program`` discipline).  The emit variant (the plan
    handoff's extra compaction outputs) is a distinct executable and
    gets a distinct name."""

    def fn(chunks, pats, meta):
        return _grep_step_impl(chunks, pats, meta, bins=bins, k=k,
                               mesh=mesh, emit=emit)

    # The HLO module takes the traced function's name: a device trace
    # then shows ``jit_grep_stream_step`` and not a ``jit_fn`` among others.
    fn.__name__ = fn.__qualname__ = "grep_stream_step"
    name = (f"grep_stream_d{n_dev}_c{chunk_bytes}_m{m}_b{bins}_t{k}"
            + ("_em" if emit else ""))
    return name, fn


def _grep_examples(n_dev: int, chunk_bytes: int, m: int):
    sds = jax.ShapeDtypeStruct
    return (sds((n_dev, chunk_bytes), jnp.uint8),
            sds((n_dev, m), jnp.uint8),
            sds((n_dev, 2), jnp.uint64))


def _grep_fn(example_args, **kw):
    """Explicitly compiled grep step (``backends/aotcache.py`` memo;
    the ``tfidf._wave_fn`` rationale)."""
    from dsi_tpu.backends import aotcache

    name, fn = _grep_program(**kw)
    with _quiet_unusable_donation():  # a cold entry compiles right here
        return aotcache.cached_compile(name, fn, example_args,
                                       donate_argnums=_GREP_DONATE,
                                       x64=True)


# ── grep engine ────────────────────────────────────────────────────────


class GrepStreamResult(NamedTuple):
    """Whole-stream grep statistics.  ``hist[b]`` is the number of lines
    with ``min(occurrences, bins-1) == b``; ``topk`` is ``((line_no,
    occ), ...)`` count desc, line asc — exact, not approximate."""

    lines: int
    matched: int
    occurrences: int
    hist: Tuple[int, ...]
    topk: Tuple[Tuple[int, int], ...]


def _count_occurrences(line: bytes, pat: bytes) -> int:
    """Overlapping occurrence count — the engine counts every position
    where the pattern starts, so the oracle must too (``bytes.count`` is
    non-overlapping and would disagree on self-overlapping patterns)."""
    n = 0
    i = line.find(pat)
    while i >= 0:
        n += 1
        i = line.find(pat, i + 1)
    return n


def grep_host_oracle(blocks: Iterable[bytes], pattern: str, *,
                     bins: int = GREP_BINS,
                     topk: int = DEFAULT_TOPK) -> GrepStreamResult:
    """Single-pass host oracle with the engine's exact semantics — the
    parity ground truth for the bench row, the CLI ``--check``, and the
    test grid (one definition so the three cannot drift)."""
    pat = pattern.encode("ascii")
    hist = [0] * bins
    matched = occurrences = line_no = 0
    cands: List[Tuple[int, int]] = []
    carry = b""

    def take(line: bytes) -> None:
        nonlocal matched, occurrences, line_no
        occ = _count_occurrences(line, pat)
        hist[min(occ, bins - 1)] += 1
        if occ:
            matched += 1
            occurrences += occ
            cands.append((line_no, occ))
        line_no += 1

    for block in blocks:
        parts = (carry + bytes(block)).split(b"\n")
        carry = parts.pop()  # the unterminated tail stays pending
        for line in parts:
            take(line)
    if carry:
        take(carry)  # a final line without a trailing newline
    top = tuple(sorted(cands, key=lambda r: (-r[1], r[0]))[:topk])
    return GrepStreamResult(line_no, matched, occurrences, tuple(hist), top)


def merge_topk(cands: Iterable[Tuple[int, int]],
               k: int) -> Tuple[Tuple[int, int], ...]:
    """Exact global top-k from a union of per-step top-k candidate
    lists (``(line_no, occurrences)`` pairs, line numbers disjoint
    across steps).  Exact because any line in the global top-k is, with
    the same ``k``, necessarily in its own step's top-k: a step holding
    ``k`` lines that all beat it would beat it globally too.  One
    definition shared by the packed serving lanes and their tests."""
    return tuple(sorted(cands, key=lambda r: (-r[1], r[0]))[:k])


def grep_pack_fn(n_dev: int, chunk_bytes: int, m: int, *,
                 bins: int = GREP_BINS, k: int = DEFAULT_TOPK,
                 mesh: Mesh):
    """The compiled packed-grep step for one shape — the
    serving packer's entry (``serve/pack.py PackedGrepScheduler``) to
    the per-row grep program.  The kernel body runs per device row
    under ``shard_map`` with no collectives, so each row may carry a
    DIFFERENT pattern of the same length ``m``: K tenants whose
    patterns share a length share one executable and one dispatch.
    Same persistent-AOT cache entry the streaming engine uses — a
    daemon and a one-shot CLI warm each other."""
    return _grep_fn(_grep_examples(n_dev, chunk_bytes, m), n_dev=n_dev,
                    chunk_bytes=chunk_bytes, m=m, bins=bins, k=k, mesh=mesh)


class GrepStep(EngineStep):
    """Resumable step object over the streaming grep engine — the
    ``{advance, confirm, checkpoint, restore, close}`` lifecycle
    (``parallel/stepobj.py``) with :func:`grep_streaming`'s parameters
    and semantics.  A non-literal pattern routes to the host path at
    construction (the object is already terminal, ``close()`` → None);
    ``resume=True`` restores the newest valid chain before the first
    dispatch.

    ``line_sink`` (the plan layer's stage handoff, ``dsi_tpu/plan``) is
    a relay — :class:`~dsi_tpu.device.relay.DeviceRelay` or
    :class:`~dsi_tpu.device.relay.HostRelay` — receiving every confirmed
    step's compacted matching-line bytes via ``append(comp, kept)``:
    the step program grows the emit outputs and the downstream stage's
    upload becomes this stage's device-resident output."""

    def __init__(self, blocks: Iterable[bytes], pattern: str,
                 mesh: Mesh | None = None, chunk_bytes: int = 1 << 20,
                 depth: Optional[int] = None, aot: bool = False,
                 device_accumulate: bool = False,
                 sync_every: Optional[int] = None,
                 mesh_shards: Optional[int] = None,
                 topk: int = DEFAULT_TOPK, bins: int = GREP_BINS,
                 pipeline_stats: Optional[dict] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_async: Optional[bool] = None,
                 checkpoint_delta: Optional[bool] = None,
                 resume: bool = False, line_sink=None,
                 input_range: Optional[Tuple[int, int]] = None):
        super().__init__()
        _grep_setup(self, blocks, pattern, mesh, chunk_bytes, depth, aot,
                    device_accumulate, sync_every, mesh_shards, topk,
                    bins, pipeline_stats, checkpoint_dir,
                    checkpoint_every, checkpoint_async, checkpoint_delta,
                    resume, line_sink, input_range)


def grep_streaming(
        blocks: Iterable[bytes], pattern: str, mesh: Mesh | None = None,
        chunk_bytes: int = 1 << 20, depth: Optional[int] = None,
        aot: bool = False, device_accumulate: bool = False,
        sync_every: Optional[int] = None,
        mesh_shards: Optional[int] = None, topk: int = DEFAULT_TOPK,
        bins: int = GREP_BINS, pipeline_stats: Optional[dict] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_async: Optional[bool] = None,
        checkpoint_delta: Optional[bool] = None, resume: bool = False,
) -> Optional[GrepStreamResult]:
    """Whole-stream literal grep with bounded memory, pipelined.

    Returns a :class:`GrepStreamResult`, or None when the stream needs
    the host path (non-literal pattern, or a line wider than
    ``chunk_bytes``).  Every step runs the one compiled program of the
    stream's shape, whatever its lines look like; nothing is replayed.
    Results are bit-identical to ``depth=1`` because the accumulators
    only ever ingest confirmed per-step tensors.

    ``device_accumulate=True`` folds each confirmed step's histogram
    vector into a persistent :class:`DeviceHistogram` and its top-k
    candidate rows into a :class:`DeviceTopK` (lag = pipeline depth),
    pulling only a top-k snapshot + the histogram vector every
    ``sync_every`` folds (``DSI_STREAM_SYNC_EVERY`` default) plus the
    final close drain — ``step_pulls`` drops to 0 and ``sync_pulls``
    counts the K-fold windows (+1 close), with ``widens`` the
    drain→realloc×4→re-fold recoveries of a candidate table that
    outgrew its rung.  Results stay bit-identical: histogram folds are
    exact uint64 adds, candidate keys (global line numbers) are unique,
    and the close drain hands the host the complete multiset the
    per-step path would have pulled.

    ``mesh_shards`` (default ``DSI_STREAM_MESH_SHARDS``, 0 = off;
    implies ``device_accumulate``) mesh-shards both services: candidate
    folds route line keys by ``ihash % n_shards`` with an in-program
    all-to-all (per-shard widens, ``shard_widens``/``shard_imbalance``)
    and histogram pulls pre-merge on device to one ``[slots]`` vector.
    Results stay bit-identical.

    ``pipeline_stats`` mirrors ``wordcount_streaming``'s dict
    (``batch_s``/``batch_wait_s``/``upload_s``/``kernel_s``/``pull_s``
    with its parts ``device_wait_s`` + ``d2h_s``/``merge_s``/
    ``replay_s``/``finalize_s``, ``steps``/``replays``/``step_pulls``/
    ``sync_pulls``/``pull_bytes``, ``device_rows`` = confirmed lines per
    device, ``results_ready`` = steps whose host reads the device had
    already produced when ``finish_one`` first asked (their copies start
    at dispatch), plus the service counters; ``replays`` and ``replay_s`` are
    the shared pipeline's keys and stay 0 here).

    ``checkpoint_dir``/``checkpoint_every``/``resume`` follow the
    ``wordcount_streaming`` crash-resume contract (``dsi_tpu/ckpt``):
    snapshots at confirmed-step boundaries carry the host accumulators
    (or the device histogram/top-k images), the global line counter
    and the byte cursor; resumed output is bit-identical to an
    uninterrupted run.  ``checkpoint_async`` /
    ``checkpoint_delta`` (env twins ``DSI_STREAM_CKPT_ASYNC`` /
    ``DSI_STREAM_CKPT_DELTA``, both default off = bit-identical PR-5
    behavior) follow the ``wordcount_streaming`` capture/commit and
    incremental-save contracts: an async save captures at the boundary
    and commits in the background writer; a delta save ships only the
    candidate rows appended since the previous save (the histogram is
    cumulative KBs and rides every delta whole, newest-wins).
    """
    return GrepStep(
        blocks, pattern, mesh=mesh, chunk_bytes=chunk_bytes, depth=depth,
        aot=aot, device_accumulate=device_accumulate,
        sync_every=sync_every, mesh_shards=mesh_shards, topk=topk,
        bins=bins, pipeline_stats=pipeline_stats,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        checkpoint_async=checkpoint_async,
        checkpoint_delta=checkpoint_delta, resume=resume).close()


def _grep_setup(step, blocks, pattern, mesh, chunk_bytes, depth, aot,
                device_accumulate, sync_every, mesh_shards, topk, bins,
                pipeline_stats, checkpoint_dir, checkpoint_every,
                checkpoint_async, checkpoint_delta, resume,
                line_sink=None, input_range=None):
    """The engine body behind :class:`GrepStep`: full setup (resume
    restore included) ending with the pipeline armed and the lifecycle
    hooks attached to ``step``."""
    emit = line_sink is not None
    if emit and checkpoint_dir:
        # The relay's content is not part of the engine checkpoint, so a
        # mid-stage resume would drop already-emitted lines; chains
        # commit at stage boundaries instead (plan/driver.py).
        raise ValueError("line_sink and checkpoint_dir are exclusive: "
                         "chained stages commit at stage boundaries")
    if not is_literal_pattern(pattern):
        step._phase = "hostpath"  # terminal before any device work
        return
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    depth = pipeline_depth(depth)
    m = len(pattern)
    # Registry scope (dsi_tpu/obs): grep_phases is a view over the one
    # schema, not its own dialect.
    stats = metrics_scope("grep")
    stats.update({"depth": depth, "steps": 0, "replays": 0,
                  "results_ready": 0, "step_pulls": 0, "sync_pulls": 0,
                  "device_accumulate": device_accumulate,
                  "batch_s": 0.0, "batch_wait_s": 0.0, "recopied_bytes": 0,
                  "upload_s": 0.0, "kernel_s": 0.0, "pull_s": 0.0,
                  "device_wait_s": 0.0, "d2h_s": 0.0, "pull_bytes": 0,
                  "merge_s": 0.0, "replay_s": 0.0})
    sh2 = NamedSharding(mesh, P(AXIS, None))
    pat_np = np.tile(np.frombuffer(pattern.encode("ascii"), np.uint8),
                     (n_dev, 1))
    pat_dev = jax.device_put(pat_np, sh2)  # once per stream, never donated
    pool = BufferPool((n_dev, chunk_bytes), retain=2 * depth + 3)
    next_line = [0]
    dev_lines = np.zeros(n_dev, dtype=np.int64)  # confirmed, per device

    # Host-merge accumulators (the depth=1-equivalent path).
    hist_h = np.zeros(bins, dtype=np.int64)
    totals = np.zeros(3, dtype=np.int64)  # lines, matched, occurrences
    cand_h: List[Tuple[int, int]] = []

    # Device services.  ``mesh_shards`` makes them mesh-sharded
    # (device/table.py module docs): candidate keys — global line
    # numbers — route to ``ihash % n_shards`` inside the fold, the
    # top-k widen goes per-shard, and the histogram pull pre-merges on
    # device (one [slots] vector instead of n_dev partials).
    mesh_shards = mesh_shards_default(mesh_shards)
    if mesh_shards:
        device_accumulate = True
        stats["device_accumulate"] = True
    acc = KeyCounts()
    hist_svc: Optional[DeviceHistogram] = None
    topk_svc: Optional[DeviceTopK] = None
    policy: Optional[SyncPolicy] = None
    if device_accumulate:
        policy = SyncPolicy(sync_every)
        stats["sync_every"] = policy.sync_every
        stats["mesh_shards"] = mesh_shards
        hist_svc = DeviceHistogram(mesh, slots=bins + 3, aot=aot,
                                   stats=stats, mesh_shards=mesh_shards)
        topk_svc = DeviceTopK(mesh, kk=2, cap=_default_topk_cap(n_dev, topk),
                              k=topk, acc=acc, aot=aot,
                              lag=max(0, depth - 1), stats=stats,
                              mesh_shards=mesh_shards)

    # ── checkpoint/restore (dsi_tpu/ckpt) ──
    ck_store: Optional[CheckpointStore] = None
    ck_policy: Optional[CheckpointPolicy] = None
    ck_writer: Optional[CheckpointWriter] = None
    ck_cursor = {"offset": 0, "lines": 0}
    offsets: Optional[list] = None
    dispatch_idx = [0]
    start_offset = 0
    ck_async = checkpoint_async_default(checkpoint_async)
    ck_delta = checkpoint_delta_default(checkpoint_delta)
    cand_mark = [0]  # non-dacc delta watermark into the cand_h append log
    if checkpoint_dir:
        # input_range = the shard scheduler's cursor range: part of the
        # chain identity so a shard attempt can never restore another
        # range's (range-relative) cursors (mr/shards.py).
        ident = {"n_dev": n_dev, "chunk_bytes": chunk_bytes,
                 "pattern": pattern, "bins": bins, "topk": topk,
                 "device_accumulate": bool(device_accumulate)}
        if input_range is not None:
            ident["input_range"] = [int(input_range[0]),
                                    int(input_range[1])]
        ck_store = CheckpointStore(checkpoint_dir, "grep", ident)
        ck_policy = CheckpointPolicy(checkpoint_every)
        offsets = []
        stats.update({"ckpt_saves": 0, "ckpt_s": 0.0,
                      "ckpt_every": ck_policy.every,
                      "ckpt_capture_s": 0.0,
                      "ckpt_async": ck_async, "ckpt_delta": ck_delta})
        ck_writer = CheckpointWriter(ck_store, stats, async_=ck_async,
                                     delta=ck_delta)
        if ck_delta and topk_svc is not None:
            topk_svc.enable_delta()
        if resume:
            t_res = time.perf_counter()
            loaded = ck_store.load_latest_chain()
            if loaded is not None:
                meta, arrays, deltas = loaded
                # Cursor state is newest-wins: the final delta's meta
                # IS the restore point; the base meta only names the
                # image shapes.  (A chain written before PR 28 also
                # carries a line-capacity key; it is read past.)
                eff = deltas[-1][0] if deltas else meta
                start_offset = int(eff["cursor"])
                ck_cursor.update(offset=start_offset,
                                 lines=int(eff["lines"]))
                next_line[0] = int(eff["lines"])
                if device_accumulate:
                    acc.restore({k[3:]: v for k, v in arrays.items()
                                 if k.startswith("kc_")})
                    # The histogram vector is cumulative and rides
                    # every delta whole: the newest copy wins.
                    hist_img = arrays.get("hist")
                    for _, darr in deltas:
                        if "hist" in darr:
                            hist_img = darr["hist"]
                    if hist_img is not None:
                        hist_svc.restore_state({"hist": hist_img})
                    if meta.get("table_cap"):
                        img = {k[6:]: v for k, v in arrays.items()
                               if k.startswith("table_")}
                        same_degree = (int(meta.get("mesh_shards", 0))
                                       == mesh_shards)
                        if deltas or not same_degree:
                            # Chain restore (and the sharding-degree
                            # change) re-enters via the drain path:
                            # the image's merged rows flow into the
                            # KeyCounts accumulator, the candidate
                            # table starts empty, and the resumed
                            # folds rebuild device state.
                            DeviceTable.drain_image(acc, img)
                            if not same_degree:
                                stats["resharded_resume"] = int(
                                    meta.get("mesh_shards", 0))
                        else:
                            topk_svc.restore_state(img)
                            if ck_delta:
                                topk_svc.enable_delta()
                    policy.restore(eff.get("sync_since", 0))
                    for _, darr in deltas:
                        # Each delta's retained candidate steps re-enter
                        # the accumulator in save order — the drain-path
                        # argument, same as the cross-degree resume.
                        drain_packed_steps(acc, darr)
                else:
                    if "gs_hist" in arrays:
                        hist_h[:] = arrays["gs_hist"]
                        totals[:] = arrays["gs_totals"]
                    if "gs_cands" in arrays:
                        cand_h.extend(
                            (int(a), int(b))
                            for a, b in arrays["gs_cands"].tolist())
                    for _, darr in deltas:
                        # Cumulative counters newest-wins; candidate
                        # rows are the append-only log's increments.
                        hist_h[:] = darr["gs_hist"]
                        totals[:] = darr["gs_totals"]
                        if "gs_cands" in darr:
                            cand_h.extend(
                                (int(a), int(b))
                                for a, b in darr["gs_cands"].tolist())
                    cand_mark[0] = len(cand_h)
            stats["resume_gap_s"] = round(time.perf_counter() - t_res, 4)
            stats["resume_cursor"] = start_offset
        else:
            ck_store.reset()

    def save_ckpt() -> None:
        """Consistent snapshot at a confirmed-step boundary — capture
        here (device images first: flushing the top-k lag can widen,
        whose drain lands in the KeyCounts accumulator; host residue
        second), commit inline or in the background writer
        (``ckpt/writer.py``).  A delta save ships the candidate rows
        appended since the previous save plus the cumulative histogram
        vector (KBs — newest copy wins on restore); every
        ``DSI_STREAM_CKPT_REBASE``-th save is a full re-base (an
        invalid delta window forces one)."""
        with _span("ckpt", stats=stats, key="ckpt_s",
                   lines=ck_cursor["lines"]):
            meta = {"cursor": ck_cursor["offset"],
                    "lines": ck_cursor["lines"]}
            kind = "full"
            parts = None
            with _span("ckpt_capture", lane="ckpt", stats=stats,
                       key="ckpt_capture_s"):
                if ck_writer.want_delta():
                    if device_accumulate:
                        entries = topk_svc.take_delta()
                        if entries is not None:
                            parts = [("", DeltaSteps(entries)),
                                     ("", {"hist": hist_svc
                                           .checkpoint_state()["hist"]})]
                            meta["sync_since"] = policy.snapshot()
                            kind = "delta"
                    else:
                        new_cands = cand_h[cand_mark[0]:]
                        cand_mark[0] = len(cand_h)
                        d_arrays = {"gs_hist": hist_h.copy(),
                                    "gs_totals": totals.copy()}
                        if new_cands:
                            d_arrays["gs_cands"] = np.array(new_cands,
                                                            dtype=np.int64)
                        parts = [("", d_arrays)]
                        kind = "delta"
                if parts is None:
                    # Full image — the PR-5 arrays (device pulls
                    # dispatched, not awaited), and a fresh delta
                    # window: payloads recorded before this base are in
                    # the image, so the logs reset here.
                    parts = []
                    if device_accumulate:
                        parts.append(("table_",
                                      topk_svc.checkpoint_capture()))
                        meta["table_cap"] = topk_svc.cap
                        meta["table_kk"] = topk_svc.kk
                        meta["mesh_shards"] = topk_svc.mesh_shards
                        parts.append(("", hist_svc.checkpoint_capture()))
                        parts.append(("kc_", acc.snapshot()))
                        meta["sync_since"] = policy.snapshot()
                        if ck_delta:
                            topk_svc.take_delta()
                    else:
                        arrays = {"gs_hist": hist_h.copy(),
                                  "gs_totals": totals.copy()}
                        if cand_h:
                            arrays["gs_cands"] = np.array(cand_h,
                                                          dtype=np.int64)
                        parts.append(("", arrays))
                    cand_mark[0] = len(cand_h)
            fault_point("mid-capture")
            ck_writer.commit(parts, meta, kind=kind)

    def host_reads(hist_d, cand_d, scal, kept_d):
        """The arrays of one step that ``finish_one`` converts on the
        host: the scalar row always, the histogram and candidate rows
        where the host accumulates (with ``device_accumulate`` they stay
        on the device), the kept counts of the ``emit`` handoff."""
        reads = [scal]
        if not device_accumulate:
            reads += [hist_d, cand_d]
        if emit:
            reads.append(kept_d)
        return reads

    def step_call(buf, lens_np, bases_np):
        with _span("upload", stats=stats, key="upload_s",
                   step=stats["steps"]):
            # One put for the step's two inputs.  Under x64 so that the
            # u64 line bases stay u64 through it.
            with enable_x64(True):
                chunks, meta = jax.device_put(
                    (buf, step_meta(lens_np, bases_np)), (sh2, sh2))
        # What dispatch costs beside its upload: the program's lookup and
        # call, which returns before the device has run it, and the
        # starts of the copies below.
        with _span("enqueue", lane="dispatch", stats=stats,
                   step=stats["steps"], program="grep_stream_step"):
            fn = _grep_fn((chunks, pat_dev, meta), n_dev=n_dev,
                          chunk_bytes=chunk_bytes, m=m, bins=bins, k=topk,
                          mesh=mesh, emit=emit)
            with _quiet_unusable_donation():
                outs = fn(chunks, pat_dev, meta)
            if not emit:
                outs += (None, None)  # (hist, cand, scal, comp, kept)
            hist_d, cand_d, scal, _, kept_d = outs
            _enqueued(scal)
            # The results start for the host now, behind the step on the
            # device's queue, not when ``finish_one`` asks for them one
            # pump later: it then reads finished copies instead of paying
            # one round trip per array.
            for arr in host_reads(hist_d, cand_d, scal, kept_d):
                _copy_to_host_async(arr)
        return outs

    def dispatch(item):
        buf, lens_np, row_lines = item
        bases = np.zeros(n_dev, dtype=np.int64)
        bases[0] = next_line[0]
        np.cumsum(row_lines[:-1], out=bases[1:])
        bases[1:] += next_line[0]
        next_line[0] += int(row_lines.sum())
        hist_d, cand_d, scal, comp_d, kept_d = step_call(buf, lens_np,
                                                         bases)
        stats["steps"] += 1
        rec_offset = 0
        if offsets is not None:
            rec_offset = start_offset + offsets[dispatch_idx[0]]
            dispatch_idx[0] += 1
        fault_point("post-dispatch")
        return (buf, row_lines, hist_d, cand_d, scal, comp_d, kept_d,
                rec_offset, next_line[0])

    def finish_one(record) -> None:
        buf, row_lines, hist_d, cand_d, scal, comp_d, kept_d, \
            rec_offset, rec_lines = record
        with _span("kernel", stats=stats, key="kernel_s"):
            scal_np = np.asarray(scal)  # blocks until the kernel lands
        if not np.array_equal(scal_np[:, 1].astype(np.int64), row_lines):
            # The global line numbering depends on host/device agreeing
            # on per-row line counts; a disagreement is an engine bug and
            # must fail loudly, never skew the keys silently.
            pool.give(buf)
            raise RuntimeError(
                f"host/device line-count disagreement: "
                f"{row_lines.tolist()} vs {scal_np[:, 1].tolist()}")
        dev_lines[:] += row_lines
        if device_accumulate:
            hist_svc.fold(hist_d)
            if int(scal_np[:, 0].max()) > 0:
                topk_svc.fold(cand_d, scal, scal_np)
            policy.note_fold()
            if policy.due():
                fault_point("pre-sync")
                topk_svc.sync()
                hist_svc.pull()
                stats["sync_pulls"] += 1
                policy.reset()
        else:
            with _span("pull", stats=stats, key="pull_s"):
                # The parts ``streaming.to_host`` has: ``wait`` until
                # the device has produced what the next lines convert,
                # then ``d2h``, the copies themselves.
                with _span("wait", lane="pull", stats=stats,
                           key="device_wait_s"):
                    jax.block_until_ready((hist_d, cand_d))
                with _span("d2h", lane="pull", stats=stats,
                           bytes=hist_d.nbytes + cand_d.nbytes):
                    hist_np = np.asarray(hist_d)
                    cand_np = np.asarray(cand_d)
                stats["pull_bytes"] += hist_np.nbytes + cand_np.nbytes
                stats["step_pulls"] += 1
            with _span("merge", stats=stats, key="merge_s"):
                hist_h[:] += hist_np[:, :bins].astype(np.int64).sum(axis=0)
                totals[:] += hist_np[:, bins:].astype(np.int64).sum(axis=0)
                # Candidate rows in (device, rank) order, each device's
                # first ``n_cand``: (hi << 32 | lo, occurrences).
                live = np.arange(cand_np.shape[1]) < scal_np[:, :1]
                rows = cand_np[live].astype(np.int64)
                cand_h.extend(zip(((rows[:, 0] << 32) | rows[:, 1]).tolist(),
                                  rows[:, 3].tolist()))
        if emit:
            # The stage handoff: this confirmed step's compacted
            # matching-line bytes flow into the relay — device-resident
            # (DeviceRelay packs on device) or pulled (HostRelay, the
            # staged baseline).  The kept counts are the only host-side
            # metadata (n_dev int32s).
            kept_np = np.asarray(kept_d).astype(np.int64)
            line_sink.append(comp_d, kept_np)
        # Confirmed: merged/folded, nothing later is.  Fault before the
        # cursor advances — the torn-update instant.
        fault_point("mid-fold")
        if ck_store is not None:
            ck_cursor["offset"] = rec_offset
            ck_cursor["lines"] = rec_lines
            ck_policy.note_step()
            if ck_policy.due():
                save_ckpt()
                ck_policy.reset()
        pool.give(buf)

    pipe = StepPipeline(depth=depth, dispatch=dispatch, finish=finish_one,
                        stats=stats, produce_key="batch_s",
                        wait_key="batch_wait_s",
                        inflight_key="max_inflight_chunks",
                        thread_name="dsi-grep-batcher", engine="grep")

    feed = skip_stream(blocks, start_offset) if start_offset else blocks
    step._pipe = pipe
    step._cursor_ref = ck_cursor
    pipe.begin(lambda: batch_lines(feed, n_dev, chunk_bytes, pool=pool,
                                   offsets=offsets, stats=stats))
    step._host_excs = (_LineTooLong,)
    step._save = save_ckpt if ck_store is not None else None
    step._writer = ck_writer
    if resume:
        step._restore_info = {
            "resume_cursor": stats.get("resume_cursor", 0),
            "resume_gap_s": stats.get("resume_gap_s", 0.0)}

    def on_complete():
        h, t, cands = hist_h, totals, cand_h
        if device_accumulate or ck_writer is not None:
            with _span("drain", lane="sync", stats=stats, key="drain_s"):
                if device_accumulate:
                    fault_point("pre-sync")
                    # the exact final drain into the KeyCounts
                    topk_svc.close()
                    final = hist_svc.close()
                    h = final[:bins]
                    t = final[bins:]
                    cands = [(line, occ)
                             for line, occ in acc.finalize().items()]
                if ck_writer is not None:
                    # surface async commit errors; counters settle
                    # before the caller reads them
                    ck_writer.drain()
        with _span("finalize", lane="host", stats=stats,
                   cands=len(cands)):
            step.result = GrepStreamResult(int(t[0]), int(t[1]), int(t[2]),
                                           tuple(int(x) for x in h),
                                           merge_topk(cands, topk))

    released = []

    def release():
        if released:
            return
        released.append(True)
        if ck_writer is not None:
            ck_writer.shutdown()
        fold_source_stats(stats, blocks)
        if pipeline_stats is not None:
            stats["batch_allocs"] = pool.allocs
            stats["device_rows"] = dev_lines.tolist()
            for k in ("batch_s", "batch_wait_s", "upload_s", "kernel_s",
                      "pull_s", "device_wait_s", "d2h_s", "merge_s",
                      "replay_s", "finalize_s", "fold_s", "sync_s",
                      "sync_wait_s", "widen_s", "hist_s", "ckpt_s",
                      "ckpt_capture_s",
                      "ckpt_commit_s", "ckpt_barrier_s",
                      "ckpt_compress_s", "dispatch_s", "retire_s",
                      "enqueue_s", "drain_s"):
                if k in stats:
                    stats[k] = round(stats[k], 4)
            pipeline_stats.update(stats)

    step._on_complete = on_complete
    step._release = release


def warm_grepstream_aot(mesh: Mesh | None = None,
                        chunk_bytes: int = 1 << 20, pattern_len: int = 3,
                        bins: int = GREP_BINS, topk: int = DEFAULT_TOPK,
                        device_accumulate: bool = False,
                        mesh_shards: int = 0, emit: bool = False) -> None:
    """Compile + persist the grep step program of this shape plus, with
    ``device_accumulate``, the top-k fold/snapshot and histogram fold
    shapes (the ``mesh_*`` shuffle-fold variants under ``mesh_shards``).
    ``emit`` additionally warms the plan handoff's ``*_em`` compaction
    variant (and the relay pack program at this chunk shape).  From
    shape structs alone; mirror of ``warm_stream_aot``."""
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    examples = _grep_examples(n_dev, chunk_bytes, pattern_len)
    for em in (False, True) if emit else (False,):
        _grep_fn(examples, n_dev=n_dev, chunk_bytes=chunk_bytes,
                 m=pattern_len, bins=bins, k=topk, mesh=mesh, emit=em)
    if emit:
        from dsi_tpu.device.relay import _pack_fn

        _pack_fn(True, mesh=mesh, cap=chunk_bytes)
    if device_accumulate:
        from dsi_tpu.device.topk import warm_histogram, warm_topk_service

        warm_topk_service(mesh, kk=2, rows=topk,
                          cap=_default_topk_cap(n_dev, topk), k=topk,
                          table_rungs=2, mesh_shards=mesh_shards)
        warm_histogram(mesh, slots=bins + 3, mesh_shards=mesh_shards)


# ── the indexer posting step ───────────────────────────────────────────


def _idx_device_step(chunk: jax.Array, doc_id: jax.Array, *, n_dev: int,
                     n_reduce: int, max_word_len: int, u_cap: int,
                     t_cap_frac: int, pack_docs: bool = False):
    """Per-device wave body: the word-count map prologue over its
    document with a (tf ≡ 1, doc, part) payload — one posting row per
    distinct word per document — routed by the shared shuffle primitive
    and partitioned valid-first, exactly the TF-IDF wave discipline
    minus the term frequency.  A second output carries the received
    rows with the doc lane dropped: DeviceTable's packed (keys, len,
    count, part) layout with count ≡ 1, i.e. the wave's
    document-frequency increments ready to fold into the top-k table.

    ``pack_docs``: the chunk holds whole documents with ``DOC_SEP``
    between them (:func:`pack_chunk`) and ``doc_id`` is the vector of
    their ordinals in chunk order.  The prologue then groups by (word,
    document), a row a pair, and a row's document lane is the ordinal
    its place in the chunk names: the same rows, in another order, as
    one wave a document gives."""
    k = max_word_len // 4
    chunk = chunk.reshape(-1)

    if not pack_docs:
        doc = doc_id.reshape(())

    with jax.named_scope("map"):
        packed_u, len_u, cnt_u, part, dest, (
            n_unique, max_len, has_high, token_overflow), *doc_u = \
            map_prologue(
                chunk, n_dev=n_dev, n_reduce=n_reduce,
                max_word_len=max_word_len, u_cap=u_cap,
                t_cap_frac=t_cap_frac,
                doc_sep=DOC_SEP if pack_docs else None)

    with jax.named_scope("shuffle"):
        rows = jnp.concatenate(
            [packed_u, len_u[:, None].astype(jnp.uint32),
             jnp.ones((u_cap, 1), jnp.uint32),
             (doc_id.reshape(-1)[doc_u[0]].astype(jnp.uint32) if pack_docs
              else jnp.broadcast_to(doc.astype(jnp.uint32),
                                    (u_cap,)))[:, None],
             part[:, None]], axis=1)
        recv = shuffle_rows(rows, dest, n_dev=n_dev, u_cap=u_cap, k=k)

    # Valid rows first, in the order they came; every pad row behind
    # them.  One single-key int32 sort of the valid positions and a row
    # gather (``compact_positions``), where a stable sort of the rows
    # themselves carried all their lanes as operands: that sort doubled
    # the wave program's compile for the v5e (120 s against 61 at 1 MiB
    # and 32,768 rows; CHANGES.md, PR 38).
    with jax.named_scope("sort"):
        m = recv.shape[0]
        valid = recv[:, 0] != jnp.uint32(_PAD_KEY)
        n_rows = jnp.sum(valid, dtype=jnp.int32)
        pad_row = jnp.concatenate([jnp.full((k,), _PAD_KEY, jnp.uint32),
                                   jnp.zeros((4,), jnp.uint32)])
        srecv = jnp.concatenate([recv, pad_row[None]])[
            compact_positions(valid, m, fill_value=m)]

    df = jnp.concatenate([srecv[:, :k + 2], srecv[:, k + 3:k + 4]], axis=1)
    scalars = jnp.stack([n_rows, n_unique, max_len,
                         has_high.astype(jnp.int32),
                         token_overflow.astype(jnp.int32)]) \
        .astype(jnp.int32)  # x64 literal promotion must not widen these
    return srecv[None], df[None], scalars[None]


def _idx_wave_step_impl(chunks, doc_ids, *, n_dev: int, n_reduce: int,
                        max_word_len: int, u_cap: int, mesh: Mesh,
                        t_cap_frac: int = 4, pack_docs: bool = False):
    body = functools.partial(_idx_device_step, n_dev=n_dev,
                             n_reduce=n_reduce, max_word_len=max_word_len,
                             u_cap=u_cap, t_cap_frac=t_cap_frac,
                             pack_docs=pack_docs)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None) if pack_docs else P(AXIS)),
        out_specs=(P(AXIS, None, None), P(AXIS, None, None),
                   P(AXIS, None)))(chunks, doc_ids)


#: jax.jit donate_argnums for the wave program (chunk consumed; the tiny
#: doc-id vector is not worth donating) — the TF-IDF wave's contract.
_IDX_DONATE = (0,)


def _idx_program(*, n_dev: int, n_reduce: int, max_word_len: int,
                 u_cap: int, size: int, mesh: Mesh, t_cap_frac: int,
                 pack_docs: bool = False):
    def fn(chunk, ids):
        return _idx_wave_step_impl(chunk, ids, n_dev=n_dev,
                                   n_reduce=n_reduce,
                                   max_word_len=max_word_len, u_cap=u_cap,
                                   mesh=mesh, t_cap_frac=t_cap_frac,
                                   pack_docs=pack_docs)

    # The HLO module takes the traced function's name (``_grep_program``),
    # packed or not: one wave program, one name in a device trace.
    fn.__name__ = fn.__qualname__ = "idx_wave_step"
    name = (f"idx_wave_d{n_dev}_r{n_reduce}_w{max_word_len}"
            f"_u{u_cap}_s{size}_f{t_cap_frac}")
    if pack_docs:
        name += "_pk"
    return name, fn


def _idx_fn(example_args, **kw):
    from dsi_tpu.backends import aotcache

    name, fn = _idx_program(**kw)
    with _quiet_unusable_donation():
        return aotcache.cached_compile(name, fn, example_args,
                                       donate_argnums=_IDX_DONATE,
                                       x64=True)


def pack_docs_cap(size: int) -> int:
    """The most documents one packed chunk of ``size`` bytes names: the
    length of the wave program's vector of ordinals a device."""
    return max(64, size // 256)


def plan_packed_waves(doc_lens: Sequence[int], n_dev: int,
                      chunk_bytes: int) -> List[Tuple[List[List[int]], int]]:
    """``[(slots, chunk_size), ...]``: the waves of a packed walk, a
    function of the documents' lengths alone (so a checkpoint's
    confirmed-wave cursor means the same waves in every run).  ``slots``
    holds a device's documents, in chunk order, for at most ``n_dev``
    devices.

    A document longer than ``chunk_bytes`` goes alone, as
    ``tfidf.plan_waves`` has it: those first, longest first, ``n_dev`` a
    wave, the chunk the power of two over the wave's longest.  Every
    other document goes, in document order, into the open chunk of
    ``chunk_bytes`` while it fits behind a separator byte and the chunk
    names fewer than :func:`pack_docs_cap` documents; then the chunk
    closes.  The chunks go ``n_dev`` a wave in the order they closed.  No
    document is split."""
    alone = sorted((i for i, n in enumerate(doc_lens) if n > chunk_bytes),
                   key=lambda i: doc_lens[i], reverse=True)
    waves: List[Tuple[List[List[int]], int]] = []
    for w in range(0, len(alone), n_dev):
        idxs = alone[w:w + n_dev]
        longest = max(doc_lens[i] for i in idxs)
        waves.append(([[i] for i in idxs],
                      1 << max(8, int(longest).bit_length())))
    most = pack_docs_cap(chunk_bytes)
    chunks: List[List[int]] = []
    used = chunk_bytes  # no chunk is open
    for i, n in enumerate(doc_lens):
        if n > chunk_bytes:
            continue
        if used + 1 + n > chunk_bytes or len(chunks[-1]) >= most:
            chunks.append([])
            used = -1  # the first document stands behind no separator
        chunks[-1].append(i)
        used += 1 + n
    for w in range(0, len(chunks), n_dev):
        waves.append((chunks[w:w + n_dev], chunk_bytes))
    return waves


_DOC_SEP_BYTE = bytes([DOC_SEP])


def pack_chunk(docs: Sequence[bytes], slots: Sequence[Sequence[int]],
               n_dev: int, size: int,
               pad_id: int) -> Tuple[np.ndarray, np.ndarray]:
    """One packed wave: the ``[n_dev, size]`` block, a device's documents
    joined by ``DOC_SEP`` and zero-padded, and the ``[n_dev,
    pack_docs_cap(size)]`` vector of their ordinals (``pad_id`` behind
    them).  A document that holds the separator byte goes up with a
    space in its place: both are non-letters under 128, so its words are
    the same, and the count of separators before a byte stays the
    document's place in the chunk."""
    out = np.zeros((n_dev, size), dtype=np.uint8)
    ids = np.full((n_dev, pack_docs_cap(size)), pad_id, dtype=np.int32)
    for r, idxs in enumerate(slots):
        joined = _DOC_SEP_BYTE.join(
            bytes(docs[i]).replace(_DOC_SEP_BYTE, b" ") for i in idxs)
        out[r, :len(joined)] = np.frombuffer(joined, dtype=np.uint8)
        ids[r, :len(idxs)] = idxs
    return out, ids


class _AbortRung(Exception):
    """A wave proved this word-window rung's results will be discarded
    (non-ASCII input, or a word wider than the packed window)."""


class IndexerStep(EngineStep):
    """Resumable step object over the streaming indexer's wave walk —
    :func:`indexer_streaming`'s parameters and semantics behind the
    ``{advance, confirm, checkpoint, restore, close}`` lifecycle.  The
    word-window rung ladder lives INSIDE the lifecycle: a wave proving
    the rung too narrow tears it down and ``advance()`` transparently
    restarts at the 64-byte rung; non-ASCII input (or a word wider than
    64 bytes) routes to the host path (``close()`` → None).

    ``keep_services=True`` (the plan layer's stage handoff) completes
    the walk WITHOUT draining the device services: ``exported`` then
    carries the live :class:`DeviceTopK` df table, the
    :class:`DevicePostings` buffer, and the host accumulators, so a
    downstream stage can take a k-row df snapshot (no drain-to-host)
    and a selective postings join instead of the full materialization;
    ``result`` is a handoff marker, not the (postings, topk) tuple."""

    _rung_excs = (_AbortRung,)

    def __init__(self, docs: Sequence[bytes], mesh: Mesh | None = None,
                 n_reduce: int = 10, max_word_len: int = 16,
                 u_cap: int = 1 << 15, depth: Optional[int] = None,
                 device_accumulate: bool = False,
                 sync_every: Optional[int] = None,
                 mesh_shards: Optional[int] = None,
                 topk: int = DEFAULT_TOPK, stats: Optional[dict] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_async: Optional[bool] = None,
                 checkpoint_delta: Optional[bool] = None,
                 resume: bool = False, keep_services: bool = False,
                 input_range: Optional[Tuple[int, int]] = None,
                 pack_docs: bool = False, chunk_bytes: int = 1 << 20):
        super().__init__()
        _indexer_setup(self, docs, mesh, n_reduce, max_word_len, u_cap,
                       depth, device_accumulate, sync_every, mesh_shards,
                       topk, stats, checkpoint_dir, checkpoint_every,
                       checkpoint_async, checkpoint_delta, resume,
                       keep_services, input_range, pack_docs, chunk_bytes)

    def _next_rung(self) -> bool:
        self._pipe.end()
        if self._writer is not None:
            self._writer.shutdown()  # a rung restart discards rung state
        if not self._outcome["high"]:
            nxt = [m for m in self._rungs if m > self._mwl]
            if nxt:
                self._begin_rung(nxt[0])
                return True
        # Non-ASCII, or a word wider than 64 bytes: the host path's job.
        self.result = None
        self._phase = "hostpath"
        return False


def indexer_streaming(
        docs: Sequence[bytes], mesh: Mesh | None = None, n_reduce: int = 10,
        max_word_len: int = 16, u_cap: int = 1 << 15,
        depth: Optional[int] = None, device_accumulate: bool = False,
        sync_every: Optional[int] = None,
        mesh_shards: Optional[int] = None, topk: int = DEFAULT_TOPK,
        stats: Optional[dict] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_async: Optional[bool] = None,
        checkpoint_delta: Optional[bool] = None, resume: bool = False,
        pack_docs: bool = False, chunk_bytes: int = 1 << 20,
):
    """Whole-corpus inverted index over the mesh, waves of ``n_dev``
    documents, pipelined ``depth`` waves deep.

    Returns ``(postings, topk)`` where ``postings`` is ``{word: (part,
    [doc indices in wave order])}`` and ``topk`` is ``((df, word), ...)``
    — the k words with the highest document frequency, df desc, word asc
    — or None when any document needs the host path (non-ASCII bytes,
    words longer than 64).  Same exactness discipline as
    ``tfidf_sharded``: waves dispatch optimistically at a sticky
    (capacity, frac) rung, scalar checks are deferred until a
    wave leaves the window, a failed check replays exactly that wave,
    and a word wider than the packed window restarts the walk at the
    64-byte rung.

    ``device_accumulate=True`` appends each confirmed wave's posting
    rows into a persistent :class:`DevicePostings` buffer (the order-
    preserving sticky-overflow protocol from the TF-IDF walk) AND folds
    its document-frequency rows (count ≡ 1 per posting) into a
    :class:`DeviceTopK` table — the host sees postings once per
    ``sync_every`` waves and the df leaders as k-row snapshots, with
    the close drain completing the exact result.  Both the postings
    (including per-word posting order) and the top-k are bit-identical
    to the per-wave pull path.  ``mesh_shards`` (default
    ``DSI_STREAM_MESH_SHARDS``; implies ``device_accumulate``)
    re-routes both services by ``ihash(word) % n_shards`` inside their
    compiled programs — the mesh-sharded treatment, bit-identical
    output included.

    ``checkpoint_dir``/``checkpoint_every``/``resume`` follow the
    streaming engines' crash-resume contract (``dsi_tpu/ckpt``): the
    cursor is the CONFIRMED-wave ordinal (waves are planned
    deterministically from doc lengths, so skipping the first n waves
    on resume reproduces the walk), snapshots carry the postings table
    residue, the device buffers' drain-free images, and the sticky
    rung; the checkpoint records its word-window rung, and a rung that
    widens after resume simply restarts wider, exactly as the
    uninterrupted walk would.  Resumed postings (incl. per-word order)
    and df top-k are bit-identical to an uninterrupted run.

    ``pack_docs=True`` fills a wave with whole documents, a chunk of
    ``chunk_bytes`` a device (:func:`plan_packed_waves`,
    :func:`pack_chunk`), and the wave program groups by (word,
    document): the collection of many small documents, where a document
    a wave pays a dispatch and a pull for a few kilobytes.  The posting
    rows are the same rows, so the postings are the same sets; within a
    word the documents come in another order than the unpacked walk
    gives, which no consumer reads (the index names a word's documents
    sorted).  The capacity rungs count (word, document) pairs, the cursor
    of a checkpoint counts the packed waves, and the checkpoint's
    identity holds ``chunk_bytes``.
    """
    return IndexerStep(
        docs, mesh=mesh, n_reduce=n_reduce, max_word_len=max_word_len,
        u_cap=u_cap, depth=depth, device_accumulate=device_accumulate,
        sync_every=sync_every, mesh_shards=mesh_shards, topk=topk,
        stats=stats, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        checkpoint_async=checkpoint_async,
        checkpoint_delta=checkpoint_delta, resume=resume,
        pack_docs=pack_docs, chunk_bytes=chunk_bytes).close()


def _indexer_setup(step, docs, mesh, n_reduce, max_word_len, u_cap,
                   depth, device_accumulate, sync_every, mesh_shards,
                   topk, stats, checkpoint_dir, checkpoint_every,
                   checkpoint_async, checkpoint_delta, resume,
                   keep_services=False, input_range=None, pack_docs=False,
                   chunk_bytes=1 << 20):
    """The engine body behind :class:`IndexerStep`: corpus-wide setup,
    then ``begin_rung`` (the former per-rung ``run``) arms the pipeline
    and attaches the lifecycle hooks to ``step``.

    ``input_range`` is the shard scheduler's cursor range in DOC
    ordinals (the wave walks' cursor unit, mr/shards.py): the engine
    drives ``docs[start:end]`` and the range joins the chain identity,
    so two attempts over different ranges can never cross-restore."""
    if input_range is not None:
        docs = docs[int(input_range[0]):int(input_range[1])]
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    depth = pipeline_depth(depth)
    # ``mesh_shards`` re-routes the postings buffer AND the df top-k by
    # ``ihash(word) % n_shards`` inside their compiled programs — word
    # state shards by key, not by ``n_reduce % n_dev`` placement.
    mesh_shards = mesh_shards_default(mesh_shards)
    if mesh_shards:
        device_accumulate = True
    from dsi_tpu.parallel.tfidf import _wave_chunk, plan_waves

    doc_lens = getattr(docs, "lengths", None)
    if doc_lens is None:
        doc_lens = [len(d) for d in docs]
    if pack_docs:
        waves = plan_packed_waves(doc_lens, n_dev, int(chunk_bytes))
        size_max = max((size for _, size in waves), default=256)
    else:
        waves = plan_waves(doc_lens, n_dev)
        longest = max(doc_lens, default=1)
        size_max = 1 << max(8, int(longest).bit_length())
    n_real = len(docs)

    def wave_ordinals(idxs) -> List[int]:
        """A wave's documents, in the order its chunk is built."""
        return [i for slot in idxs for i in slot] if pack_docs else idxs

    # Internal registry scope (dsi_tpu/obs); copied out to the caller's
    # ``stats`` dict when the walk ends, like pipeline_stats everywhere.
    st = metrics_scope("indexer")
    st.update({"waves": len(waves), "docs": n_real, "step_pulls": 0,
               "depth": depth, "replays": 0,
               "device_accumulate": device_accumulate,
               # what the walk uploaded, counted a wave as it is
               # dispatched (a restarted rung's waves again): chunk
               # bytes -> waves, the documents' bytes and the padded
               # bytes they went up as (their ratio is how full the
               # waves were)
               "waves_by_size": {}, "wave_doc_bytes": 0,
               "wave_chunk_bytes": 0,
               # and the documents those waves held: all of them once,
               # the most in one wave (n_dev unless the walk packs)
               "pack_docs": bool(pack_docs), "wave_docs": 0,
               "docs_per_wave_max": 0,
               "upload_s": 0.0, "enqueue_s": 0.0, "kernel_s": 0.0,
               "pull_s": 0.0, "merge_s": 0.0, "replay_s": 0.0})
    sh_chunk = NamedSharding(mesh, P(AXIS, None))
    sh_ids = NamedSharding(mesh, P(AXIS, None) if pack_docs else P(AXIS))
    if pack_docs:
        st["pack_s"] = 0.0

    # ── checkpoint/restore (dsi_tpu/ckpt): wave-cursor variant ──
    ck_store: Optional[CheckpointStore] = None
    resume_meta = None
    resume_arrays = None
    resume_deltas: list = []
    ck_async = checkpoint_async_default(checkpoint_async)
    ck_delta = checkpoint_delta_default(checkpoint_delta)
    if checkpoint_dir:
        import zlib

        # The wave plan — and with it the cursor's meaning — is a
        # function of the full per-doc length vector, so the vector's
        # CRC is part of the job identity: same count + same total with
        # shuffled lengths must refuse, not silently misalign waves.
        lens_crc = zlib.crc32(np.asarray(doc_lens, np.int64).tobytes())
        ident = {"n_dev": n_dev, "n_reduce": n_reduce, "u_cap": u_cap,
                 "n_docs": n_real, "doc_lens_crc32": lens_crc,
                 "topk": topk,
                 "device_accumulate": bool(device_accumulate)}
        if input_range is not None:
            ident["input_range"] = [int(input_range[0]),
                                    int(input_range[1])]
        if pack_docs:  # the chunk size decides the packed plan
            ident["pack_docs"] = int(chunk_bytes)
        ck_store = CheckpointStore(checkpoint_dir, "indexer", ident)
        if resume:
            loaded = ck_store.load_latest_chain()
            if loaded is not None:
                resume_meta, resume_arrays, resume_deltas = loaded
        else:
            ck_store.reset()

    def begin_rung(mwl: int):
        kk = mwl // 4
        table = PostingsTable()
        state = {"cap": rung0_cap(size_max, u_cap), "frac": 4}
        outcome = {"high": False, "widen": False}

        def buffer_rows(r: np.ndarray) -> None:
            """One device's pulled posting rows into the host table,
            the short last wave's padding documents filtered FIRST."""
            r = r[r[:, kk + 2] < n_real]
            if len(r):
                table.add(r, kk)

        buf_dev = None
        topk_svc: Optional[DeviceTopK] = None
        df_acc = PackedCounts()
        policy = None
        if device_accumulate:
            from dsi_tpu.device import DevicePostings

            try:
                pcap = int(os.environ.get("DSI_DEVICE_POSTINGS_CAP", "0"))
            except ValueError:
                pcap = 0
            buf_dev = DevicePostings(
                mesh, width=kk + 4,
                cap=pcap if pcap > 0 else n_dev * state["cap"],
                sink=buffer_rows, lag=max(0, depth - 1), stats=st,
                mesh_shards=mesh_shards, kk=kk)
            policy = SyncPolicy(sync_every)
            st["sync_every"] = policy.sync_every
            st["mesh_shards"] = mesh_shards

        # A checkpoint belongs to ONE word-window rung (a widen re-keys
        # every row and restarts the walk, discarding rung state): apply
        # the loaded image only when this run() is at its rung.
        ck_policy: Optional[CheckpointPolicy] = None
        ck_writer: Optional[CheckpointWriter] = None
        ck_wave = [0]  # confirmed-wave cursor (absolute ordinal)
        host_delta = HostDeltaLog()  # non-dacc delta log: trimmed copies
        # of the pulled (rows, nrows) waves, bounded like device logs
        start_wave = 0
        if ck_store is not None:
            ck_policy = CheckpointPolicy(checkpoint_every)
            st.setdefault("ckpt_saves", 0)
            st.setdefault("ckpt_s", 0.0)
            st.setdefault("ckpt_capture_s", 0.0)
            st["ckpt_every"] = ck_policy.every
            st["ckpt_async"] = ck_async
            st["ckpt_delta"] = ck_delta
            # A fresh writer per rung: a rung restart discards rung
            # state, so its first save is a full base again.
            ck_writer = CheckpointWriter(ck_store, st, async_=ck_async,
                                         delta=ck_delta)
            if ck_delta and buf_dev is not None:
                buf_dev.enable_delta()
            eff = resume_deltas[-1][0] if resume_deltas else resume_meta
            if eff is not None and int(eff["mwl"]) == mwl:
                t_res = time.perf_counter()
                start_wave = int(eff["wave"])
                ck_wave[0] = start_wave
                state.update({"cap": int(eff["cap"]),
                              "frac": int(eff["frac"])})
                table.restore({k[3:]: v for k, v in resume_arrays.items()
                               if k.startswith("pt_")})
                if device_accumulate:
                    saved_shards = int(resume_meta.get("mesh_shards", 0))
                    if resume_meta.get("pb_cap"):
                        pb_img = {"buf": resume_arrays["pb_buf"],
                                  "nrows": resume_arrays["pb_nrows"],
                                  "cap": resume_meta["pb_cap"]}
                        if resume_deltas or saved_shards != mesh_shards:
                            # Chain restore (and the sharding-degree
                            # change) re-enters through the drain path:
                            # buffered rows into the host table, buffer
                            # empty; resumed waves rebuild device state.
                            DevicePostings.drain_image(buffer_rows, pb_img)
                            if saved_shards != mesh_shards:
                                st["resharded_resume"] = saved_shards
                        else:
                            buf_dev.restore_state(pb_img)
                            if ck_delta:
                                buf_dev.enable_delta()
                    df_acc.restore(
                        {k[3:]: v for k, v in resume_arrays.items()
                         if k.startswith("df_")})
                    if resume_meta.get("table_cap"):
                        img = {k[6:]: v for k, v in resume_arrays.items()
                               if k.startswith("table_")}
                        if (not resume_deltas
                                and saved_shards == mesh_shards):
                            topk_svc = DeviceTopK(
                                mesh, kk=int(resume_meta["table_kk"]),
                                cap=int(resume_meta["table_cap"]), k=topk,
                                acc=df_acc, aot=False,
                                lag=max(0, depth - 1), stats=st,
                                mesh_shards=mesh_shards)
                            topk_svc.restore_state(img)
                            if ck_delta:
                                topk_svc.enable_delta()
                        else:
                            DeviceTable.drain_image(df_acc, img)
                            if saved_shards != mesh_shards:
                                st["resharded_resume"] = saved_shards
                    policy.restore(eff.get("sync_since", 0))
                for _, darr in resume_deltas:
                    # Each delta's retained wave payloads re-enter the
                    # host side in save order — postings through the
                    # sink (per-word order preserved: the drain-path
                    # argument), df rows through the accumulator.
                    drain_posting_steps(buffer_rows, darr, "pb_")
                    drain_packed_steps(df_acc, darr, "tk_")
                st["resume_gap_s"] = round(time.perf_counter() - t_res, 4)
                st["resume_wave"] = start_wave

        def save_ckpt() -> None:
            """Consistent snapshot at a confirmed-wave boundary —
            capture here, commit inline or in the background writer
            (``ckpt/writer.py``).  Device captures first — flushing the
            postings buffer's lag drains into the host table on
            overflow recovery, and flushing the df top-k's lag can
            widen into ``df_acc`` — host residue second, so both sides
            of any such move land in the same image.  A delta save
            ships only the wave payloads retained since the previous
            save (device logs in dacc mode, the already-pulled host
            rows otherwise); every ``DSI_STREAM_CKPT_REBASE``-th save
            is a full re-base (an invalid delta window forces one)."""
            with _span("ckpt", stats=st, key="ckpt_s", wave=ck_wave[0]):
                meta = {"mwl": mwl, "wave": ck_wave[0],
                        "cap": state["cap"], "frac": state["frac"]}
                kind = "full"
                parts = None
                with _span("ckpt_capture", lane="ckpt", stats=st,
                           key="ckpt_capture_s"):
                    if ck_writer.want_delta():
                        if device_accumulate:
                            pb_entries = buf_dev.take_delta()
                            tk_entries = (topk_svc.take_delta()
                                          if topk_svc is not None else [])
                        else:
                            pb_entries = host_delta.take()
                            tk_entries = []
                        if pb_entries is not None and tk_entries is not None:
                            parts = [("pb_", DeltaSteps(pb_entries)),
                                     ("tk_", DeltaSteps(tk_entries))]
                            if device_accumulate:
                                meta["sync_since"] = policy.snapshot()
                            kind = "delta"
                    if parts is None:
                        # Full image — the PR-5 arrays (device pulls
                        # dispatched, not awaited); the delta logs
                        # reset here: payloads recorded before this
                        # base are inside the image.
                        parts = []
                        if buf_dev is not None:
                            parts.append(("pb_",
                                          buf_dev.checkpoint_capture()))
                            meta["pb_cap"] = buf_dev.cap
                            meta["mesh_shards"] = buf_dev.mesh_shards
                            if topk_svc is not None:
                                parts.append(
                                    ("table_",
                                     topk_svc.checkpoint_capture()))
                                meta["table_cap"] = topk_svc.cap
                                meta["table_kk"] = topk_svc.kk
                            parts.append(("df_", df_acc.snapshot()))
                            meta["sync_since"] = policy.snapshot()
                            if ck_delta:
                                buf_dev.take_delta()
                                if topk_svc is not None:
                                    topk_svc.take_delta()
                        host_delta.reset()
                        parts.append(("pt_", table.snapshot()))
                fault_point("mid-capture")
                ck_writer.commit(parts, meta, kind=kind)

        def materialize():
            for idxs, size in waves[start_wave:]:
                held = wave_ordinals(idxs)
                # A lazy sequence is asked here, once a document and
                # outside ``pack``: what it waits for is its own span.
                wave = {i: docs[i] for i in held}
                if pack_docs:
                    with _span("pack", lane="materialize", stats=st,
                               key="pack_s", docs=len(held), size=size):
                        chunk_np, ids_np = pack_chunk(wave, idxs, n_dev,
                                                      size, n_real)
                else:
                    chunk_np = _wave_chunk(wave, idxs, n_dev, size)
                    ids_np = np.array(
                        list(idxs) + [n_real] * (n_dev - len(idxs)),
                        dtype=np.int32)
                yield (size, chunk_np, ids_np,
                       sum(doc_lens[i] for i in held), len(held))

        def wave_call(chunk_np, ids_np, size, cap, frac):
            with _span("upload", stats=st, key="upload_s"):
                chunk = jax.device_put(chunk_np, sh_chunk)
                ids = jax.device_put(ids_np, sh_ids)
            # The program's lookup and call, which returns before the
            # device has run it (``grep``'s ``enqueue``).
            with _span("enqueue", lane="dispatch", stats=st,
                       program="idx_wave_step", size=size, cap=cap):
                fn = _idx_fn((chunk, ids), n_dev=n_dev, n_reduce=n_reduce,
                             max_word_len=mwl, u_cap=cap, size=size,
                             mesh=mesh, t_cap_frac=frac,
                             pack_docs=pack_docs)
                with _quiet_unusable_donation():
                    outs = fn(chunk, ids)
                _enqueued(outs[2])  # (rows, df, scal)
                return outs

        def dispatch(item):
            size, chunk_np, ids_np, doc_bytes, n_held = item
            st["waves_by_size"][size] = st["waves_by_size"].get(size, 0) + 1
            st["wave_doc_bytes"] += doc_bytes
            st["wave_chunk_bytes"] += n_dev * size
            st["wave_docs"] += n_held
            st["docs_per_wave_max"] = max(st["docs_per_wave_max"], n_held)
            rows, df, scal = wave_call(chunk_np, ids_np, size,
                                       state["cap"], state["frac"])
            fault_point("post-dispatch")
            return (size, chunk_np, ids_np, rows, df, scal, state["cap"])

        def replay_wave(size, chunk_np, ids_np):
            st["replays"] += 1
            cap = state["cap"]
            with _span("replay", stats=st, key="replay_s"):
                while True:
                    for frac in (4, 2):
                        rows, df, scal = wave_call(chunk_np, ids_np,
                                                   size, cap, frac)
                        scal_np = np.asarray(scal)
                        if not scal_np[:, 4].any():
                            break
                    if bool(scal_np[:, 3].any()):
                        outcome["high"] = True
                        raise _AbortRung
                    if int(scal_np[:, 2].max()) > mwl:
                        outcome["widen"] = True
                        raise _AbortRung
                    uniques = int(scal_np[:, 1].max())
                    if uniques > cap:
                        # The first x4 rung that holds what the wave
                        # reported (``exactness_retry``): a rung known
                        # not to fit is never compiled.  Uniques <=
                        # tokens <= size/2, so this terminates.
                        while cap < uniques:
                            cap *= 4
                        continue
                    break
            state["cap"], state["frac"] = cap, frac
            return rows, df, scal, scal_np

        def commit(rows, df, scal, scal_np):
            nonlocal topk_svc
            m = int(scal_np[:, 0].max())
            if m == 0:
                return
            if buf_dev is not None:
                # The df fold rides the SAME confirmation: only waves the
                # postings path accepted fold their frequency rows.
                if topk_svc is None:
                    # Rung-0 df-table capacity: the wave's row count (a
                    # single fold can never overflow it), unless the
                    # shared DSI_DEVICE_TOPK_CAP override asks smaller.
                    topk_svc = DeviceTopK(
                        mesh, kk=kk,
                        cap=_topk_cap_env() or int(df.shape[1]),
                        k=topk, acc=df_acc, aot=False,
                        lag=max(0, depth - 1), stats=st,
                        mesh_shards=mesh_shards)
                    if ck_store is not None and ck_delta:
                        topk_svc.enable_delta()
                pulls_before = st["sync_pulls"]
                buf_dev.append(rows, scal,
                               nvalid=scal_np[:, 0].astype(np.int64))
                topk_svc.fold(df, scal, scal_np)
                policy.note_fold()
                if st["sync_pulls"] != pulls_before:
                    policy.reset()  # an overflow recovery just drained:
                    # that WAS this window's pull
                elif policy.due():
                    fault_point("pre-sync")
                    buf_dev.sync()
                    topk_svc.sync()
                    policy.reset()
                return
            with _span("pull", stats=st, key="pull_s"):
                mp = occupied_prefix(m, rows.shape[1])
                # the slice's program and its copy, blocked on at once
                with _span("d2h", lane="pull", stats=st, key="d2h_s"):
                    rows_np = np.asarray(rows[:, :mp])
                st["step_pulls"] += 1
            with _span("merge", stats=st, key="merge_s"):
                for d in range(n_dev):
                    nr = int(scal_np[d, 0])
                    if nr:
                        buffer_rows(rows_np[d, :nr])
                if ck_store is not None and ck_delta:
                    # Host-merge delta log: the wave's payload, window-
                    # bounded like the device logs.
                    host_delta.append(rows_np, scal_np[:, 0])

        def finish(rec):
            size, chunk_np, ids_np, rows, df, scal, cap = rec
            with _span("kernel", stats=st, key="kernel_s"):
                scal_np = np.asarray(scal)  # blocks until the kernel lands
            if bool(scal_np[:, 3].any()):
                outcome["high"] = True
                raise _AbortRung
            if int(scal_np[:, 2].max()) > mwl:
                outcome["widen"] = True
                raise _AbortRung
            if scal_np[:, 4].any() or int(scal_np[:, 1].max()) > cap:
                rows, df, scal, scal_np = replay_wave(size, chunk_np,
                                                      ids_np)
            commit(rows, df, scal, scal_np)
            # Confirmed (empty waves included — the cursor must advance
            # past them too); fault before the cursor moves.
            fault_point("mid-fold")
            if ck_policy is not None:
                ck_wave[0] += 1
                ck_policy.note_step()
                if ck_policy.due():
                    save_ckpt()
                    ck_policy.reset()

        st.setdefault("sync_pulls", 0)
        pipe = StepPipeline(depth=depth, dispatch=dispatch, finish=finish,
                            stats=st, produce_key="materialize_s",
                            wait_key="materialize_wait_s",
                            inflight_key="max_inflight_waves",
                            thread_name="dsi-idx-materializer",
                            engine="indexer")
        step._pipe = pipe
        step._mwl = mwl
        step._outcome = outcome
        step._save = save_ckpt if ck_policy is not None else None
        step._writer = ck_writer
        # A sequence that reads ahead (``ioread.ReadAheadDocs``) is told
        # the order this rung's walk will ask in, duck-typed as
        # ``lengths`` is.
        read_ahead = getattr(docs, "read_ahead", None)
        if read_ahead is not None:
            read_ahead([i for idxs, _ in waves[start_wave:]
                        for i in wave_ordinals(idxs)])
        pipe.begin(materialize)

        def end_ok():
            if keep_services:
                # The plan handoff: finish the walk but leave the
                # device services RESIDENT — no drain-to-host.  The
                # downstream stages pull a k-row df snapshot
                # (DeviceTopK.sync) and close the postings buffer
                # themselves; the host residue travels alongside so a
                # widen that already drained stays accounted for.
                try:
                    if ck_writer is not None:
                        ck_writer.drain()
                finally:
                    if ck_writer is not None:
                        ck_writer.shutdown()
                step.exported = {
                    "kk": kk, "n_real": n_real, "topk": topk,
                    "device_accumulate": device_accumulate,
                    "topk_svc": topk_svc, "postings_svc": buf_dev,
                    "df_acc": df_acc, "table": table,
                    "buffer_rows": buffer_rows}
                step.result = ("plan-handoff",)
                return
            try:
                if buf_dev is not None:
                    fault_point("pre-sync")
                    buf_dev.close()
                    if topk_svc is not None:
                        topk_svc.close()
                if ck_writer is not None:
                    ck_writer.drain()  # surface async commit errors
                    # before the payload (and save counters) are read
            finally:
                if ck_writer is not None:
                    ck_writer.shutdown()
            postings = {
                w: (part, [d for d, _ in pairs])
                for w, (part, pairs) in table.finalize(stats=st).items()}
            if device_accumulate and topk_svc is not None:
                df_map = {w: c for w, (c, _) in df_acc.finalize().items()}
            else:
                df_map = {w: len(ds) for w, (_, ds) in postings.items()}
            top = tuple(sorted(((c, w) for w, c in df_map.items()),
                               key=lambda r: (-r[0], r[1]))[:topk])
            step.result = (postings, top)

        step._on_complete = end_ok

    rungs = ((max_word_len, 64) if max_word_len < 64 else (max_word_len,))
    if resume_meta is not None:
        # The checkpoint is at a rung: start there (an earlier rung had
        # provably aborted before the checkpointed one began).
        rungs = tuple(m for m in rungs
                      if m >= int(resume_meta["mwl"])) or rungs
    step._rungs = tuple(rungs)
    step._begin_rung = begin_rung

    released = []

    def release():
        if released:
            return
        released.append(True)
        w = step._writer  # the CURRENT rung's writer (re-set per rung)
        if w is not None:
            w.shutdown()
        fold_source_stats(st, docs)  # a doc source may pool-read too
        if stats is not None:
            stats.update(st)

    step._release = release
    begin_rung(rungs[0])


def write_indexer_output(result, doc_names: Sequence[str], n_reduce: int,
                         workdir: str = ".") -> List[str]:
    """Materialise mr-out-<r> files byte-identical to the host indexer
    app's reduce output (``"<count> <doc1>,<doc2>,..."`` with documents
    sorted and deduplicated), via the shared partitioned writer."""
    from dsi_tpu.parallel.shuffle import write_partitioned_output

    postings, _ = result if isinstance(result, tuple) else (result, ())
    formatted = {}
    for w, (part, doc_ids) in postings.items():
        names = sorted({doc_names[d] for d in doc_ids})
        formatted[w] = (f"{len(names)} {','.join(names)}", part)
    return write_partitioned_output(formatted, n_reduce, workdir)


def warm_indexer_aot(mesh: Mesh | None = None, sizes: Sequence[int] = (
        1 << 18,), n_reduce: int = 10, word_lens: Sequence[int] = (16,),
        caps: Sequence[int] = (1 << 14,), fracs: Sequence[int] = (4, 2),
        topk: int = DEFAULT_TOPK, device_accumulate: bool = False,
        mesh_shards: int = 0, pack_docs: bool = False) -> None:
    """Compile + persist the ``idx_wave_*`` shapes an
    ``indexer_streaming`` run reaches at these wave sizes/capacities,
    plus — with ``device_accumulate`` — the df top-k fold shapes.  From
    shape structs alone."""
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    sds = jax.ShapeDtypeStruct
    for mwl in word_lens:
        for cap in caps:
            for size in sizes:
                ids = (n_dev, pack_docs_cap(size)) if pack_docs else (n_dev,)
                examples = (sds((n_dev, size), jnp.uint8),
                            sds(ids, jnp.int32))
                for frac in fracs:
                    _idx_fn(examples, n_dev=n_dev, n_reduce=n_reduce,
                            max_word_len=mwl, u_cap=cap, size=size,
                            mesh=mesh, t_cap_frac=frac,
                            pack_docs=pack_docs)
            if device_accumulate:
                from dsi_tpu.device.topk import warm_topk_service

                warm_topk_service(mesh, kk=mwl // 4, rows=n_dev * cap,
                                  cap=n_dev * cap, k=topk, table_rungs=2,
                                  mesh_shards=mesh_shards)
