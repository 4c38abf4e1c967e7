"""Vectorized host-side merge tables for the SPMD paths' per-step outputs.

The streaming word-count and TF-IDF paths produce per-step device tables of
packed word keys (big-endian uint32 lanes, ``ops/wordcount.py``
tokenize_group_core) plus payload columns.  Round 3 merged those into Python
dicts one word at a time — O(rows) interpreter iterations with a string
decode per row, the scale ceiling of both paths.

This module replaces the per-row loops with numpy table algebra:

* rows accumulate as raw uint32 arrays (copied out of the step's transfer
  buffer so no device-shaped block stays alive),
* a batch whose rows strictly increase is a *run*, and a device's step
  table is one as it arrives (the step program sorted and grouped it), so
  the word-count accumulator merges runs and sorts nothing twice: the
  batches since the last compaction (the window) are merged into one run
  and that run into the merged table, which stands apart from the window
  and never goes through a sort again (a window that fills is merged on
  a thread of its own, the next one filling beside it).  Two pointers in
  ``native/mergeruns.cpp``; without the library one stable sort of the
  window on a packed ``uint64`` key (a merge of the runs it finds), ties
  repaired, ``np.add.reduceat``, and ``np.searchsorted`` placement into the
  table.  Only a batch that does not arrive sorted is sorted, alone, on
  entry (one ``np.lexsort`` over its key lanes),
* the final merged table IS the result (``PackedWordCounts``): the
  partition writer renders ``mr-out-*`` from its arrays, and word
  spellings are decoded (ONCE, vocabulary-sized, via the same bulk
  ``decode_packed`` the kernels use) only for a caller that asks for a
  word by key, iterates or compares.

Zero-padded key lanes make width harmonisation trivial: a word packed into
K lanes and the same word packed into K' > K lanes agree on the first K
lanes and are zero beyond, so narrower tables are right-padded with zero
columns before they are merged, which keeps a run sorted.

The reference has no analogue (its reduce merge is the in-memory group of
``mr/worker.go:110-124``); this is that merge re-done as array algebra so
the host side can keep up with the device side at GB scale.
"""

from __future__ import annotations

import threading
from collections.abc import Mapping
from typing import Dict, List, Optional, Tuple

import numpy as np

from dsi_tpu import native as _native
from dsi_tpu.obs import span as _span
from dsi_tpu.ops.wordcount import decode_packed


#: 10^1 .. 10^18, every power of ten an int64 count can reach.
_POW10 = 10 ** np.arange(1, 19, dtype=np.int64)


def _index_dtype(limit: int):
    """The narrowest integer type that holds indices below ``limit``."""
    return np.int32 if limit < 1 << 31 else np.int64


def _word_number_rows(key_bytes: np.ndarray, rows: np.ndarray,
                      lens: np.ndarray, numbers: np.ndarray,
                      last: int, decimals: int = 0) -> np.ndarray:
    """``"word number"`` and the byte ``last`` for each of ``rows``, flat,
    from the arrays: the words' bytes out of ``key_bytes`` ([n, 4K]
    uint8, the big-endian view of the key lanes) cut at the longest
    word; a space; decimal digits by integer comparisons and ``divmod``
    (an int64 past 2^53 must print exactly, so no float logarithm);
    ``last``.  One [rows, longest word + 1 + most digits + 1] byte matrix
    and a keep-mask: what a row keeps follows its (length, digits)
    alone, so one mask a class, gathered a row.  With ``decimals`` the
    numbers count 10^-decimals units and print as ``<integer
    part>.<decimals digits>``: that many columns and the point's more, at
    the right, which every row keeps.  ``numbers`` of two dimensions
    ([rows, m]) print as m numbers a row, a space before each: a field of
    its own width a column, and a class is (length, digits, digits...)."""
    lens = lens.astype(np.int64)
    w = int(lens.max())
    tail = decimals + 1 if decimals else 0
    columns = list(numbers.T) if numbers.ndim == 2 else [numbers]
    digits = [np.searchsorted(
        _POW10, c // 10 ** decimals if decimals else c, side="right") + 1
        for c in columns]
    most = [int(d.max()) for d in digits]
    width = w + sum(d + tail + 1 for d in most) + 1
    mat = np.empty((len(rows), width), np.uint8)
    mat[:, :w] = np.take(key_bytes, rows, axis=0)[:, :w]
    col = np.arange(width)
    masks = col < np.arange(w + 1)[:, None]
    kind, at = lens, w  # a row's class; the space before the next number
    for numbers, digit_count, d in zip(columns, digits, most):
        mat[:, at] = 0x20
        end = at + d + tail
        for c in range(end, at, -1):  # right-aligned, least first
            if tail and c == end - decimals:
                mat[:, c] = 0x2E
                continue
            numbers, digit = np.divmod(numbers, 10)
            mat[:, c] = digit + 0x30
        field = (col == at) | ((col <= end) & (
            col >= at + 1 + d - np.arange(d + 1)[:, None]))
        masks = (masks[:, None] | field).reshape(-1, width)
        kind = kind * (d + 1) + digit_count
        at = end + 1
    mat[:, at] = last
    masks[:, at] = True
    return mat[np.take(masks, kind, axis=0)]


def _pad_width(keys: np.ndarray, k: int) -> np.ndarray:
    """Right-pad packed-key lanes with zero columns to width ``k``."""
    if keys.shape[1] == k:
        return keys
    out = np.zeros((keys.shape[0], k), dtype=np.uint32)
    out[:, :keys.shape[1]] = keys
    return out


def _group_starts(sorted_keys: np.ndarray) -> np.ndarray:
    """Start indices of equal-key runs in a lexsorted [n, k] table."""
    n = len(sorted_keys)
    boundary = np.empty(n, dtype=bool)
    boundary[0] = True
    np.any(sorted_keys[1:] != sorted_keys[:-1], axis=1, out=boundary[1:])
    return np.flatnonzero(boundary)


def _lexsort_rows(keys: np.ndarray) -> np.ndarray:
    """Row order sorting a [n, k] table lexicographically (lane 0 primary).

    ``np.lexsort`` treats its LAST key as primary, so lanes are passed in
    reverse.
    """
    return np.lexsort(tuple(keys[:, j] for j in range(keys.shape[1] - 1,
                                                      -1, -1)))


def _rows_increase(keys: np.ndarray) -> bool:
    """Whether a [n, k] table's rows strictly increase lexicographically
    (lane 0 primary): sorted and distinct, which makes the table a run.
    One pass in ``native/mergeruns.cpp``; else decided at each row's
    first lane that differs from the row before."""
    if len(keys) < 2:
        return True
    native = _native.rows_increase(keys)
    if native is not None:
        return native
    prev, nxt = keys[:-1], keys[1:]
    first = (prev != nxt).argmax(axis=1)
    rows = np.arange(len(prev))
    return bool((nxt[rows, first] > prev[rows, first]).all())


#: One table of the accumulator: key lanes [n, K] uint32, byte lengths
#: int32, counts int64 ([n], or [n, m]: m sums a key side by side, merged
#: in numpy), reduce partitions int32, all C-contiguous.
_Table = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def _pad_table(table: _Table, k: int) -> _Table:
    return (_pad_width(table[0], k),) + table[1:]


def _reduce_ordered(table: _Table, order: np.ndarray,
                    skeys: np.ndarray) -> _Table:
    """The run of a table whose rows ``order`` sorts (``skeys`` =
    ``keys[order]``): one row a word.  Length and partition are
    functions of the word, so first-of-run is exact; only the counts
    need the segmented sum."""
    _, lens, cnts, parts = table
    starts = _group_starts(skeys)
    first = order[starts]
    return (skeys[starts], lens[first],
            np.add.reduceat(cnts[order], starts), parts[first])


def _sort_reduce(table: _Table) -> _Table:
    """Any batch as a run: its rows ordered by one ``np.lexsort`` over
    the key lanes, the counts of a word it holds twice summed."""
    order = _lexsort_rows(table[0])
    return _reduce_ordered(table, order, table[0][order])


def _own_run(keys, lens, cnts, parts) -> Tuple[_Table, bool]:
    """A batch as a run of the accumulator's own arrays, and whether it
    arrived as one.  Copies detach the rows from the step's
    full-capacity transfer buffer; counts widen to int64 so multi-step
    sums can't wrap."""
    batch = (np.array(keys, dtype=np.uint32, order="C"),
             np.array(lens, dtype=np.int32),
             np.array(cnts, dtype=np.int64),
             np.array(parts, dtype=np.int32))
    if _rows_increase(batch[0]):
        return batch, True
    return _sort_reduce(batch), False


def _merge2_native(a: _Table, b: _Table) -> Optional[_Table]:
    """Two runs of one lane width as one, by ``mergeruns.cpp``'s two
    pointers; None without the library."""
    if a[2].ndim > 1:  # several sums a key: the library merges one
        return None
    cap = len(a[0]) + len(b[0])
    out = (np.empty((cap, a[0].shape[1]), np.uint32),
           np.empty(cap, np.int32), np.empty(cap, np.int64),
           np.empty(cap, np.int32))
    n = _native.merge_runs2(a, b, out)
    return None if n is None else tuple(x[:n] for x in out)


def _stable_key_order(table: np.ndarray,
                      k: int) -> Tuple[np.ndarray, np.ndarray]:
    """The stable order of a ``[n, >= k]`` uint32 table's rows by their
    first ``k`` columns (key lanes, lane 0 primary), and the table in
    that order: ONE stable sort of the first two lanes packed into a
    ``uint64`` (numpy's stable sort of 64-bit integers is a merge of the
    runs it finds, so rows that arrive as runs cost a merge and not a
    sort), then one gather of whole rows.  Words that share their first
    eight bytes and differ later tie in that column: the groups that
    hold such words, a few, are put in order by their other lanes, rows
    of one word staying as they stood."""
    primary = table[:, 0].astype(np.uint64)
    if k > 1:
        primary <<= np.uint64(32)
        primary |= table[:, 1]
    order = np.argsort(primary, kind="stable")
    stable = table[order]
    if k > 2:
        primary = primary[order]
        tie = primary[1:] == primary[:-1]
        mixed = tie & (stable[1:, 2:k] != stable[:-1, 2:k]).any(axis=1)
        if mixed.any():
            group = np.zeros(len(table), np.int64)
            np.cumsum(~tie, out=group[1:])
            holds_words = np.zeros(int(group[-1]) + 1, bool)
            holds_words[group[1:][mixed]] = True
            pos = np.flatnonzero(holds_words[group])
            rest = stable[pos, 2:k]
            inner = np.lexsort(tuple(rest[:, j] for j in
                                     range(k - 3, -1, -1)) + (group[pos],))
            order[pos] = order[pos][inner]
            stable[pos] = table[order[pos]]
    return order, stable


def _merge_runs_numpy(runs: List[_Table]) -> _Table:
    """Runs of one lane width as one, in numpy: the rows one behind the
    other, put in order by :func:`_stable_key_order` (a merge of the
    runs, not a sort), then the counts summed over each word's rows."""
    table = tuple(np.concatenate([r[i] for r in runs]) for i in range(4))
    order, skeys = _stable_key_order(table[0], table[0].shape[1])
    return _reduce_ordered(table, order, skeys)


def _merge_runs(runs: List[_Table]) -> _Table:
    """A window's runs as one run: pairwise by the native two-pointer
    merge, level by level (a row is copied once a level, and a word that
    several runs hold collapses on the way), else in numpy."""
    k = max(r[0].shape[1] for r in runs)
    runs = [_pad_table(r, k) for r in runs]
    if len(runs) > 1 and (not _native.available() or runs[0][2].ndim > 1):
        return _merge_runs_numpy(runs)
    while len(runs) > 1:
        merged = [_merge2_native(runs[i], runs[i + 1])
                  for i in range(0, len(runs) - 1, 2)]
        if len(runs) % 2:
            merged.append(runs[-1])
        runs = merged
    return runs[0]


def _bytes_column(keys: np.ndarray) -> np.ndarray:
    """A [n, K] key table as n byte strings of 4K bytes, big-endian
    lanes, so that byte order is lane order: what ``np.searchsorted``
    can search."""
    return np.ascontiguousarray(keys.astype(">u4")).view(
        f"S{4 * keys.shape[1]}").ravel()


def _merge_into(table: _Table, run: _Table) -> _Table:
    """The merged table with one run merged in, no row of the table
    through a sort: the native two pointers, else each of the run's rows
    placed by binary search (its count added where the table holds the
    word, the row inserted where it does not)."""
    k = max(table[0].shape[1], run[0].shape[1])
    table, run = _pad_table(table, k), _pad_table(run, k)
    merged = _merge2_native(table, run)
    if merged is not None:
        # the output had room for every row of both: let that go
        return tuple(x.copy() for x in merged) \
            if len(merged[0]) < len(table[0]) + len(run[0]) else merged
    tkeys, tlens, tcnts, tparts = table
    tcol, rcol = _bytes_column(tkeys), _bytes_column(run[0])
    pos = np.searchsorted(tcol, rcol)
    held = pos < len(tcol)
    held[held] = tcol[pos[held]] == rcol[held]
    tcnts = tcnts.copy()  # a snapshot or a result may hold the old one
    tcnts[pos[held]] += run[2][held]
    new = np.flatnonzero(~held)
    if len(new) == 0:
        return tkeys, tlens, tcnts, tparts
    at = pos[new] + np.arange(len(new))
    old = np.ones(len(tkeys) + len(new), bool)
    old[at] = False
    out = []
    for told, rnew in zip((tkeys, tlens, tcnts, tparts), run):
        col = np.empty((len(old),) + told.shape[1:], told.dtype)
        col[old] = told
        col[at] = rnew[new]
        out.append(col)
    return tuple(out)


def _compact_window(table: Optional[_Table], window: List[_Table],
                    fields: dict, stats: dict) -> Tuple[_Table, float]:
    """One compaction, on whichever thread: the window's runs into one
    run, that run into the merged table; the table and the seconds it
    took.  Its ``compact`` span is all it writes to ``stats``
    (``compact_s``)."""
    with _span("compact", lane="merge", stats=stats,
               table_rows=0 if table is None else len(table[0]),
               **fields) as sp:
        run = _merge_runs(window)
        table = run if table is None else _merge_into(table, run)
        sp.set(rows_out=len(table[0]))
    return table, sp.elapsed_s


class _Compaction(threading.Thread):
    """One full window's compaction on a thread of its own, which ends
    with it: then ``table`` is the merged table, or ``error`` what the
    merge raised, for :meth:`PackedCounts._join` to raise in the
    caller."""

    def __init__(self, table: Optional[_Table], window: List[_Table],
                 fields: dict, stats: dict):
        super().__init__(name="dsi-merge-compact", daemon=True)
        self.table, self.error = None, None
        self._work = (table, window, fields, stats)

    def run(self) -> None:
        try:
            self.table = _compact_window(*self._work)[0]
        except BaseException as e:  # whatever it is: ``_join`` raises it
            self.error = e
        finally:
            self._work = None


class PackedCounts:
    """Word-count accumulator over packed-key row batches.

    ``add`` ingests per-device step outputs (keys [n, K] uint32, byte
    lengths, counts, reduce partitions).  The accumulator holds one
    merged table, sorted and distinct, and beside it the window: the
    batches added since the last compaction, each a run (rows that
    strictly increase: a device's step table is one as it arrives; any
    other batch is sorted and reduced on entry, alone).  When the
    window's rows reach ``compact_rows`` a compaction merges the
    window's runs into one and that one into the table, and the number
    of compactions follows the rows handed over, not the table's size.
    Nothing is sorted that arrived sorted, and the table is never sorted
    again.  ``finalize`` returns the merged table as a
    :class:`PackedWordCounts`, which equals the ``{word: (count,
    reduce_partition)}`` dict the dict-based merge produced and builds
    it only when a caller needs Python objects.

    A window that fills is compacted on a thread of its own
    (:class:`_Compaction`: it lives for that one compaction, and
    ``native/mergeruns.cpp`` holds no interpreter lock), while the
    caller goes on with an empty window.  One compaction is in flight
    at most: a second full window, ``finalize``, ``snapshot`` and
    ``restore`` first wait for it, in a ``merge_wait`` span, and an
    exception it raised is raised there or in the next ``add``, in the
    caller (``close`` waits for it and drops it).  So host memory is
    O(vocabulary + two windows), never O(corpus) (a window of 2^21 rows
    at 4 key lanes is 67 MB).  What is left when the caller asks for
    the table, the last, partial window, is compacted on the caller's
    thread; an accumulator whose window never fills starts no thread.
    The result is the same bit for bit, whichever thread merged: the
    same merges in the same order over exact integer sums.

    ``stats`` (an engine's scope, else a dict of the accumulator's own)
    takes what the merge costs.  Five counters that repeat exactly for
    one input, with the native library or without: ``merge_rows_in``
    (rows handed to ``add``), ``merge_runs_in`` (batches handed to
    ``add``), ``merge_runs_unsorted`` (those that had to be sorted on
    entry), ``merge_rows_sorted`` (rows handed to an ordering routine:
    the rows of each unsorted batch, and a window's rows at its
    compaction; the merged table's rows never) and ``merge_compacts``;
    ``merge_compacts_async`` of them were handed to a thread.  And the
    seconds of the ``compact`` spans, on whichever thread
    (``compact_s``), of those on the caller's own thread and its
    ``merge_wait`` spans (``compact_caller_s``: the part of
    ``compact_s`` the caller was held for) and of the ``decode`` span
    (``finalize_decode_s``, 0.0 until the result is decoded), and
    ``finalize_decoded_keys`` (spellings turned into ``str``).  The
    caller writes every key but ``compact_s``, which is the thread's
    while a compaction is in flight.
    """

    def __init__(self, compact_rows: int = 1 << 21,
                 stats: Optional[dict] = None, *, decimals: int = 0):
        self._decimals = decimals  # of the result's numbers as printed
        self._table: Optional[_Table] = None
        self._window: List[_Table] = []
        self._pending = 0  # rows of the window
        self._unsorted = 0  # batches of the window sorted on entry
        self._inflight: Optional[_Compaction] = None  # it has the table
        self._compact_rows = max(1, compact_rows)
        self.stats = {} if stats is None else stats
        for key in ("merge_rows_in", "merge_rows_sorted", "merge_compacts",
                    "merge_runs_in", "merge_runs_unsorted",
                    "merge_compacts_async"):
            self.stats.setdefault(key, 0)
        self.stats.setdefault("compact_caller_s", 0.0)

    def add(self, keys: np.ndarray, lens: np.ndarray, cnts: np.ndarray,
            parts: np.ndarray) -> None:
        if len(keys) == 0:
            return
        self._join()  # what a compaction in flight raised, raised here
        self.stats["merge_rows_in"] += len(keys)
        self.stats["merge_runs_in"] += 1
        run, arrived_sorted = _own_run(keys, lens, cnts, parts)
        if not arrived_sorted:
            self._unsorted += 1
            self.stats["merge_runs_unsorted"] += 1
            self.stats["merge_rows_sorted"] += len(keys)
        self._window.append(run)
        self._pending += len(run[0])
        if self._pending >= self._compact_rows:
            self._join(wait=True)
            job = _Compaction(self._table, *self._take_window(), self.stats)
            job.start()
            self._inflight, self._table = job, None
            self.stats["merge_compacts_async"] += 1

    def add_packed_step(self, packed: np.ndarray, n_uniques,
                        kk: int) -> None:
        """Ingest one pulled step tensor ``[n_dev, mp, kk+3]`` (the
        ``shuffle._slice_pack`` layout: kk key lanes + len/count/partition
        columns), taking the first ``n_uniques[d]`` rows of each device's
        table: a run each, the step program having sorted and grouped
        them.  One call per stream step — the merge phase the pipelined
        engine (parallel/streaming.py) runs on the host while later
        steps' kernels are still in flight on device.  A tensor of
        ``kk+4`` lanes carries sums of two, low then high, where a count
        has one; one of ``kk+2+2m`` lanes m such sums a key, side by side
        (the join's revenue, rank and rows), and the table's counts are
        then ``[n, m]``."""
        sums = (packed.shape[2] - kk - 2) // 2  # 0: a count of one lane
        for d in range(packed.shape[0]):
            nu = int(n_uniques[d])
            r = packed[d, :nu]
            cnts = r[:, kk + 1]
            if sums:
                wide = r[:, kk + 1:kk + 1 + 2 * sums].astype(np.int64)
                cnts = wide[:, 0::2] | (wide[:, 1::2] << 32)
                if sums == 1:
                    cnts = cnts[:, 0]
            self.add(r[:, :kk], r[:, kk], cnts, r[:, -1])

    def _take_window(self) -> Tuple[List[_Table], dict]:
        """The window, to whoever compacts it, with its ``compact``
        span's fields; the next one is empty.  The counters of its
        compaction follow the rows alone and are counted here."""
        window = self._window
        fields = {"rows_in": self._pending, "runs_in": len(window),
                  "runs_unsorted": self._unsorted}
        self.stats["merge_rows_sorted"] += self._pending
        self.stats["merge_compacts"] += 1
        self._window, self._pending, self._unsorted = [], 0, 0
        return window, fields

    def _join(self, wait: bool = False) -> None:
        """The compaction in flight, if it is over or ``wait``: its
        table taken, what it raised raised in the caller."""
        job = self._inflight
        if job is None:
            return
        if job.is_alive():
            if not wait:
                return
            with _span("merge_wait", lane="merge", stats=self.stats,
                       key="compact_caller_s"):
                job.join()
        self._inflight = None
        if job.error is not None:
            raise job.error
        self._table = job.table

    def _compact(self) -> None:
        """The table, whole: the compaction in flight waited for, then
        the window's runs into one run and that run into the table, on
        the caller's thread."""
        self._join(wait=True)
        if self._window:
            self._table, took = _compact_window(
                self._table, *self._take_window(), self.stats)
            self.stats["compact_caller_s"] += took

    def close(self) -> None:
        """For a caller that gives the accumulator up without its result
        (a job that failed, or fell back to the host path): the
        compaction in flight is waited for and dropped, so that no thread
        is left behind."""
        job, self._inflight = self._inflight, None
        if job is not None:
            job.join()

    def finalize(self) -> "PackedWordCounts":
        """The merged table as the job's result, after the last
        compaction: no spelling is decoded and no Python object per word
        is built here."""
        self._compact()
        if self._table is None:
            return PackedWordCounts(stats=self.stats,
                                    decimals=self._decimals)
        return PackedWordCounts(*self._table, stats=self.stats,
                                decimals=self._decimals)

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def snapshot(self) -> Dict[str, np.ndarray]:
        """Checkpoint image: the merged table as four arrays, compacted
        first so the image is bounded by vocabulary, not by the window.
        Empty accumulator -> empty dict (no keys saved)."""
        self._compact()
        if self._table is None:
            return {}
        keys, lens, cnts, parts = self._table
        return {"keys": keys, "lens": lens, "cnts": cnts, "parts": parts}

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        """Load a :meth:`snapshot` image, replacing any current state:
        the image is the merged table (an image whose rows do not
        increase, one an earlier layout wrote of a single batch, is
        sorted once).  Final results are invariant to how the same
        (word, count) contributions were buffered, so a restored
        accumulator finalizes bit-identically to the uninterrupted
        one."""
        self._join(wait=True)
        self._table, self._window = None, []
        self._pending = self._unsorted = 0
        if not arrays or "keys" not in arrays or len(arrays["keys"]) == 0:
            return
        self._table, arrived_sorted = _own_run(
            arrays["keys"], arrays["lens"], arrays["cnts"], arrays["parts"])
        if not arrived_sorted:
            self.stats["merge_rows_sorted"] += len(arrays["keys"])


class PackedWordCounts(Mapping):
    """A word count's result: the merged table itself, as a read-only
    ``{word: (count, reduce_partition)}`` mapping.

    ``skeys`` ([n, K] uint32, rows strictly increasing: big-endian
    zero-padded lanes, so lane order is byte order is ``str`` order for
    the ASCII a key holds: a word's letters, an aggregation key's
    printable bytes), ``lens`` (bytes a word), ``cnts`` (int64) and
    ``parts`` are per word.  ``decimals`` makes a count a sum of
    10^-decimals units, printed ``<integer part>.<decimals digits>``.
    Counts of two dimensions, ``[n, 3]``, are a sum, a second sum and the
    rows a key (the join's): a row prints the sum and the second sum's
    mean over the rows, both as decimals, the mean by integer division
    (truncated, exact) and a mapping's value is ``([sum, sum, rows],
    partition)``.  ``len()`` and
    :meth:`render_partition` (what ``shuffle.write_partitioned_output``
    commits) read the arrays alone.  The first keyed access, iteration
    or comparison decodes every spelling once (``decode_packed``) into
    the dict the merge used to return, and keeps it: the ``decode`` span
    (``finalize_decode_s`` of ``stats``, the accumulator's scope) and
    ``finalize_decoded_keys`` say when that happened and for how many
    words; a ``wcstream`` job reads 0.0 and 0.
    """

    def __init__(self, skeys: Optional[np.ndarray] = None,
                 lens: Optional[np.ndarray] = None,
                 cnts: Optional[np.ndarray] = None,
                 parts: Optional[np.ndarray] = None,
                 stats: Optional[dict] = None, decimals: int = 0):
        self.decimals = decimals
        if skeys is None:
            skeys = np.zeros((0, 1), np.uint32)
            lens = parts = np.zeros(0, np.int32)
            cnts = np.zeros(0, np.int64)
        self.skeys, self.lens, self.cnts, self.parts = (skeys, lens, cnts,
                                                        parts)
        self.stats = {} if stats is None else stats
        self.stats.setdefault("finalize_decode_s", 0.0)
        self.stats.setdefault("finalize_decoded_keys", 0)
        self._dict: Optional[Dict[str, Tuple[int, int]]] = None
        self._bytes: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.skeys)

    def __repr__(self) -> str:
        return (f"PackedWordCounts(words={len(self)}, "
                f"lanes={self.skeys.shape[1]}, "
                f"decoded={self._dict is not None})")

    def to_dict(self) -> Dict[str, Tuple[int, int]]:
        """The whole table as Python objects, built once and kept."""
        if self._dict is None:
            n = len(self.skeys)
            with _span("decode", lane="host", stats=self.stats,
                       key="finalize_decode_s", keys=n):
                words = decode_packed(self.skeys, self.lens, n)
                self._dict = {w: (c, p) for w, c, p
                              in zip(words, self.cnts.tolist(),
                                     self.parts.tolist())}
            self.stats["finalize_decoded_keys"] += n
        return self._dict

    def __getitem__(self, word: str) -> Tuple[int, int]:
        return self.to_dict()[word]

    def __iter__(self):
        return iter(self.to_dict())

    def __contains__(self, word) -> bool:
        return word in self.to_dict()

    def keys(self):
        return self.to_dict().keys()

    def items(self):
        return self.to_dict().items()

    def values(self):
        return self.to_dict().values()

    def __eq__(self, other) -> bool:
        if isinstance(other, PackedWordCounts):
            other = other.to_dict()
        elif not isinstance(other, Mapping):
            return NotImplemented
        return self.to_dict() == other

    def render_partition(self, r: int) -> bytes:
        """Partition ``r``'s ``mr-out`` bytes, ``"word count\\n"`` a row
        in table order (the order ``sorted`` gives the spellings), from
        the arrays: one [rows, longest word + 1 + most digits + 1] byte
        matrix and a keep-mask, a few MB a partition."""
        rows = np.flatnonzero(self.parts == r)
        if len(rows) == 0:
            return b""
        if self._bytes is None:
            # [n, 4K] uint8: big-endian lanes are the spelling's bytes
            self._bytes = np.ascontiguousarray(
                self.skeys.astype(">u4")).view(np.uint8)
        numbers = self.cnts[rows]
        if numbers.ndim == 2:
            numbers = np.stack([numbers[:, 0], self.means(rows)], axis=1)
        return _word_number_rows(self._bytes, rows, self.lens[rows],
                                 numbers, 0x0A, self.decimals).tobytes()

    def means(self, rows: np.ndarray) -> np.ndarray:
        """The second sum's mean over the rows' count, for ``rows`` of a
        table of three counts a key, in 10^-decimals units: ``sum *
        10^decimals // count`` without the product, which 64 bits may
        not hold."""
        _, total, n = self.cnts[rows].T
        unit = 10 ** self.decimals
        return total // n * unit + total % n * unit // n


#: A buffer of posting rows is merged as the runs it arrives in where
#: they hold this many rows each on average, and is sorted into one run on
#: entry to the group where they are shorter.  ``scripts/group_micro.py``
#: on the chip's host (PERF.md section 6, PR 48): the two cost the same at
#: about 4 rows a run; at 8 the merge wins by a sixth, and the
#: tournament's seats stay under half the rows' own bytes.
_RUN_ROWS_MIN = 8


def _run_cuts(rows: np.ndarray, kk: int) -> Optional[np.ndarray]:
    """The rows of a ``[n, kk + 4]`` buffer at which a new run starts
    (the row sorts before the one above it in its ``kk`` key lanes; an
    equal word is no descent), found in one pass over the buffer; None
    where they are so many that the runs fall short of
    :data:`_RUN_ROWS_MIN` rows each."""
    cap = len(rows) // _RUN_ROWS_MIN
    cuts = np.empty(cap, np.int64)
    found = _native.run_cuts(rows, kk, cuts)
    if found is None:
        prev, nxt = rows[:-1, :kk], rows[1:, :kk]
        first = (prev != nxt).argmax(axis=1)
        at = np.arange(len(prev))
        cuts = np.flatnonzero(nxt[at, first] < prev[at, first]) + 1
        found = len(cuts)
    return None if found > cap else cuts[:found]


def _merge_posting_runs(bufs: List[np.ndarray], cuts: List[np.ndarray],
                        kk: int) -> Tuple[np.ndarray, ...]:
    """Buffers of posting rows, each the runs its ``cuts`` divide it
    into, as the grouped index's columns ``(skeys, lens, parts, starts,
    tfs, docs)``: the stable merge of the runs, the earlier run first
    among equal words.  The tournament of ``native/mergeruns.cpp``,
    which reads every row once and writes the columns; without the
    library the rows one behind the other, :func:`_stable_key_order`
    (a merge of the runs it finds and ONE gather of whole rows), the
    columns cut from the rows in order."""
    if _native.available():
        n = sum(map(len, bufs))
        out = (np.empty((n, kk), np.uint32), np.empty(n, np.uint32),
               np.empty(n, np.uint32), np.empty(n, np.int64),
               np.empty(n, np.uint32), np.empty(n, np.uint32))
        words = _native.merge_posting_runs(bufs, cuts, kk, out)
        # the per-word columns had room for a word a row: let that go
        return tuple(x[:words].copy() for x in out[:4]) + out[4:]
    rows = np.concatenate(bufs) if len(bufs) > 1 else bufs[0]
    if len(bufs) + sum(map(len, cuts)) > 1:
        rows = _stable_key_order(rows, kk)[1]
    starts = _group_starts(rows[:, :kk])
    return (np.ascontiguousarray(rows[starts, :kk]), rows[starts, kk],
            rows[starts, kk + 3], starts,
            np.ascontiguousarray(rows[:, kk + 1]),
            np.ascontiguousarray(rows[:, kk + 2]))


class PostingsTable:
    """TF-IDF accumulator over packed (word, tf, doc, part) row batches.

    Rows are retained raw (uint32, ~16+4K bytes each — several times
    smaller than the Python tuple lists they replace), in the order they
    were handed over, and grouped once at ``finalize``: a wave's rows
    leave the device in word order, so the buffers are runs and the
    index is their stable merge, a word's postings in wave order, with
    no row through a sort; then one bulk spelling decode, and per-word
    postings sliced out with C-speed ``tolist``/``zip``.  Output matches
    the dict-based walk: ``{word: (reduce_partition, [(doc_index, tf),
    ...])}``.
    """

    def __init__(self):
        self._bufs: List[np.ndarray] = []
        self._kk: int | None = None

    def add(self, rows: np.ndarray, kk: int) -> None:
        """Ingest [n, kk+4] rows: kk key lanes + (len, tf, doc, part)."""
        if len(rows) == 0:
            return
        if self._kk is None:
            self._kk = kk
        elif kk != self._kk:  # one retry rung per table by contract
            raise ValueError(f"mixed key widths: {self._kk} vs {kk}")
        self._bufs.append(np.array(rows, dtype=np.uint32))

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def snapshot(self) -> Dict[str, np.ndarray]:
        """Checkpoint image: every buffered row, concatenated in
        insertion order — order is part of the postings contract
        (per-word doc order is an engine invariant), and the stable
        merge of the runs at finalize preserves it (the image's one
        buffer holds the same runs, found again from its rows), so a
        restored table groups bit-identically."""
        if not self._bufs:
            return {}
        rows = (np.concatenate(self._bufs) if len(self._bufs) > 1
                else self._bufs[0])
        return {"rows": rows, "kk": np.array(self._kk, dtype=np.int64)}

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        if not arrays or "rows" not in arrays or len(arrays["rows"]) == 0:
            self._bufs, self._kk = [], None
            return
        self._kk = int(arrays["kk"])
        self._bufs = [np.array(arrays["rows"], dtype=np.uint32)]

    def finalize(self, stats: Optional[dict] = None
                 ) -> Dict[str, Tuple[int, List[Tuple[int, int]]]]:
        return self.finalize_packed(stats).to_dict()

    def finalize_packed(self, stats: Optional[dict] = None
                        ) -> "PackedPostings":
        """Group without pythonizing: the full postings stay as numpy
        arrays (~32 B/posting) instead of ~250 B of tuples/lists/ints per
        posting — at GB scale the dict materialization alone was ~2 GB of
        the soak's peak RSS.  Use ``to_dict()``
        (or ``lookup_many`` for a few words) only at scales that afford
        it.  Finding the runs and merging them is the ``group`` span
        (``group_s`` of ``stats``, the engine's scope), which also takes
        the table's ``postings_rows`` and ``index_terms``, the runs that
        were merged (``group_runs``) and the rows of the buffers that did
        not arrive in runs and went through a sort first
        (``group_rows_sorted``: 0 where every wave's rows came as the
        device leaves them)."""
        n_rows = sum(len(b) for b in self._bufs)
        with _span("group", lane="merge", stats=stats, key="group_s",
                   rows=n_rows) as sp:
            out, runs, rows_sorted = self._group()
            sp.set(terms=len(out), runs=runs, rows_sorted=rows_sorted)
        if stats is not None:
            stats["postings_rows"] = n_rows
            stats["index_terms"] = len(out)
            stats["group_runs"] = runs
            stats["group_rows_sorted"] = rows_sorted
        return out

    def _group(self) -> Tuple["PackedPostings", int, int]:
        """The index, the runs it was merged from, and the rows that were
        sorted first: a buffer is the runs its rows show, or, where they
        show too many, one run by a stable sort of the buffer alone."""
        if not self._bufs:
            return PackedPostings(0), 0, 0
        kk = self._kk
        bufs, cuts, rows_sorted = [], [], 0
        for rows in self._bufs:
            rows = np.ascontiguousarray(rows)
            cut = _run_cuts(rows, kk)
            if cut is None:
                rows = _stable_key_order(rows, kk)[1]
                rows_sorted += len(rows)
                cut = np.zeros(0, np.int64)
            bufs.append(rows)
            cuts.append(cut)
        out = PackedPostings(kk)
        (out.skeys, out.lens, out.parts, out.starts, out.tfs,
         out.docs) = _merge_posting_runs(bufs, cuts, kk)
        out.ends = np.append(out.starts[1:], len(out.tfs))
        return out, len(bufs) + sum(map(len, cuts)), rows_sorted


class PackedPostings:
    """Grouped TF-IDF postings as numpy tables (lexicographic word
    order).  ``skeys/lens/parts/starts/ends`` are per-unique-word;
    ``tfs/docs`` are the full postings, ``starts[i]:ends[i]`` slicing
    word i's.

    With the documents' names (:meth:`named`) the table is also an
    inverted index that ``shuffle.write_partitioned_output`` commits:
    :meth:`render_partition` gives a partition's ``mr-out`` bytes from
    the arrays, with no Python object a posting."""

    __slots__ = ("kk", "skeys", "lens", "parts", "starts", "ends",
                 "tfs", "docs", "_be", "doc_names", "_by_name")

    def __init__(self, kk: int):
        self.kk = kk
        self._be = None  # lazy big-endian key view (_be_keys)
        self.doc_names: Optional[List[str]] = None
        self._by_name = None  # lazy (named, render_partition)
        self.skeys = np.zeros((0, max(kk, 1)), np.uint32)
        self.lens = np.zeros(0, np.uint32)
        self.parts = np.zeros(0, np.uint32)
        self.starts = np.zeros(0, np.int64)
        self.ends = np.zeros(0, np.int64)
        self.tfs = np.zeros(0, np.uint32)
        self.docs = np.zeros(0, np.uint32)

    def __len__(self) -> int:
        return len(self.skeys)

    @property
    def n_postings(self) -> int:
        return len(self.tfs)

    def postings_per_word(self) -> np.ndarray:
        return self.ends - self.starts

    def _be_keys(self) -> np.ndarray:
        """The key lanes big-endian ([n, K] ``>u4``: lane order is byte
        order), built once: the table is immutable after
        ``finalize_packed``."""
        if self._be is None:
            self._be = np.ascontiguousarray(self.skeys.astype(">u4"))
        return self._be

    def lookup_many(self, words) -> Dict[str, Tuple[int, List[Tuple[int,
                                                                    int]]]]:
        """{word: (part, [(doc, tf), ...])} for just these words (absent
        words omitted) — dict-shaped output without pythonizing the whole
        table.  Binary search per word over the lexsorted big-endian key
        bytes (uint32 lanes are big-endian packed, so byte order == lane
        order)."""
        n = len(self.skeys)
        if n == 0:
            return {}
        be = self._be_keys()
        width = 4 * self.kk
        out: Dict[str, Tuple[int, List[Tuple[int, int]]]] = {}
        for w in words:
            try:
                raw = w.encode("ascii")
            except UnicodeEncodeError:
                continue  # non-ASCII cannot be in the table: omit, never
                # alias to an ASCII-stripped spelling
            if not raw or len(raw) > width:
                continue
            q = raw.ljust(width, b"\x00")
            lo, hi = 0, n
            while lo < hi:
                mid = (lo + hi) // 2
                if be[mid].tobytes() < q:
                    lo = mid + 1
                else:
                    hi = mid
            if lo >= n or be[lo].tobytes() != q \
                    or int(self.lens[lo]) != len(raw):
                continue
            s, e = int(self.starts[lo]), int(self.ends[lo])
            out[w] = (int(self.parts[lo]),
                      list(zip(self.docs[s:e].tolist(),
                               self.tfs[s:e].tolist())))
        return out

    @classmethod
    def from_postings(cls, postings: Dict[str, Tuple[int, List[int]]]
                      ) -> "PackedPostings":
        """The table of ``{word: (part, [doc, ...])}``, an indexer's
        result that is already Python objects (the plan layer's staged
        baseline and its stage commits): term frequencies read 1."""
        words = sorted(postings)
        if not words:
            return cls(0)
        raw = [w.encode("ascii") for w in words]
        kk = (max(map(len, raw)) + 3) // 4
        out = cls(kk)
        out.skeys = np.frombuffer(
            b"".join(b.ljust(4 * kk, b"\x00") for b in raw),
            dtype=">u4").reshape(len(raw), kk).astype(np.uint32)
        out.lens = np.array([len(b) for b in raw], np.uint32)
        out.parts = np.array([postings[w][0] for w in words], np.uint32)
        df = np.array([len(postings[w][1]) for w in words], np.int64)
        out.ends = np.cumsum(df)
        out.starts = out.ends - df
        out.docs = np.fromiter(
            (d for w in words for d in postings[w][1]), np.uint32,
            int(out.ends[-1]))
        out.tfs = np.ones(len(out.docs), np.uint32)
        return out

    def named(self, doc_names) -> "PackedPostings":
        """The same table, its documents named: ``doc_names[d]`` is
        document ``d``'s name in the committed index."""
        self.doc_names = list(doc_names)
        self._by_name = None
        return self

    def _ranks_in_order(self, rank_of: np.ndarray):
        """``(ranks, offs)`` straight from the table where every word's
        documents already stand in name order, no name twice: what a walk
        in document order over names that sort as their ordinals leaves
        (``planrun --pack-docs`` over ``d00000.txt``, ``d00001.txt``,
        ...), 10^7 postings checked in one pass and none moved.  ``(None,
        None)`` where a word's documents do not."""
        if not (self.ends > self.starts).all():
            return None, None
        rank_of = rank_of.astype(_index_dtype(len(rank_of)))
        # A table in another order (a walk longest document first) shows
        # it within its first words: the head is looked at alone before
        # the whole is gathered.
        for n in (min(len(self.docs), 1 << 14), len(self.docs)):
            ranks = rank_of[self.docs[:n]]
            falls = ranks[1:] <= ranks[:-1]
            # a new word may start lower
            falls[self.ends[:np.searchsorted(self.ends, n)] - 1] = False
            if falls.any():
                return None, None
        offs = np.empty(len(self.skeys) + 1, np.int64)
        offs[0] = 0
        offs[1:] = self.ends
        return ranks, offs

    def _ranks_sorted(self, rank_of: np.ndarray, n_names: int):
        """``(ranks, offs)`` by one sort: every posting as one number,
        word * names + rank, in the narrowest index type (the arrays are
        postings long, and what they allocate is most of a commit's
        time): the sort orders every word's documents by name, and what
        two documents of one name would repeat is dropped."""
        n_words = len(self.skeys)
        idx = _index_dtype(n_words * n_names)
        pairs = np.repeat(np.arange(n_words, dtype=idx),
                          self.ends - self.starts)
        pairs *= idx(n_names)
        pairs += rank_of.astype(idx)[self.docs]
        pairs.sort()
        if len(pairs):
            fresh = np.empty(len(pairs), bool)
            fresh[0] = True
            np.not_equal(pairs[1:], pairs[:-1], out=fresh[1:])
            if not fresh.all():
                pairs = pairs[fresh]
        word_of, ranks = np.divmod(pairs, idx(n_names))
        offs = np.zeros(n_words + 1, np.int64)
        np.cumsum(np.bincount(word_of, minlength=n_words), out=offs[1:])
        return ranks, offs

    def _named_postings(self):
        """What :meth:`render_partition` reads, built once: every
        word's documents as ranks among the sorted, unique names,
        sorted and unique within the word (``ranks``, word ``i``'s at
        ``offs[i]:offs[i + 1]``), and the names as one byte table, each
        followed by a comma (``table``, name ``j`` at ``name_offs[j]``,
        ``name_lens[j]`` bytes with its comma)."""
        if self._by_name is None:
            if self.doc_names is None:
                raise ValueError("PackedPostings.named() first: an index "
                                 "line names its documents")
            if len(self.docs) and int(self.docs.max()) >= len(
                    self.doc_names):
                raise ValueError(
                    f"document {int(self.docs.max())} of a posting has no "
                    f"name among {len(self.doc_names)}")
            names = sorted(set(self.doc_names))
            rank = {name: j for j, name in enumerate(names)}
            rank_of = np.array([rank[name] for name in self.doc_names],
                               np.int64)
            ranks, offs = self._ranks_in_order(rank_of)
            if ranks is None:
                ranks, offs = self._ranks_sorted(rank_of, len(names))
            raw = [name.encode("utf-8") + b"," for name in names]
            name_lens = np.array([len(b) for b in raw], np.int64)
            self._by_name = (
                ranks, offs, np.frombuffer(b"".join(raw), np.uint8),
                np.cumsum(name_lens) - name_lens, name_lens,
                self._be_keys().view(np.uint8))
        return self._by_name

    def render_partition(self, r: int) -> bytes:
        """Partition ``r``'s ``mr-out`` bytes of the inverted index,
        ``"word n doc,doc,...\n"`` a word in table order (the order
        ``sorted`` gives the spellings), the documents' names sorted and
        unique and ``n`` their number (``apps/indexer.Reduce``): from
        the arrays.  The output is one gather out of one source buffer
        (each word's ``"word n "``, then the name table), a piece a
        word and a piece a posting."""
        ranks, offs, table, name_offs, name_lens, key_bytes = \
            self._named_postings()
        rows = np.flatnonzero(self.parts == r)
        if len(rows) == 0:
            return b""
        # the heads, "word n ", as PackedWordCounts renders its rows
        df = offs[rows + 1] - offs[rows]
        heads = _word_number_rows(key_bytes, rows, self.lens[rows], df,
                                  0x20)
        head_lens = (self.lens[rows].astype(np.int64) + 2
                     + np.searchsorted(_POW10, df, side="right") + 1)
        # the pieces in output order: word k's head at piece
        # (postings before it) + k, its postings behind it
        before = np.cumsum(df) - df
        picked = np.repeat(offs[rows] - before, df) \
            + np.arange(int(df.sum()))
        n_pieces = len(rows) + len(picked)
        src = np.empty(n_pieces, np.int64)
        length = np.empty(n_pieces, np.int64)
        head_at = before + np.arange(len(rows))
        post_at = np.arange(len(picked)) \
            + np.repeat(np.arange(1, len(rows) + 1), df)
        src[head_at] = np.cumsum(head_lens) - head_lens
        length[head_at] = head_lens
        src[post_at] = len(heads) + name_offs[ranks[picked]]
        length[post_at] = name_lens[ranks[picked]]
        ends = np.cumsum(length)
        source = np.concatenate([heads, table])
        idx = _index_dtype(max(len(source), int(ends[-1])))
        gather = np.arange(int(ends[-1]), dtype=idx)
        gather += np.repeat((src - (ends - length)).astype(idx), length)
        out = source[gather]
        # a word's last comma is its line's end (every word of an
        # index has a posting, so the piece before a head is a posting)
        out[ends[np.append(head_at[1:], n_pieces) - 1] - 1] = 0x0A
        return out.tobytes()

    def to_dict(self) -> Dict[str, Tuple[int, List[Tuple[int, int]]]]:
        if len(self.skeys) == 0:
            return {}
        words = decode_packed(self.skeys, self.lens, len(self.skeys))
        tfs = self.tfs.tolist()
        docs = self.docs.tolist()
        out: Dict[str, Tuple[int, List[Tuple[int, int]]]] = {}
        for i, w in enumerate(words):
            s, e = int(self.starts[i]), int(self.ends[i])
            out[w] = (int(self.parts[i]), list(zip(docs[s:e], tfs[s:e])))
        return out
