"""Parallel mmap'd file ingest with readahead — the disk half of the
compressed-wire PR (ISSUE 13).

The streaming engines consume an *iterator of byte blocks*
(``parallel/streaming.py stream_files``): on the host-read-bound margins
of the stream row, every block is read INSIDE the pipeline's producer
thread — the read wall lands in ``materialize_s`` and serializes with
batch slicing.  This module moves it off: a small pool of reader
threads mmaps the input files and copies fixed-size segments out AHEAD
of the consumer (a bounded readahead window keeps memory O(readahead ×
block)), so by the time the batcher asks for block *i* its bytes are
already host-resident and ``materialize_s`` shrinks to the slicing work
the batcher actually owns.

The contract that makes this safe to drop into the checkpointed
engines: the yielded BYTE STREAM is exactly ``stream_files``' —
per-file bytes in order, a single ``b"\\n"`` separator between files —
and the engines' batchers are pure functions of the byte stream
(``batch_stream``/``batch_lines`` module docs), so cursors, checkpoint
offsets and ``skip_stream`` resume seeks stay byte-exact whatever the
reader count or block boundaries.  Only segment *scheduling* is
parallel; delivery order is total.

No jax, no numpy: importable by no-jax consumers (CLI arg parsing,
bench gating) and by the dsicheck bare-interpreter job.  Read-only by
construction — mmap ``ACCESS_READ`` with a seek/read fallback — so
there is nothing here for the raw-write rule to exempt.

Stats (``ParallelBlocks.ingest_stats()``; the engines fold them into
their metrics scope at release — ``parallel/pipeline.py
fold_source_stats``): ``ingest_readers``, ``ingest_blocks``,
``readahead_hit_pct`` (blocks already resident when the consumer asked
— the "did readahead actually run ahead" evidence), ``ingest_wait_s``
(consumer wall blocked on a block that was NOT ready).

:class:`ReadAheadDocs` is the same idea for an engine that takes whole
documents by ordinal and not a byte stream (the indexer's wave walk):
the files as a sequence, read ahead of the walk in the walk's order.
"""

from __future__ import annotations

import itertools
import mmap
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List, Optional, Sequence, Tuple

from dsi_tpu import native
from dsi_tpu.obs import span as _span

_READERS_ENV = "DSI_INGEST_READERS"
#: Default block size — matches ``stream_files``' 4 MiB.
DEFAULT_BLOCK_BYTES = 4 << 20


def ingest_readers_default(readers: Optional[int] = None) -> int:
    """Resolve the reader-pool width: an explicit value wins, else
    ``DSI_INGEST_READERS`` (default 0 = no pool, inline reads — the
    historical ``stream_files`` path, bit-identical by construction)."""
    if readers is None:
        try:
            readers = int(os.environ.get(_READERS_ENV, "0"))
        except ValueError:
            readers = 0
    return max(0, int(readers))


def serial_blocks(paths: Sequence[str],
                  block_bytes: int = DEFAULT_BLOCK_BYTES) -> Iterator[bytes]:
    """File contents as an in-order block stream with ``b"\\n"`` file
    separators — byte-identical to ``parallel/streaming.stream_files``
    (that module needs jax; this one is import-light for the CLIs'
    no-pool path)."""
    for i, p in enumerate(paths):
        if i:
            yield b"\n"
        with open(p, "rb") as f:
            while True:
                b = f.read(block_bytes)
                if not b:
                    break
                yield b


#: Segment plan entries: (path_index, offset, length) for file bytes,
#: or (-1, 0, 0) for the inter-file separator block.
_SEP = (-1, 0, 0)


def _plan_segments(paths: Sequence[str],
                   block_bytes: int) -> List[Tuple[int, int, int]]:
    segs: List[Tuple[int, int, int]] = []
    for i, p in enumerate(paths):
        if i:
            segs.append(_SEP)
        size = os.path.getsize(p)
        off = 0
        while off < size:
            n = min(block_bytes, size - off)
            segs.append((i, off, n))
            off += n
    return segs


class ParallelBlocks:
    """In-order block stream over ``paths`` read by ``readers`` threads
    with a bounded readahead window.

    Iterable (single pass).  Reader threads claim segment ordinals up to
    ``consumed + readahead`` and fill per-segment slots; the consumer
    yields slot *i* strictly in order, blocking only when the pool has
    not reached it yet (counted as a readahead miss).  Abandoning the
    iterator mid-stream (a tenant eviction, an engine unwinding on an
    error) tears the pool down via the generator's ``finally`` —
    threads are daemons and stop at their next claim check either way.
    """

    def __init__(self, paths: Sequence[str],
                 block_bytes: int = DEFAULT_BLOCK_BYTES,
                 readers: Optional[int] = None,
                 readahead: Optional[int] = None):
        self.paths = [str(p) for p in paths]
        self.block_bytes = max(1, int(block_bytes))
        self.readers = max(1, ingest_readers_default(readers))
        #: In-flight + ready-but-unconsumed segments the pool may hold:
        #: the memory bound (readahead × block_bytes) and the distance
        #: the pool can run ahead of the consumer.
        self.readahead = (max(2, 2 * self.readers) if readahead is None
                          else max(1, int(readahead)))
        self._segs = _plan_segments(self.paths, self.block_bytes)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._slots: dict = {}
        self._next_claim = 0
        self._consumed = 0
        self._closed = False
        self._err: Optional[BaseException] = None
        self._threads: List[threading.Thread] = []
        self._mmaps: dict = {}
        self._hits = 0
        self._misses = 0
        self._wait_s = 0.0

    # ── reading (reader threads) ──

    def _read_segment(self, seg: Tuple[int, int, int]) -> bytes:
        pi, off, n = seg
        if pi < 0:
            return b"\n"
        mm = self._file_map(pi)
        if mm is not None:
            return bytes(mm[off:off + n])
        with open(self.paths[pi], "rb") as f:  # mmap-refusing file
            f.seek(off)
            return f.read(n)

    def _file_map(self, pi: int):
        """One shared read-only mmap per file, opened lazily (None for
        files mmap refuses — zero-length, special files — which fall
        back to seek/read)."""
        with self._lock:
            if pi in self._mmaps:
                return self._mmaps[pi]
        try:
            with open(self.paths[pi], "rb") as f:
                mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (ValueError, OSError):
            mm = None
        with self._lock:
            # First opener wins; a racing duplicate closes itself.
            cur = self._mmaps.setdefault(pi, mm)
            if cur is not mm and mm is not None:
                mm.close()
            return cur

    def _reader_loop(self) -> None:
        while True:
            with self._cond:
                while (not self._closed
                       and (self._next_claim >= len(self._segs)
                            or self._next_claim
                            >= self._consumed + self.readahead)):
                    if self._next_claim >= len(self._segs):
                        return
                    self._cond.wait(0.2)
                if self._closed:
                    return
                i = self._next_claim
                self._next_claim += 1
            try:
                data = self._read_segment(self._segs[i])
            except BaseException as e:
                with self._cond:
                    self._err = self._err or e
                    self._cond.notify_all()
                return
            with self._cond:
                self._slots[i] = data
                self._cond.notify_all()

    def _start(self) -> None:
        if self._threads:
            return
        n = min(self.readers, max(1, len(self._segs)))
        for r in range(n):
            t = threading.Thread(target=self._reader_loop, daemon=True,
                                 name=f"dsi-ingest-reader-{r}")
            self._threads.append(t)
            t.start()

    # ── consuming ──

    def __iter__(self) -> Iterator[bytes]:
        if self._closed:
            # Single-pass source: after exhaustion/abandonment no reader
            # will ever fill another slot — a second pass would wait
            # forever on slot 0.  Fail loudly instead of hanging.
            raise RuntimeError("ParallelBlocks is single-pass and was "
                               "already consumed/closed; construct a "
                               "fresh pool to re-read")
        self._start()
        try:
            for i in range(len(self._segs)):
                with self._cond:
                    if i in self._slots:
                        self._hits += 1
                    else:
                        self._misses += 1
                        t0 = time.perf_counter()
                        while i not in self._slots and self._err is None:
                            self._cond.wait(0.2)
                        self._wait_s += time.perf_counter() - t0
                    if self._err is not None and i not in self._slots:
                        raise self._err
                    data = self._slots.pop(i)
                    self._consumed = i + 1
                    self._cond.notify_all()
                yield data
        finally:
            self.close()

    def close(self) -> None:
        """Stop the pool and release the file maps.  Idempotent; called
        by the iterator's own ``finally`` (stream end OR mid-stream
        abandonment)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            maps, self._mmaps = self._mmaps, {}
        for t in self._threads:
            t.join(timeout=5.0)
        for mm in maps.values():
            if mm is not None:
                try:
                    mm.close()
                except (ValueError, OSError):
                    pass

    def ingest_stats(self) -> dict:
        """The engines' release-time fold (``fold_source_stats``):
        schema-pinned keys only (``obs/registry.py SCHEMA_KEYS``)."""
        asked = self._hits + self._misses
        return {"ingest_readers": self.readers,
                "ingest_blocks": asked,
                "readahead_hit_pct": round(100.0 * self._hits / asked, 1)
                if asked else 0.0,
                "ingest_wait_s": round(self._wait_s, 4)}


def open_blocks(paths: Sequence[str],
                readers: Optional[int] = None,
                block_bytes: int = DEFAULT_BLOCK_BYTES,
                readahead: Optional[int] = None):
    """The one ingest entry point the CLIs/bench use: a
    :class:`ParallelBlocks` pool when the resolved reader count
    (``--ingest-readers`` / ``DSI_INGEST_READERS``) is >= 1, else the
    plain in-order generator — byte-identical streams either way."""
    n = ingest_readers_default(readers)
    if n >= 1:
        return ParallelBlocks(paths, block_bytes=block_bytes,
                              readers=n, readahead=readahead)
    return serial_blocks(paths, block_bytes=block_bytes)


#: Reader threads of a :class:`ReadAheadDocs` pool.  From the chip
#: host's reading of a page job's own 12,000 files of 1-16 KiB at 1 to
#: 32 threads (``scripts/docread_micro.py``; PERF.md §5 has the table):
#: two readers that make their file calls outside the interpreter
#: (``native.read_files``) deliver the 12,000 in 1.3 s, under the 1.45 s
#: the walk takes; one does not, and more only take the interpreter's
#: lock from the dispatch loop more often.
DOC_READ_THREADS = 2
#: Threads that take its lengths, before the first stage (the same
#: script's ``native_stat_name_<threads>_s``).
DOC_STAT_THREADS = 4

#: Directories a :class:`ReadAheadDocs` keeps open, to name its files
#: from (``dir_fd``); the files of any further directory go by path.
_MAX_DIR_FDS = 64
_DIR_FD = {os.open, os.stat} <= os.supports_dir_fd

#: What a reader thread claims at once, and reads in one call that holds
#: no interpreter lock (``native.read_files``): so many documents, or
#: fewer once they come to so many bytes.  Runs of 256 and of 1,024
#: slowed the page job's walk by a third and more, runs of 32 left the
#: readers too near the packer (PERF.md §6, PR 42).
_RUN_DOCS, _RUN_BYTES = 64, 1 << 20


def _changed(path: str, want: int) -> OSError:
    return OSError(f"{path}: no longer the {want} bytes it was when the "
                   "job began")


def _dir_fd(dfd: int) -> Optional[int]:
    """``os``'s spelling of a directory descriptor, or of none (-1: the
    name is a path), which is ``docread.cpp``'s."""
    return None if dfd < 0 else dfd


def _read_file(name: bytes, dfd: int, want: int, path: str) -> bytes:
    """What ``native.read_files`` does with one file, where there is no
    library."""
    fd = os.open(name, os.O_RDONLY, dir_fd=_dir_fd(dfd))
    try:
        # One byte more than the length: a file that grew shows it, and
        # one that did not is at its end with this one call.
        data = os.read(fd, want + 1)
        while 0 < len(data) < want:  # a short read
            more = os.read(fd, want + 1 - len(data))
            if not more:
                break
            data += more
    finally:
        os.close(fd)
    if len(data) != want:
        raise _changed(path, want)
    return data


class ReadAheadDocs:
    """The files of ``paths`` as a sequence of documents (``len``,
    ``lengths``, ``docs[i]`` -> bytes) whose bytes a small pool of reader
    threads fetches AHEAD of whoever walks it, in the order the walk
    will ask.

    ``lengths`` are taken at construction (by as many threads), so a
    wave plan exists before any byte is read.  :meth:`read_ahead` names
    the order and starts the pool; the first ``docs[i]`` starts it in
    document order if nobody has.  ``docs[i]`` returns the bytes if they
    are there; if not, it reads that one document itself, or waits for
    the reader that has it in hand.  A document once read is HELD, as the list this replaces
    held it: asking again, out of order, after a rung restart or a
    replay reads nothing twice.

    A file costs three calls (open, one read of its length and a byte,
    close) and its length one, each by the file's name from its
    directory, which is opened once: on a host whose every call walks
    the whole path anew that is half of what a file costs.  A reader
    makes them for a run of documents at a time in
    ``native.read_files``, outside the interpreter: a Python thread
    waits for the interpreter's lock after each call, behind a busy
    dispatch loop for longer than the call took (PERF.md §5).

    A file that cannot be read, or whose bytes are not the length taken
    at construction (it grew or was cut since), is an ``OSError`` for
    whoever asks next: the plan made from ``lengths`` would no longer
    describe the bytes.

    ``stats`` (``obs/registry.py`` spellings): ``read_docs`` (documents
    asked for, each counted once), ``read_ahead_hits`` (those that were
    there when first asked for), ``read_wait_s`` (seconds callers were
    held by a document that was not: the ``read_wait`` spans, on the
    caller's thread), ``read_threads`` (the pool's size, 0 if it never
    started).  :meth:`close` ends the pool; what is held stays readable.
    """

    def __init__(self, paths: Sequence[str]):
        self.paths = [str(p) for p in paths]
        self._dirs: dict = {}  # directory -> its fd, open until close()
        self._cond = threading.Condition()
        self._threads: List[threading.Thread] = []
        self._started = self._closed = False
        try:
            self._where = [self._locate(p) for p in self.paths]
            self.lengths = self._stat()
        except BaseException:
            self.close()
            raise
        n = len(self.paths)
        self._docs: List[Optional[bytes]] = [None] * n
        self._claimed = bytearray(n)  # read, or in a reader's hands
        self._asked = bytearray(n)
        self._order: Sequence[int] = range(n)
        self._at = 0  # the pool's place in ``_order``
        self._err: Optional[BaseException] = None
        self.stats = {"read_wait_s": 0.0, "read_ahead_hits": 0,
                      "read_docs": 0, "read_threads": 0}

    def _stat(self) -> List[int]:
        """Every file's length, by ``DOC_STAT_THREADS`` threads, a
        contiguous run of files each: the wave plan needs them all
        before the first wave, and a serial pass over a page job's
        12,000 files costs the chip's host most of what reading them
        does (PERF.md §5)."""

        def lengths(lo: int) -> List[int]:
            names, dfds = zip(*self._where[lo:lo + run])
            got = native.file_lengths(names, dfds)
            if got is None:
                return [os.stat(name, dir_fd=_dir_fd(dfd)).st_size
                        for name, dfd in zip(names, dfds)]
            sizes, bad, errno = got
            if bad >= 0:
                raise OSError(errno, os.strerror(errno),
                              self.paths[lo + bad])
            return sizes

        run = max(512, -(-len(self._where) // DOC_STAT_THREADS))
        with ThreadPoolExecutor(DOC_STAT_THREADS) as pool:
            return [n for part in pool.map(
                lengths, range(0, len(self._where), run)) for n in part]

    def _locate(self, path: str) -> Tuple[bytes, int]:
        """``(name, dir_fd)`` to open ``path`` by; -1: the name is the
        path."""
        head, name = os.path.split(path)
        if not name or not _DIR_FD or (
                head not in self._dirs
                and len(self._dirs) >= _MAX_DIR_FDS):
            return os.fsencode(path), -1
        if head not in self._dirs:
            self._dirs[head] = os.open(
                head or ".", os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
        return os.fsencode(name), self._dirs[head]

    def _read(self, run: Sequence[int]) -> List[bytes]:
        """The documents ``run`` from their files, each whole and of its
        length."""
        if self._closed:  # the directories are, too
            where = [(os.fsencode(self.paths[i]), -1) for i in run]
        else:
            where = [self._where[i] for i in run]
        names, dfds = zip(*where)
        lengths = [self.lengths[i] for i in run]
        got = native.read_files(names, dfds, lengths)
        if got is None:
            return [_read_file(name, dfd, want, self.paths[i])
                    for i, name, dfd, want in zip(run, names, dfds, lengths)]
        data, bad, errno = got
        if bad >= 0:
            raise (OSError(errno, os.strerror(errno), self.paths[run[bad]])
                   if errno else _changed(self.paths[run[bad]],
                                          lengths[bad]))
        ends = list(itertools.accumulate(lengths))
        return [bytes(data[end - n:end]) for n, end in zip(lengths, ends)]

    def __len__(self) -> int:
        return len(self.paths)

    def read_ahead(self, order: Optional[Sequence[int]] = None) -> None:
        """Start reading, in ``order`` (the ordinals as the walk will
        ask for them) or in the order the pool already has."""
        with self._cond:
            self._started = True
            if order is not None:
                self._order, self._at = list(order), 0
            if self._closed:
                return
            alive = [t for t in self._threads if t.is_alive()]
            unread = len(self._claimed) - sum(self._claimed)
            for r in range(len(alive), min(DOC_READ_THREADS, unread)):
                alive.append(threading.Thread(
                    target=self._reader, daemon=True,
                    name=f"dsi-doc-reader-{r}"))
                alive[-1].start()
            self._threads = alive
            self.stats["read_threads"] = max(self.stats["read_threads"],
                                             len(alive))

    def close(self) -> None:
        """End the pool, once nobody is asking: no reader thread
        outlives the call.  Idempotent."""
        with self._cond:
            self._closed = True
            threads, self._threads = self._threads, []
        for t in threads:
            t.join()  # at most one run's read away
        while self._dirs:
            os.close(self._dirs.popitem()[1])

    def _claim_run(self) -> List[int]:
        """The pool's next documents, claimed for the calling reader
        (the lock is held); none at the order's end, after
        :meth:`close` or a failure."""
        run: List[int] = []
        size = 0
        while (self._at < len(self._order) and self._err is None
               and not self._closed and len(run) < _RUN_DOCS
               and size < _RUN_BYTES):
            i = self._order[self._at]
            self._at += 1
            if not self._claimed[i]:
                self._claimed[i] = 1
                run.append(i)
                size += self.lengths[i]
        return run

    def _reader(self) -> None:
        while True:
            with self._cond:
                run = self._claim_run()
            if not run:
                return
            self._load(run)

    def _load(self, run: Sequence[int]) -> None:
        """Read the documents ``run``, which the caller has claimed, and
        wake whoever waits for one; a failure is kept for them
        instead."""
        docs, err = [], None
        try:
            docs = self._read(run)
        except Exception as e:
            err = e
        with self._cond:
            for i, data in zip(run, docs):
                self._docs[i] = data
            self._err = self._err or err
            self._cond.notify_all()

    def __getitem__(self, i: int) -> bytes:
        i = range(len(self.paths))[i]  # IndexError past the end, as a list
        if self._asked[i] and self._docs[i] is not None:
            return self._docs[i]
        with self._cond:
            data = self._docs[i]
            if not self._asked[i]:
                self._asked[i] = 1
                self.stats["read_docs"] += 1
                self.stats["read_ahead_hits"] += data is not None
            if data is not None:
                return data
        with _span("read_wait", lane="materialize", stats=self.stats,
                   key="read_wait_s", doc=i):
            if not self._started:
                self.read_ahead()
            with self._cond:
                mine = not self._claimed[i]
                self._claimed[i] = 1
            if mine:
                self._load([i])
            with self._cond:
                while self._docs[i] is None and self._err is None:
                    self._cond.wait()
                if self._err is not None:
                    raise self._err
                return self._docs[i]
