"""Native runtime components (C++, ctypes-bound), with Python fallbacks.

The reference has no native code (SURVEY.md §2: pure Go stdlib), but its
compiled-Go host runtime is the moral bar for this framework's host paths.
This package provides natively-accelerated pieces of the host data plane —
currently the intermediate-file decoder used by every reduce task
(``mr/worker.go:102-121`` semantics) — built by ``scripts/build_native.sh``
and loaded lazily.  Every entry point degrades to the pure-Python
implementation when the library is missing (``DSI_NO_NATIVE=1`` forces
that), and the C parser defers to Python on any input it cannot prove it
parsed completely, so native and pure runs can never diverge.

The library is built from the committed sources only: the build writes a
SHA-256 of ``kvcodec.cpp`` + ``wcjob.cpp`` + ``docread.cpp`` +
``mergeruns.cpp`` beside the ``.so``, and a
library whose recorded hash does not match the sources on disk is never
loaded — it is rebuilt, or the process says on stderr that it runs the
pure-Python data plane.  (File times say nothing: a copied tree resets
them.)
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import struct
import subprocess
import sys
import threading
from typing import List, Optional, Sequence

_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # None = not tried, False = absent

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SO_PATH = os.path.join(_REPO, "build", "libkvcodec.so")
_HASH_PATH = _SO_PATH + ".sha256"
_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = (os.path.join(_HERE, "kvcodec.cpp"),
            os.path.join(_HERE, "wcjob.cpp"),
            os.path.join(_HERE, "docread.cpp"),
            os.path.join(_HERE, "mergeruns.cpp"))


def _source_hash() -> str:
    """SHA-256 over the sources in build order — the same bytes
    ``scripts/build_native.sh`` hashes with ``cat ... | sha256sum``."""
    h = hashlib.sha256()
    for path in _SOURCES:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_from_sources() -> bool:
    """True when the ``.so`` exists and its recorded hash is the hash of
    the sources on disk."""
    try:
        with open(_HASH_PATH, "r", encoding="ascii") as f:
            recorded = f.read().strip()
    except OSError:
        return False
    return os.path.exists(_SO_PATH) and recorded == _source_hash()


def _load():
    """Load (building on first use if a toolchain exists) or mark absent."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib or None
        if os.environ.get("DSI_NO_NATIVE") == "1":
            _lib = False
            return None
        if not _built_from_sources():
            script = os.path.join(_REPO, "scripts", "build_native.sh")
            try:
                subprocess.run(["bash", script], check=True,
                               capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError) as e:
                print(f"dsi_tpu.native: build unavailable ({e}); "
                      "using pure-Python data plane", file=sys.stderr)
                _lib = False
                return None
            if not _built_from_sources():
                print("dsi_tpu.native: built library does not match its "
                      "sources; using pure-Python data plane",
                      file=sys.stderr)
                _lib = False
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
            lib.kv_decode_file.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.kv_decode_file.argtypes = [ctypes.c_char_p,
                                           ctypes.POINTER(ctypes.c_size_t)]
            lib.kv_arena_free.restype = None
            lib.kv_arena_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
            lib.kv_encode_partitions.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.kv_encode_partitions.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
                ctypes.c_uint32, ctypes.POINTER(ctypes.c_size_t)]
            lib.wc_map_file.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.wc_map_file.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                        ctypes.POINTER(ctypes.c_size_t)]
            lib.wc_reduce.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.wc_reduce.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                      ctypes.c_uint32,
                                      ctypes.POINTER(ctypes.c_size_t)]
            lib.idx_map_file.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.idx_map_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                         ctypes.c_uint32,
                                         ctypes.POINTER(ctypes.c_size_t)]
            lib.idx_reduce.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.idx_reduce.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                       ctypes.c_uint32,
                                       ctypes.POINTER(ctypes.c_size_t)]
            lib.grep_map_file.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.grep_map_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                          ctypes.c_uint32,
                                          ctypes.POINTER(ctypes.c_size_t)]
            lib.grep_reduce.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.grep_reduce.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                        ctypes.c_uint32,
                                        ctypes.POINTER(ctypes.c_size_t)]
            lib.tfidf_map_file.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.tfidf_map_file.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_size_t)]
            lib.doc_file_lengths.restype = ctypes.c_long
            lib.doc_file_lengths.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int), ctypes.c_long,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int)]
            lib.doc_read_files.restype = ctypes.c_long
            lib.doc_read_files.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_long,
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int)]
            lib.pc_rows_increase.restype = ctypes.c_int
            lib.pc_rows_increase.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                             ctypes.c_int]
            lib.pc_merge2.restype = ctypes.c_long
            lib.pc_merge2.argtypes = (
                [ctypes.c_void_p] * 4 + [ctypes.c_long]) * 2 + [
                ctypes.c_int] + [ctypes.c_void_p] * 4
            lib.pt_run_cuts.restype = ctypes.c_long
            lib.pt_run_cuts.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                        ctypes.c_int, ctypes.c_void_p,
                                        ctypes.c_long]
            lib.pt_merge_runs.restype = ctypes.c_long
            lib.pt_merge_runs.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_long,
                ctypes.c_int] + [ctypes.c_void_p] * 6
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: a stale .so predating a symbol and a failed
            # rebuild (no toolchain) — pure-Python fallback, never crash.
            print(f"dsi_tpu.native: load failed ({e}); "
                  "using pure-Python data plane", file=sys.stderr)
            _lib = False
        return _lib or None


def available() -> bool:
    return _load() is not None


def decode_kv_file(path: str) -> Optional[List[tuple]]:
    """Decode one mr-X-Y intermediate file natively.

    Returns a list of (key, value) string pairs, or None when the caller
    must use the Python decoder (library unavailable, IO error — including
    the tolerated missing-file case — or the strict parser stopped early).
    """
    lib = _load()
    if lib is None:
        return None
    out_len = ctypes.c_size_t()
    ptr = lib.kv_decode_file(path.encode(), ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        arena = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.kv_arena_free(ptr)
    n, complete = struct.unpack_from("<II", arena, 0)
    if not complete:
        return None  # lenient Python decoder takes over (never diverge)
    out: List[tuple] = []
    off = 8
    try:
        for _ in range(n):
            klen, vlen = struct.unpack_from("<II", arena, off)
            off += 8
            key = arena[off:off + klen].decode("utf-8")
            off += klen
            val = arena[off:off + vlen].decode("utf-8")
            off += vlen
            out.append((key, val))
    except (UnicodeDecodeError, struct.error):
        # e.g. a lone-surrogate \uXXXX escape: json.dumps emits it, strict
        # UTF-8 rejects it.  Never diverge — let the Python decoder decide.
        return None
    return out


def encode_partitions(kva, n_reduce: int) -> Optional[List[bytes]]:
    """Partition + serialize a map task's output natively.

    One C pass computes the reference partitioner (``fnv1a32(key) &
    0x7fffffff % n_reduce``, mr/worker.go:33-37,76) and renders each
    partition's JSON-lines blob — the three host hot loops of the map side
    (per-byte hash, json.dumps per record, bucket appends) fused.

    Returns ``n_reduce`` byte blobs, or None when the caller must use the
    Python writer (library unavailable, or a key/value that strict UTF-8
    cannot encode — e.g. surrogates from decode errors)."""
    lib = _load()
    if lib is None:
        return None
    kva = list(kva)
    pack = struct.Struct("<II").pack
    parts: List[bytes] = []
    try:
        for kv in kva:
            kb = kv.key.encode("utf-8")
            vb = kv.value.encode("utf-8")
            parts.append(pack(len(kb), len(vb)))
            parts.append(kb)
            parts.append(vb)
    except (UnicodeEncodeError, struct.error):
        # Surrogates (json.dumps can represent them, raw UTF-8 can't) or a
        # >=4 GiB string (length field would not fit): Python writer path.
        return None
    buf = b"".join(parts)
    out_len = ctypes.c_size_t()
    ptr = lib.kv_encode_partitions(buf, len(buf), len(kva), n_reduce,
                                   ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        arena = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.kv_arena_free(ptr)
    return _unpack_blobs(arena, n_reduce)


def _unpack_blobs(arena: bytes, want: int) -> Optional[List[bytes]]:
    (n,) = struct.unpack_from("<I", arena, 0)
    if n != want:
        return None
    out: List[bytes] = []
    off = 4
    for _ in range(n):
        (bl,) = struct.unpack_from("<I", arena, off)
        off += 4
        out.append(arena[off:off + bl])
        off += bl
    return out


def _call_arena(symbol: str, args: tuple, want: int) -> Optional[List[bytes]]:
    """Shared call shape for every wcjob.cpp entry point: load, call,
    copy the arena out, ALWAYS free it, unpack the blob framing.  One
    place owns the arena-free-on-any-path invariant."""
    lib = _load()
    if lib is None:
        return None
    out_len = ctypes.c_size_t()
    ptr = getattr(lib, symbol)(*args, ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        arena = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.kv_arena_free(ptr)
    return _unpack_blobs(arena, want)


def wc_map_file(path: str, n_reduce: int) -> Optional[List[bytes]]:
    """Whole word-count COMBINER map task natively (dsi_tpu/native/
    wcjob.cpp): tokenize + count-per-unique + reference partition hash +
    JSON-lines render in one C++ pass.  Returns the n_reduce partition
    blobs, or None when the split needs the host path (non-ASCII bytes,
    IO failure, or no library)."""
    return _call_arena("wc_map_file", (path.encode(), n_reduce), n_reduce)


def wc_reduce(workdir: str, reduce_task: int, n_map: int) -> Optional[bytes]:
    """Whole word-count SUM reduce task natively: parse + per-key sum +
    bytewise sort + "key sum\\n" render.  Returns the mr-out-<r> blob, or
    None when the Python reduce (the app's own Reduce) must own the task
    (escapes/non-ASCII/malformed records, overflow, or no library)."""
    blobs = _call_arena("wc_reduce", (workdir.encode(), reduce_task, n_map),
                        1)
    return None if blobs is None else blobs[0]


def idx_map_file(path: str, docname: str,
                 n_reduce: int) -> Optional[List[bytes]]:
    """Whole inverted-index map task natively (distinct words +
    partition + render); None -> host path (non-ASCII split, docname
    needing JSON escapes, or no library)."""
    try:
        args = (path.encode(), docname.encode("ascii"), n_reduce)
    except UnicodeEncodeError:
        return None
    return _call_arena("idx_map_file", args, n_reduce)


def idx_reduce(workdir: str, reduce_task: int, n_map: int) -> Optional[bytes]:
    """Whole inverted-index reduce task natively ("<count> <docs,...>"
    over sorted deduplicated documents); None -> Python reduce."""
    blobs = _call_arena("idx_reduce", (workdir.encode(), reduce_task, n_map),
                        1)
    return None if blobs is None else blobs[0]


def grep_map_file(path: str, pattern: str,
                  n_reduce: int) -> Optional[List[bytes]]:
    """Whole literal-grep map task natively (byte-level substring search
    per line + partition + render); None -> host re path (regex
    metacharacters, non-ASCII split/pattern, rare control bytes)."""
    try:
        args = (path.encode(), pattern.encode("ascii"), n_reduce)
    except UnicodeEncodeError:
        return None
    return _call_arena("grep_map_file", args, n_reduce)


def grep_reduce(workdir: str, reduce_task: int,
                n_map: int) -> Optional[bytes]:
    """Whole occurrence-count grep reduce task natively; None -> Python
    reduce (escapes beyond the map's minimal set, non-ASCII keys)."""
    blobs = _call_arena("grep_reduce", (workdir.encode(), reduce_task,
                                        n_map), 1)
    return None if blobs is None else blobs[0]


def tfidf_map_file(path: str, docname: str,
                   n_reduce: int) -> Optional[List[bytes]]:
    """Whole TF-IDF map task natively (distinct words x in-doc counts,
    value "<doc>\\t<tf>"); None -> host path.  The reduce (float
    scoring) always runs on the Python path."""
    try:
        args = (path.encode(), docname.encode("ascii"), n_reduce)
    except UnicodeEncodeError:
        return None
    return _call_arena("tfidf_map_file", args, n_reduce)


def file_lengths(names: Sequence[bytes], dir_fds: Sequence[int]):
    """The files' lengths in one call that holds no interpreter lock
    (``docread.cpp``), named as for :func:`read_files`.  Returns
    ``(lengths, bad, errno)``: -1, or the index of the first file that
    has none.  None -> the caller stats them itself."""
    lib = _load()
    if lib is None:
        return None
    n = len(names)
    out = (ctypes.c_int64 * n)()
    err = ctypes.c_int()
    bad = lib.doc_file_lengths((ctypes.c_char_p * n)(*names),
                               (ctypes.c_int * n)(*dir_fds), n, out,
                               ctypes.byref(err))
    return list(out), bad, err.value


def read_files(names: Sequence[bytes], dir_fds: Sequence[int],
               lengths: Sequence[int]):
    """Whole files in one call that holds no interpreter lock
    (``docread.cpp``): file ``k`` is ``names[k]`` from the directory
    open as ``dir_fds[k]`` (-1: the name is a path) and had
    ``lengths[k]`` bytes when the job began.  Returns ``(data, bad,
    errno)``: the files' bytes one behind the other, and -1, or the
    index of the first file that could not be read (``errno``) or was
    no longer its length (``errno`` 0).  None -> the caller reads them
    itself (library unavailable)."""
    lib = _load()
    if lib is None:
        return None
    n = len(names)
    out = ctypes.create_string_buffer(sum(lengths) + 1)
    err = ctypes.c_int()
    bad = lib.doc_read_files((ctypes.c_char_p * n)(*names),
                             (ctypes.c_int * n)(*dir_fds),
                             (ctypes.c_int64 * n)(*lengths), n, out,
                             ctypes.byref(err))
    return memoryview(out), bad, err.value


def _table_pointers(table, k: Optional[int] = None) -> list:
    """The four data pointers of a ``(keys, lens, cnts, parts)`` table
    for ``mergeruns.cpp``, which reads them as C arrays of uint32 ``[n,
    k]``, int32, int64 and int32 of ``n`` rows: anything else is refused
    here, not read there."""
    keys = table[0]
    if keys.ndim != 2 or (k is not None and keys.shape[1] != k):
        raise ValueError(f"key lanes {keys.shape}, wanted [n, {k or 'k'}]")
    for x, dtype in zip(table, ("uint32", "int32", "int64", "int32")):
        if x.dtype != dtype or not x.flags.c_contiguous \
                or len(x) != len(keys):
            raise ValueError(
                f"a table's column has to be C-contiguous {dtype} of "
                f"{len(keys)} rows, not {x.dtype}{x.shape}")
    return [x.ctypes.data for x in table]


def rows_increase(keys) -> Optional[bool]:
    """Whether the rows of a C-contiguous ``[n, k]`` uint32 table
    strictly increase, lane 0 primary (``mergeruns.cpp``).  None -> the
    caller decides it itself."""
    lib = _load()
    if lib is None:
        return None
    if keys.ndim != 2 or keys.dtype != "uint32" \
            or not keys.flags.c_contiguous:
        raise ValueError("key lanes have to be C-contiguous uint32 [n, k], "
                         f"not {keys.dtype}{keys.shape}")
    return bool(lib.pc_rows_increase(keys.ctypes.data, keys.shape[0],
                                     keys.shape[1]))


def merge_runs2(a, b, out) -> Optional[int]:
    """Two sorted runs into ``out`` (``mergeruns.cpp``): ``a``, ``b`` and
    ``out`` are ``(keys, lens, cnts, parts)``, C-contiguous uint32 ``[n,
    k]`` of one ``k``, int32, int64, int32, ``out`` with room for the rows
    of both.  Returns the rows written (the distinct keys, increasing,
    the counts of a key both hold summed), or None -> the caller merges
    them itself."""
    lib = _load()
    if lib is None:
        return None
    k = a[0].shape[1]
    if len(out[0]) < len(a[0]) + len(b[0]):
        raise ValueError("the output has no room for the rows of both runs")
    return lib.pc_merge2(*_table_pointers(a, k), len(a[0]),
                         *_table_pointers(b, k), len(b[0]), k,
                         *_table_pointers(out, k))


def _posting_rows(rows, kk: int):
    """``rows`` as ``mergeruns.cpp`` reads posting rows: C-contiguous
    uint32 ``[n, kk + 4]``; anything else is refused here, not read
    there."""
    if rows.ndim != 2 or rows.shape[1] != kk + 4 or rows.dtype != "uint32" \
            or not rows.flags.c_contiguous:
        raise ValueError("posting rows have to be C-contiguous uint32 "
                         f"[n, {kk + 4}], not {rows.dtype}{rows.shape}")
    return rows


def run_cuts(rows, kk: int, cuts):
    """How often the posting rows ``[n, kk + 4]`` descend in their ``kk``
    key lanes, a row sorting before the one above it
    (``mergeruns.cpp``); the first ``len(cuts)`` such rows' indices are
    written to ``cuts`` (C-contiguous int64).  None -> the caller finds
    them itself."""
    lib = _load()
    if lib is None:
        return None
    if cuts.dtype != "int64" or cuts.ndim != 1 \
            or not cuts.flags.c_contiguous:
        raise ValueError("the cuts have to be C-contiguous int64, not "
                         f"{cuts.dtype}{cuts.shape}")
    return lib.pt_run_cuts(_posting_rows(rows, kk).ctypes.data, len(rows),
                           kk, cuts.ctypes.data, len(cuts))


def merge_posting_runs(bufs, cuts, kk: int, out) -> Optional[int]:
    """Buffers of posting rows merged into the grouped index's columns
    (``mergeruns.cpp``).  ``bufs[b]`` is ``[n, kk + 4]`` and holds the
    runs that ``cuts[b]`` (int64 row indices, increasing, inside the
    buffer) cuts it into, each run's words never descending; the earlier
    run leaves first among equal words.  ``out`` is ``(skeys [n, kk]
    uint32, lens uint32, parts uint32, starts int64, tfs uint32, docs
    uint32)``, every column C-contiguous with room for all the rows.
    Returns the words written to the first four, or None -> the caller
    merges the runs itself."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    where, ends = [], []
    for rows, c in zip(bufs, cuts):
        n, step = len(_posting_rows(rows, kk)), 4 * (kk + 4)
        if len(c) and not (0 < c[0] and c[-1] < n
                           and (c[1:] > c[:-1]).all()):
            raise ValueError(f"cuts outside a buffer of {n} rows")
        edges = np.concatenate(([0], c, [n])).astype(np.uint64)
        where.append(rows.ctypes.data + edges[:-1] * np.uint64(step))
        ends.append(np.diff(edges))
    where = np.concatenate(where) if where else np.zeros(0, np.uint64)
    lens = np.concatenate(ends).astype(np.int64) if ends \
        else np.zeros(0, np.int64)
    n_rows = int(lens.sum())
    for x, dtype in zip(out, ("uint32", "uint32", "uint32", "int64",
                              "uint32", "uint32")):
        if x.dtype != dtype or not x.flags.c_contiguous \
                or len(x) < n_rows:
            raise ValueError(
                f"an index column has to be C-contiguous {dtype} with room "
                f"for {n_rows} rows, not {x.dtype}{x.shape}")
    if out[0].ndim != 2 or out[0].shape[1] != kk:
        raise ValueError(f"key lanes {out[0].shape}, wanted [n, {kk}]")
    words = lib.pt_merge_runs(where.ctypes.data, lens.ctypes.data,
                              len(lens), kk, *(x.ctypes.data for x in out))
    if words < 0:
        raise MemoryError(f"no room to merge {len(lens)} runs")
    return words
