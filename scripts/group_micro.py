"""An index job's group, the parent's way and each candidate realisation
of "merge the runs the waves arrive in", over the postings of one job of
each index cell (PR 48; PERF.md section 6 holds the table this printed on
the chip's host).  Not a test and not a benchmark cell, and it needs no
chip: the group is host code, so

    python scripts/group_micro.py [--cell books|pages|both] [--tiny]

sizes the layer on whatever CPU runs it; run it through the chip tool to
size it on the host the cells run on.  The rows are built from the cells'
own collections (``benchmarks/corpus.py``'s shelves cut by
``benchmarks/docs.py``, as the cells' drivers do): ``books`` is
``plan-index-books``' job (a document a wave, longest first: a run a
document, a word once a run), ``pages`` is ``plan-index-pages``' (whole
documents packed into waves of 512 KiB: a run a wave, in (word, document)
order, a word once a document).  The forms:

* ``today``: every row concatenated, ``np.lexsort`` over the key lanes,
  the table read out of place through the permutation (``_group`` until
  PR 48).
* ``A tournament`` (the tree's route): the runs found from the rows,
  ``native/mergeruns.cpp``'s loser tree over the runs' heads, the index's
  columns written once.
* ``B pairwise``: ``scripts/group_micro.cpp``, two-pointer merges level by
  level with the whole row carried, then the columns cut from the rows.
* ``C numpy`` (the tree's route without the library): one stable sort of
  the first two lanes packed, ties repaired, ONE whole-row gather.

All give ``today``'s columns byte for byte (checked before a time is
printed).  Then the crossover that ``merge._RUN_ROWS_MIN`` is read from:
2^20 of the rows in one buffer, cut into runs of L rows, grouped as runs
and grouped after a sort on entry.  One JSON line a form on stdout and in
``chiprun_out/group_micro.jsonl``; ``--tiny`` divides the collections by
32 (a rehearsal of the script, whose times mean nothing).
"""
import argparse
import ctypes
import json
import os
import subprocess
import sys
import tempfile
import time
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import numpy as np

import corpus
import docs
from dsi_tpu import native
from dsi_tpu.parallel import merge as M

KK = 4
FIELDS = ("skeys", "lens", "parts", "starts", "ends", "tfs", "docs")
CELLS = {"books": ("plan-index-1chip", "books-1pass", 0),
         "pages": ("plan-index-pages-1chip", "pages-1pass", 512 << 10)}


def doc_rows(data: bytes, ordinal: int) -> np.ndarray:
    """One document's posting rows ``[words, KK + 4]``, in word order: a
    row a distinct word (maximal runs of ASCII letters)."""
    b = np.frombuffer(data, np.uint8)
    letter = ((b | 0x20) - np.uint8(97)) < 26
    edge = np.diff(letter.astype(np.int8), prepend=0, append=0)
    first, last = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    lens = np.minimum(last - first, 4 * KK)
    at = np.minimum(first[:, None] + np.arange(4 * KK), len(b) - 1)
    text = b[at] * (np.arange(4 * KK) < lens[:, None])
    words, tf = np.unique(text.view(f"S{4 * KK}").ravel(),
                          return_counts=True)
    lanes = np.frombuffer(words.tobytes(), ">u4").reshape(-1, KK)
    rows = np.empty((len(words), KK + 4), np.uint32)
    rows[:, :KK] = lanes
    rows[:, KK] = np.char.str_len(words)
    rows[:, KK + 1] = tf
    rows[:, KK + 2] = ordinal
    rows[:, KK + 3] = (rows[:, 0] ^ rows[:, 1]) % 10
    return rows


def job_buffers(cell: str, seed: int, shrink: int):
    """What a job of the cell hands to ``PostingsTable.add``, in order."""
    config, traffic, chunk = CELLS[cell]
    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           traffic + ".json")) as f:
        mix = json.load(f)
    params = corpus.effective(cfg["corpus"], mix["corpus"])
    params["file_bytes"] //= shrink
    lo = int(mix["reference_params"]["doc_min_bytes"])
    hi = int(mix["reference_params"]["doc_max_bytes"])
    documents, rng = [], None
    for i in range(int(params["files"])):
        data = corpus.generate_bytes(int(params["file_bytes"]),
                                     seed * 1000 + i, params)
        if rng is None:
            rng = np.random.default_rng(zlib.crc32(data))
        start = 0
        for end in docs.cuts(data, rng, lo, hi):
            documents.append(data[start:end])
            start = end
    rows = [doc_rows(d, i) for i, d in enumerate(documents)]
    if not chunk:  # a document a wave, longest first
        order = sorted(range(len(rows)), key=lambda i: -len(documents[i]))
        return [rows[i] for i in order]
    waves, held, size = [], [], 0
    for d, r in zip(documents, rows):
        if held and size + len(d) > chunk:
            waves.append(held)
            held, size = [], 0
        held.append(r)
        size += len(d)
    waves.append(held)
    out = []
    for held in waves:  # a wave's rows in (word, document) order
        wave = np.concatenate(held)
        out.append(wave[M._lexsort_rows(wave[:, :KK])])
    return out


# ── the forms ──


def today(bufs):
    """``PostingsTable._group`` as it stood before PR 48."""
    rows = np.concatenate(bufs)
    keys = rows[:, :KK]
    order = M._lexsort_rows(keys)
    skeys = keys[order]
    starts = M._group_starts(skeys)
    return {"skeys": np.ascontiguousarray(skeys[starts]), "starts": starts,
            "ends": np.append(starts[1:], len(rows)),
            "lens": rows[order[starts], KK],
            "parts": rows[order[starts], KK + 3],
            "tfs": np.ascontiguousarray(rows[order, KK + 1]),
            "docs": np.ascontiguousarray(rows[order, KK + 2])}


def tree_route(bufs):
    table = M.PostingsTable()
    table._bufs, table._kk = list(bufs), KK
    out, runs, rows_sorted = table._group()
    return {f: getattr(out, f) for f in FIELDS}, runs, rows_sorted


def build_form_b():
    """``group_micro.cpp`` as a library in a directory of its own."""
    so = os.path.join(tempfile.mkdtemp(prefix="group_micro."), "formb.so")
    subprocess.run(["g++", "-O2", "-Wall", "-shared", "-fPIC", "-std=c++17",
                    "-o", so, os.path.join(REPO, "scripts",
                                           "group_micro.cpp")], check=True)
    lib = ctypes.CDLL(so)
    lib.gm_merge_levels.restype = ctypes.c_int
    lib.gm_merge_levels.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_void_p, ctypes.c_long,
                                    ctypes.c_int]
    return lib


def pairwise(lib, bufs):
    """Form B: the runs found as the tree finds them, the rows merged
    level by level, the columns cut from the rows in order."""
    t0 = time.perf_counter()
    rows = np.concatenate(bufs)
    edges, at = [0], 0
    for b in bufs:
        edges += (at + M._run_cuts(b, KK)).tolist() + [at + len(b)]
        at += len(b)
    edges = np.array(edges, np.int64)
    other = np.empty_like(rows)
    t1 = time.perf_counter()
    if lib.gm_merge_levels(rows.ctypes.data, other.ctypes.data,
                           edges.ctypes.data, len(edges) - 1, KK):
        rows = other
    t2 = time.perf_counter()
    starts = M._group_starts(rows[:, :KK])
    out = {"skeys": np.ascontiguousarray(rows[starts, :KK]),
           "starts": starts, "ends": np.append(starts[1:], len(rows)),
           "lens": rows[starts, KK], "parts": rows[starts, KK + 3],
           "tfs": np.ascontiguousarray(rows[:, KK + 1]),
           "docs": np.ascontiguousarray(rows[:, KK + 2])}
    return out, {"runs_s": t1 - t0, "merge_s": t2 - t1,
                 "columns_s": time.perf_counter() - t2}


def without_library(fn, *args):
    lib, native._lib = native._lib, False
    try:
        return fn(*args)
    finally:
        native._lib = lib


def same(got, want, form):
    for f in FIELDS:
        assert got[f].dtype == want[f].dtype and np.array_equal(
            got[f], want[f]), (form, f)


def crossover(rows, rng, say):
    """2^20 rows in one buffer of runs of L rows: grouped as the runs
    they are, and grouped after the sort on entry."""
    rows = rows[rng.permutation(len(rows))[:1 << 20]]
    keep = M._RUN_ROWS_MIN
    for length in (2, 4, 8, 16, 32, 64, 128, 256, 1024, 4096):
        buf = np.concatenate([
            piece[M._lexsort_rows(piece[:, :KK])]
            for piece in np.array_split(rows, len(rows) // length)])
        line = {"form": "crossover", "run_rows": length}
        want = None
        for name, least in (("as_runs_s", 1), ("sorted_on_entry_s",
                                               len(buf) + 1)):
            M._RUN_ROWS_MIN = least
            try:
                t0 = time.perf_counter()
                got, runs, rows_sorted = tree_route([buf])
                line[name] = round(time.perf_counter() - t0, 4)
            finally:
                M._RUN_ROWS_MIN = keep
            assert (rows_sorted == 0) == (least == 1)
            if want is not None:
                same(got, want, name)
            want = got
        say(line)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cell", choices=("books", "pages", "both"),
                   default="both")
    p.add_argument("--seed", type=int, default=48)
    p.add_argument("--repeat", type=int, default=3)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    lines = []

    def say(line):
        line["native"] = native.available()
        lines.append(line)
        print(json.dumps(line), flush=True)

    t0 = time.perf_counter()
    subprocess.run(["bash", os.path.join(REPO, "scripts",
                                         "build_native.sh")],
                   check=True, capture_output=True)
    say({"form": "library build", "s": round(time.perf_counter() - t0, 2)})
    form_b = build_form_b()
    bufs = None
    for cell in ("books", "pages") if args.cell == "both" else (args.cell,):
        t0 = time.perf_counter()
        bufs = job_buffers(cell, args.seed, 32 if args.tiny else 1)
        shape = {"cell": cell, "buffers": len(bufs),
                 "rows": sum(map(len, bufs))}
        say(dict(shape, form="rows built",
                 s=round(time.perf_counter() - t0, 1)))
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            want = today(bufs)
            say(dict(shape, form="today", terms=len(want["skeys"]),
                     group_s=round(time.perf_counter() - t0, 4)))
            if native.available():
                t0 = time.perf_counter()
                got, runs, rows_sorted = tree_route(bufs)
                s = time.perf_counter() - t0
                same(got, want, "A")
                say(dict(shape, form="A tournament", runs=runs,
                         rows_sorted=rows_sorted, group_s=round(s, 4)))
            t0 = time.perf_counter()
            got, parts = pairwise(form_b, bufs)
            s = time.perf_counter() - t0
            same(got, want, "B")
            say(dict(shape, form="B pairwise", group_s=round(s, 4),
                     **{k: round(v, 4) for k, v in parts.items()}))
            t0 = time.perf_counter()
            got, runs, rows_sorted = without_library(tree_route, bufs)
            s = time.perf_counter() - t0
            same(got, want, "C")
            say(dict(shape, form="C numpy", runs=runs,
                     rows_sorted=rows_sorted, group_s=round(s, 4)))
    if native.available():
        crossover(np.concatenate(bufs), np.random.default_rng(args.seed),
                  say)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "group_micro.jsonl"), "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
