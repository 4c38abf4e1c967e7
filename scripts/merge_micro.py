"""The host merge of a word-count stream job, the parent's way and each
candidate realisation of "merge sorted runs", over a job's worth of
synthetic step tables (PR 43; PERF.md section 6 holds the table this
printed on the chip's host).  Not a test and not a benchmark cell, and it
needs no chip: the merge is host code, so

    python scripts/merge_micro.py [--layout heaps|mesh4] [--tiny]

sizes the layer on whatever CPU runs it; run it through the chip tool to
size it on the host the cells run on.  ``heaps`` is ``stream-wc-heaps``' job
(128 steps of 1 MiB on one device, eight files of 400,000 words each, the
key space growing file by file), ``mesh4`` is ``stream-wc-mesh4``'s (32
steps of 4 MiB, a table a device a step, the words dealt to the devices by
partition).  Every step table arrives as the device leaves it: distinct
words, in order.  The forms:

* ``parent``: every batch and the merged table concatenated, one
  ``np.lexsort`` over the four lanes, ``reduceat``; the trigger counts the
  table (the accumulator until PR 43).
* ``lexsort64``: the table apart from the window; the window sorted as two
  ``uint64`` columns by ``np.lexsort``, the table and the window's run then
  the same way.
* ``stable64+stable``: the window ordered by ONE stable sort of the first
  two lanes packed (ties repaired), the table and the run by the same sort
  (two runs: one galloping merge).
* ``stable64+place`` (the tree's route without the library): the window the
  same, the run placed into the table by ``np.searchsorted``.
* ``native`` (the tree's route): ``native/mergeruns.cpp``, pairwise
  two-pointer merges of the window, two pointers into the table.

All give the parent's table bit for bit (checked before a time is
printed).  One JSON line per form on stdout and in
``chiprun_out/merge_micro.jsonl``; ``--tiny`` divides the sizes by 64 (a
rehearsal of the script, whose times mean nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dsi_tpu import native
from dsi_tpu.parallel import merge as M

COMPACT_ROWS = 1 << 21


def step_tables(layout: str, seed: int, shrink: int):
    """A job's step tables, in arrival order: ``(keys [n, 4] uint32, lens,
    cnts, parts)`` each, distinct words in order."""
    rng = np.random.default_rng(seed)
    files, vocab, per_file = 8, 400_000 // shrink, 16
    tokens = (1 << 20) // 8 // shrink  # a MiB of text, ~8 bytes a word
    weights = 1.0 / (np.arange(vocab) + 2.7)
    weights /= weights.sum()
    out = []
    for _ in range(files):
        raw = rng.integers(ord("a"), ord("z") + 1, (vocab, 16),
                           dtype=np.uint8)
        lens = rng.integers(2, 13, vocab)
        raw[np.arange(16) >= lens[:, None]] = 0
        raw[::10, 0] -= 32  # every tenth Capitalised
        # no word twice (short ones repeat), the ranks in no order
        _, first = np.unique(raw, axis=0, return_index=True)
        first.sort()
        raw, lens = raw[first], lens[first]
        keys = raw.view(">u4").astype(np.uint32)
        parts = (keys[:, 0] % 10).astype(np.int32)
        chunks = [rng.choice(len(keys), tokens, p=weights[:len(keys)]
                             / weights[:len(keys)].sum())
                  for _ in range(per_file)]
        if layout == "mesh4":  # four chunks a step, a table a device
            chunks = [np.concatenate(chunks[i:i + 4])
                      for i in range(0, per_file, 4)]
        for chunk in chunks:
            ranks, counts = np.unique(chunk, return_counts=True)
            order = M._lexsort_rows(keys[ranks])
            ranks, counts = ranks[order], counts[order]
            for d in range(4 if layout == "mesh4" else 1):
                own = slice(None) if layout != "mesh4" \
                    else parts[ranks] % 4 == d
                r = ranks[own]
                out.append((keys[r], lens[r].astype(np.int32),
                            counts[own].astype(np.int64), parts[r]))
    return out


# ── the forms: (merge a window's runs, merge that run into the table) ──


def _concat(runs):
    return tuple(np.concatenate([r[i] for r in runs]) for i in range(4))


def _reduce(table, order):
    return M._reduce_ordered(table, order, table[0][order])


def _two_columns(keys):
    wide = keys.astype(np.uint64)
    return (wide[:, 2] << np.uint64(32)) | wide[:, 3], \
        (wide[:, 0] << np.uint64(32)) | wide[:, 1]


def lexsort64(runs):
    table = _concat(runs)
    return _reduce(table, np.lexsort(_two_columns(table[0])))


FORMS = {
    "lexsort64": (lexsort64, lambda t, w: lexsort64([t, w])),
    "stable64+stable": (M._merge_runs_numpy,
                        lambda t, w: M._merge_runs_numpy([t, w])),
    "stable64+place": (M._merge_runs_numpy, M._merge_into),
    "native": (M._merge_runs, M._merge_into),
}


def run_parent(tables):
    """The accumulator as it stood before PR 43, with its counters."""
    bufs, pending, compacts, rows_sorted, seconds = [], 0, 0, 0, 0.0

    def compact():
        nonlocal bufs, pending, compacts, rows_sorted, seconds
        if len(bufs) <= 1:
            return
        t0 = time.perf_counter()
        table = _concat(bufs)
        bufs = [_reduce(table, M._lexsort_rows(table[0]))]
        seconds += time.perf_counter() - t0
        rows_sorted += len(table[0])
        compacts += 1
        pending = len(bufs[0][0])

    for t in tables:
        bufs.append(t)
        pending += len(t[0])
        if pending >= COMPACT_ROWS:
            compact()
    compact()
    return bufs[0], {"compact_s": seconds, "compacts": compacts,
                     "rows_sorted": rows_sorted}


def run_form(tables, merge_window, merge_table):
    """The table apart from the window, the window counted alone."""
    table, window, pending = None, [], 0
    parts = {"check_s": 0.0, "window_s": 0.0, "table_s": 0.0}
    compacts = rows_sorted = 0

    def compact():
        nonlocal table, window, pending, compacts, rows_sorted
        if not window:
            return
        t0 = time.perf_counter()
        run = merge_window(window) if len(window) > 1 else window[0]
        t1 = time.perf_counter()
        table = run if table is None else merge_table(table, run)
        parts["window_s"] += t1 - t0
        parts["table_s"] += time.perf_counter() - t1
        rows_sorted += pending
        compacts += 1
        window, pending = [], 0

    for t in tables:
        t0 = time.perf_counter()
        assert M._rows_increase(t[0])
        parts["check_s"] += time.perf_counter() - t0
        window.append(t)
        pending += len(t[0])
        if pending >= COMPACT_ROWS:
            compact()
    compact()
    parts["compact_s"] = parts["window_s"] + parts["table_s"]
    return table, dict(parts, compacts=compacts, rows_sorted=rows_sorted)


def main(argv=None) -> int:
    global COMPACT_ROWS
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--layout", choices=("heaps", "mesh4"), default="heaps")
    p.add_argument("--seed", type=int, default=43)
    p.add_argument("--repeat", type=int, default=2)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    shrink = 64 if args.tiny else 1
    COMPACT_ROWS //= shrink

    tables = step_tables(args.layout, args.seed, shrink)
    rows_in = sum(len(t[0]) for t in tables)
    lines = []

    def say(form, result, counts):
        line = {"layout": args.layout, "form": form, "runs": len(tables),
                "rows_in": rows_in, "words": len(result[0]),
                "resort_x": round(counts.pop("rows_sorted") / rows_in, 4),
                **{k: round(v, 4) if isinstance(v, float) else v
                   for k, v in counts.items()},
                "native": native.available()}
        lines.append(line)
        print(json.dumps(line), flush=True)

    want = None
    for _ in range(args.repeat):
        want, counts = run_parent(tables)
        say("parent", want, counts)
        for form, (merge_window, merge_table) in FORMS.items():
            if form == "native" and not native.available():
                continue
            if form == "stable64+place":  # the tree's route, library off
                lib, native._lib = native._lib, False
            got, counts = run_form(tables, merge_window, merge_table)
            if form == "stable64+place":
                native._lib = lib
            for a, b in zip(got, want):
                assert a.dtype == b.dtype and np.array_equal(a, b), form
            say(form, got, counts)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "merge_micro.jsonl"), "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
