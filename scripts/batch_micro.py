"""The grep engines' row cut alone (``parallel/grepstream.batch_lines``),
the tree's way and the way it was until PR 56, over the benchmark's text
shape: lines of ~101 bytes in 4 MiB blocks, cut into rows of 1 MiB (the
``grepstream-rare`` and ``serve-grep-fb12`` cells) and of 64 KiB (the
daemon's default).  Not a test and not a benchmark cell, and it needs no
chip: the cut is host code, so

    python scripts/batch_micro.py [--mib 64] [--repeat 3] [--tiny]

sizes the layer on whatever CPU runs it; run it through the chip tool to
size it on the host the cells run on (PERF.md section 6 holds the table
this printed there).  The forms:

* ``carry`` (until PR 56): every block appended to a ``bytearray``, the
  row's end the last hit of ``np.flatnonzero(win == 10)`` over its first
  ``chunk_bytes``, the row copied out of it, its lines counted by a second
  compare, the ``bytearray`` cut from the left.
* ``in_place`` (the tree's): ``batch_lines`` as it stands.
* ``in_place_nocount``: the same without the line counts, as
  ``streaming._row_batches`` takes its rows.

Each form runs alone and ``beside_spin``: beside a thread that never leaves
the interpreter but at its switch interval, as a job's dispatching thread
competes with its batcher for the lock (the worst case: the dispatcher
lets go of it inside its put and its program call).  The forms give the
same rows, lengths, line counts and offsets (checked before a time is
printed).  One JSON line per (rows, form, company) on stdout and in
``chiprun_out/batch_micro.jsonl``; ``--tiny`` cuts 2 MiB (a rehearsal of
the script, whose times mean nothing).
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dsi_tpu.parallel.grepstream import _LineTooLong, batch_lines
from dsi_tpu.parallel.pipeline import BufferPool

BLOCK_BYTES = 4 << 20


def text(size: int, seed: int) -> bytes:
    """``size`` bytes of lines of 60-140 bytes (101 on average with their
    newlines), the last one whole."""
    rng = np.random.default_rng(seed)
    blob = rng.integers(97, 123, size, dtype=np.uint8)
    ends = np.cumsum(rng.integers(60, 142, size // 60))
    blob[ends[ends < size - 1]] = 10
    blob[-1] = 10
    return blob.tobytes()


def carry_lines(blocks, n_dev, chunk_bytes, pool, offsets, stats):
    """``batch_lines`` as the tree had it until PR 56, kept here for the
    comparison alone (it copies every byte twice and counts none)."""
    carry = bytearray()
    consumed = 0
    batch = pool.take()
    lens = np.zeros(n_dev, dtype=np.int32)
    row_lines = np.zeros(n_dev, dtype=np.int64)
    row = 0

    def fill_rows(final: bool):
        nonlocal batch, lens, row_lines, row, consumed
        while carry and (len(carry) > chunk_bytes or final):
            if len(carry) <= chunk_bytes:
                cut = len(carry)
            else:
                win = np.frombuffer(memoryview(carry)[:chunk_bytes],
                                    dtype=np.uint8)
                hits = np.flatnonzero(win == 10)
                del win
                if hits.size == 0:
                    raise _LineTooLong
                cut = int(hits[-1]) + 1
            view = np.frombuffer(carry, dtype=np.uint8, count=cut)
            batch[row, :cut] = view
            n_nl = int(np.count_nonzero(view == 10))
            del view
            del carry[:cut]
            consumed += cut
            batch[row, cut:] = 0
            lens[row] = cut
            row_lines[row] = n_nl + (1 if batch[row, cut - 1] != 10 else 0)
            row += 1
            if row == n_dev:
                offsets.append(consumed)
                yield batch, lens, row_lines
                batch = pool.take()
                lens = np.zeros(n_dev, dtype=np.int32)
                row_lines = np.zeros(n_dev, dtype=np.int64)
                row = 0

    for block in blocks:
        carry.extend(block)
        yield from fill_rows(final=False)
    yield from fill_rows(final=True)
    if row:
        batch[row:] = 0
        offsets.append(consumed)
        yield batch, lens, row_lines
    else:
        pool.give(batch)


FORMS = {
    "carry": carry_lines,
    "in_place": lambda blocks, n_dev, chunk, pool, offsets, stats:
        batch_lines(blocks, n_dev, chunk, pool=pool, offsets=offsets,
                    stats=stats),
    "in_place_nocount": lambda blocks, n_dev, chunk, pool, offsets, stats:
        batch_lines(blocks, n_dev, chunk, pool=pool, offsets=offsets,
                    stats=stats, count_lines=False),
}


def digest(form: str, blocks, chunk: int):
    """Everything a consumer sees of a cut, for the equality check."""
    pool, offsets, out = BufferPool((1, chunk), retain=4), [], []
    for batch, lens, row_lines in FORMS[form](iter(blocks), 1, chunk, pool,
                                              offsets, None):
        out.append((hash(batch.tobytes()), int(lens[0]), int(row_lines[0])))
        pool.give(batch)
    return out, offsets


def timed(form: str, blocks, chunk: int, spin: bool) -> dict:
    pool, offsets = BufferPool((1, chunk), retain=4), []
    stats = {"recopied_bytes": 0}
    stop = threading.Event()

    def spinner():
        n = 0
        while not stop.is_set():
            n += 1

    rival = threading.Thread(target=spinner, daemon=True)
    if spin:
        rival.start()
    rows = 0
    t0 = time.perf_counter()
    try:
        for batch, _lens, _lines in FORMS[form](iter(blocks), 1, chunk, pool,
                                                offsets, stats):
            rows += 1
            pool.give(batch)
        wall = time.perf_counter() - t0
    finally:
        stop.set()
        if spin:
            rival.join()
    size = sum(map(len, blocks))
    line = {"row_bytes": chunk, "form": form,
            "company": "beside_spin" if spin else "alone", "rows": rows,
            "ms_per_row": round(1e3 * wall / rows, 4),
            "ms_per_MiB": round(1e3 * wall / (size / (1 << 20)), 4)}
    if form != "carry":
        line["recopied_share"] = round(stats["recopied_bytes"] / size, 4)
    return line


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mib", type=int, default=64)
    p.add_argument("--repeat", type=int, default=3)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    size = (2 if args.tiny else args.mib) << 20
    blob = text(size, 56)
    blocks = [blob[i:i + BLOCK_BYTES] for i in range(0, size, BLOCK_BYTES)]
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "batch_micro.jsonl"), "w") as sink:
        for chunk in (1 << 20, 1 << 16):
            want = digest("carry", blocks, chunk)
            assert digest("in_place", blocks, chunk) == want
            rows, offsets = digest("in_place_nocount", blocks, chunk)
            assert offsets == want[1] and \
                [r[:2] for r in rows] == [r[:2] for r in want[0]]
            for spin in (False, True):
                for form in FORMS:
                    best = min((timed(form, blocks, chunk, spin)
                                for _ in range(args.repeat)),
                               key=lambda r: r["ms_per_row"])
                    best["cores"] = os.cpu_count()
                    print(json.dumps(best), flush=True)
                    sink.write(json.dumps(best) + "\n")


if __name__ == "__main__":
    main()
