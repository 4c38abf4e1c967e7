"""The serial tail of a word-count stream job, both ways, over a synthetic
merged table (PR 37; PERF.md section 6).  Not a test and not a benchmark
cell, and it needs no chip: the tail is host code, so

    python scripts/tail_micro.py [--words N] [--lanes 4|16] [--nreduce R]

sizes the layer on whatever CPU runs it (a ratio between the two tails,
not a speed of the deployment's host).  The old tail is what
``PackedCounts.finalize`` and the writer did until PR 37: every spelling
decoded into a ``str``, a ``{word: (count, partition)}`` dict, then per
partition a bucket, a ``sorted`` and an f-string a line.  The new tail
hands the merged table to the same writer, which renders each partition
from the arrays.  One JSON line per phase on stdout, then whether the two
sets of ``mr-out-*`` are equal byte for byte.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dsi_tpu.parallel.merge import PackedCounts
from dsi_tpu.parallel.shuffle import write_partitioned_output

_LETTERS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)


def synthetic_accumulator(words: int, lanes: int, n_reduce: int,
                          seed: int) -> PackedCounts:
    """An accumulator fed ``words`` random spellings of 3 to 16 letters
    (to 4 * lanes where that is wider) in eight batches, most counts small
    and a few large, as a Heaps-law stream leaves them."""
    rng = np.random.default_rng(seed)
    width = 4 * lanes
    acc = PackedCounts()
    for _ in range(8):
        n = -(-words // 8)
        raw = _LETTERS[rng.integers(0, len(_LETTERS), (n, width))]
        lens = rng.integers(3, (16 if lanes == 4 else width) + 1, n)
        raw[np.arange(width) >= lens[:, None]] = 0
        keys = raw.view(">u4").astype(np.uint32)
        cnts = np.maximum(1, (rng.pareto(1.1, n) * 2).astype(np.int64))
        # the partition is a function of the word: here of its first lane
        acc.add(keys, lens, cnts, keys[:, 0] % n_reduce)
    return acc


def timed(phase: str, fn, **fields):
    t0 = time.perf_counter()
    out = fn()
    print(json.dumps({"phase": phase,
                      "seconds": round(time.perf_counter() - t0, 4),
                      **fields}), flush=True)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--words", type=int, default=1_700_000)
    p.add_argument("--lanes", type=int, choices=(4, 16), default=4)
    p.add_argument("--nreduce", type=int, default=10)
    p.add_argument("--seed", type=int, default=37)
    args = p.parse_args(argv)

    acc = synthetic_accumulator(args.words, args.lanes, args.nreduce,
                                args.seed)
    table = timed("finalize (the last compaction; the table is the "
                  "result)", acc.finalize)
    with tempfile.TemporaryDirectory() as new_dir, \
            tempfile.TemporaryDirectory() as old_dir:
        stats: dict = {}
        timed("new: write from the table's arrays",
              lambda: write_partitioned_output(table, args.nreduce, new_dir,
                                               stats=stats),
              words=len(table))
        old = timed("old: decode every spelling, build the dict",
                    table.to_dict)
        timed("old: write from the dict (bucket, sort, format)",
              lambda: write_partitioned_output(old, args.nreduce, old_dir,
                                               stats=stats))
        same = all(
            open(os.path.join(new_dir, name), "rb").read()
            == open(os.path.join(old_dir, name), "rb").read()
            for name in sorted(os.listdir(old_dir)))
        size = sum(os.path.getsize(os.path.join(new_dir, name))
                   for name in os.listdir(new_dir))
    print(json.dumps({"byte_identical": same, "bytes": size,
                      "files": args.nreduce, **{
                          k: round(v, 4) if isinstance(v, float) else v
                          for k, v in stats.items()}}))
    return 0 if same else 1


if __name__ == "__main__":
    raise SystemExit(main())
