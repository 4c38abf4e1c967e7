"""Pavlo et al.'s ``UserVisits`` rows as remembered, NumPy only: one
helper, for the plain reference and for the driver alike.

The table of *A Comparison of Approaches to Large-Scale Data Analysis*
(SIGMOD'09): ``sourceIP VARCHAR(16) | destURL VARCHAR(100) | visitDate
DATE | adRevenue FLOAT | userAgent VARCHAR(64) | countryCode VARCHAR(3) |
languageCode VARCHAR(6) | searchWord VARCHAR(32) | duration INT``, text,
``|`` between fields, a newline behind a row, 155 million rows = 20 GB a
node: 129 B a row.  The paper's generator is not here; as drawn, and
listed under ``assumed`` in the configuration:

    sourceIP      a dotted quad out of a pool of ``pool`` addresses
                  (2.5 million: the task's group count), drawn uniformly;
                  address j of the pool is j scrambled by an odd
                  multiplier modulo 2^32, so the pool's are distinct
    destURL       "http://" and lower-case letters, digits, '.' and '/':
                  as long as it takes for the row to have its length
                  (19 to 71 bytes)
    visitDate     YYYY-MM-DD, 2000-01-01 to 2009-12-28
    adRevenue     an integer part 0-999 without leading zeros, a point,
                  1 to 6 fraction digits (DECIMAL where the paper's
                  column is FLOAT: the sum is compared exactly)
    userAgent     24 to 32 bytes of letters, digits, ' ', '/', '.', ';'
    countryCode   3 upper-case letters
    languageCode  "xx-YY"
    searchWord    6 to 12 lower-case letters
    duration      1 to 9999
    a row         119 to 139 bytes, uniformly: 129 in the mean

File ``i`` of a job is seeded from the CRC-32 of the corpus's first
generated file and ``i`` (:func:`job_files`), because the reference and
the driver are handed the same corpus files and no seed; it holds as many
whole rows as fit the corpus file's bytes, so the corpus block of a
configuration (and of its rehearsal) sizes the job.
"""

from __future__ import annotations

import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import List

import numpy as np

POOL = 2_500_000
ROW_BYTES_MIN, ROW_BYTES_MAX = 119, 139
_LOWER = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
_UPPER = np.frombuffer(b"ABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)
_URL = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789./", np.uint8)
_AGENT = np.frombuffer(b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                       b"0123456789 /.;", np.uint8)


def _number(values: np.ndarray, width: int, digits=None):
    """``values`` as decimal digits, right-aligned in ``width`` columns,
    and which columns a row keeps: its own digits, or its last
    ``digits``."""
    values = values.astype(np.int64)
    block = np.empty((len(values), width), np.uint8)
    rest = values.copy()
    for col in range(width - 1, -1, -1):
        rest, digit = np.divmod(rest, 10)
        block[:, col] = digit + 0x30
    if digits is None:
        digits = np.ones(len(values), np.int64)
        for power in range(1, width):
            digits += values >= 10 ** power
    return block, np.arange(width) >= width - digits[:, None]


def _fixed(block: np.ndarray):
    return block, np.ones(block.shape, bool)


def _text(alphabet: np.ndarray, lengths: np.ndarray, width: int,
          rng: np.random.Generator):
    block = alphabet[rng.integers(0, len(alphabet), (len(lengths), width),
                                  dtype=np.uint8)]
    return block, np.arange(width) < lengths[:, None]


def rows(n: int, rng: np.random.Generator, pool: int = POOL) -> np.ndarray:
    """``n`` rows, flat, as ``uint8``."""
    length = rng.integers(ROW_BYTES_MIN, ROW_BYTES_MAX + 1, n)
    address = (rng.integers(0, pool, n, dtype=np.int64) * 2654435761
               + 0x9E3779B9) % (1 << 32)
    sep = _fixed(np.full((n, 1), 0x7C, np.uint8))
    dot = _fixed(np.full((n, 1), 0x2E, np.uint8))
    dash = _fixed(np.full((n, 1), 0x2D, np.uint8))
    ip = []
    for shift in (24, 16, 8, 0):
        ip += [_number((address >> shift) & 255, 3), dot]
    day = rng.integers(0, 3650, n)
    date = [_number(2000 + day // 365, 4), dash,
            _number(1 + day % 365 // 31, 2, np.full(n, 2)), dash,
            _number(1 + day % 365 % 31 % 28, 2, np.full(n, 2))]
    fraction_digits = rng.integers(1, 7, n)
    revenue = [_number(rng.integers(0, 1000, n), 3), dot,
               _number(rng.integers(0, 10 ** 6, n) // 10
                       ** (6 - fraction_digits), 6, fraction_digits)]
    agent = _text(_AGENT, rng.integers(24, 33, n), 32, rng)
    country = _text(_UPPER, np.full(n, 3), 3, rng)
    language = [_text(_LOWER, np.full(n, 2), 2, rng), dash,
                _text(_UPPER, np.full(n, 2), 2, rng)]
    word = _text(_LOWER, rng.integers(6, 13, n), 12, rng)
    duration = _number(rng.integers(1, 10_000, n), 4)
    tail = ([sep] + date + [sep] + revenue + [sep, agent, sep, country, sep]
            + language + [sep, word, sep, duration,
                          _fixed(np.full((n, 1), 0x0A, np.uint8))])
    head = ip[:-1] + [sep]
    taken = sum(keep.sum(axis=1) for _, keep in head + tail)
    url = _text(_URL, length - taken, int((length - taken).max()), rng)
    url[0][:, :7] = np.frombuffer(b"http://", np.uint8)
    pieces = head + [url] + tail
    block = np.concatenate([b for b, _ in pieces], axis=1)
    return block[np.concatenate([keep for _, keep in pieces], axis=1)]


def job_seed(corpus_files: List[str]) -> int:
    """The CRC-32 of the corpus's first generated file."""
    with open(corpus_files[0], "rb") as f:
        return zlib.crc32(f.read())


def job_files(corpus_files: List[str]) -> List[str]:
    """The job's row files beside the corpus, written once a seed: one
    file of rows a corpus file, of as many whole rows as fit the corpus
    file's bytes, named ``v<3 digits>.txt``; their paths in input
    order."""
    directory = os.path.join(os.path.dirname(corpus_files[0]), "uservisits")
    paths = [os.path.join(directory, f"v{i:03d}.txt")
             for i in range(len(corpus_files))]
    done = os.path.join(directory, "DONE")
    if not os.path.exists(done):
        os.makedirs(directory, exist_ok=True)
        seed = job_seed(corpus_files)

        def write(i: int) -> int:
            size = os.path.getsize(corpus_files[i])
            data = rows(size // ROW_BYTES_MIN + 1,
                        np.random.default_rng([seed, i]))
            ends = np.flatnonzero(data == 0x0A) + 1
            whole = int(np.searchsorted(ends, size, side="right"))
            with open(paths[i], "wb") as f:
                f.write(data[:ends[whole - 1] if whole else 0].tobytes())
            return whole

        # a file is a function of (seed, i) alone: made side by side
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            total = sum(pool.map(write, range(len(paths))))
        with open(done, "w") as f:
            f.write(f"{total}\n")
    return paths
