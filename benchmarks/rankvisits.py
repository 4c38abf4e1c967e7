"""Pavlo et al.'s ``Rankings`` and ``UserVisits`` for the Join Task, as
remembered, NumPy only: one helper, for the plain reference and for the
driver alike.

*A Comparison of Approaches to Large-Scale Data Analysis* (SIGMOD'09):
``Rankings(pageURL VARCHAR(100) PRIMARY KEY, pageRank INT, avgDuration
INT)``, 18 million rows = 1 GB a node, and ``UserVisits``
(``uservisits.py``'s nine columns), 155 million rows = 20 GB a node; text,
``|`` between fields, a newline behind a row.  One worker's share after
the partitioning by URL: every ``destURL`` of its visits is a ``pageURL``
of its rankings.  As drawn, and listed under ``assumed`` in the
configuration:

    Rankings    one row a distinct pageURL: "http://" and 12 to 52 bytes
                of ``uservisits.py``'s URL alphabet, uniformly (19 to 59
                bytes, 39 in the mean: the other eight columns are 90 in
                the mean, and a visit's row stays the paper's 129; with
                12 to 64 more bytes it would be 135); pageRank 1 to 9999;
                avgDuration 1
                to 999; rows in random order over ``RANK_FILES`` files.
                As many rows as the corpus's bytes hold visits of 129 B,
                times 18 / 155, rounded.
    UserVisits  ``uservisits.py``'s columns, alphabets and draws, but
                destURL: drawn uniformly from the share's pageURLs.  A row
                is then 87 to 159 bytes, 129 in the mean.

File ``i`` of the visits is seeded from the CRC-32 of the corpus's first
generated file and ``i`` (``uservisits.job_seed``), the rankings from the
same and ``PAGES_STREAM``, because the reference and the driver are handed
the same corpus files and no seed; a visits file holds as many whole rows
as fit its corpus file's bytes, so the corpus block of a configuration
(and of its rehearsal) sizes the job.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

from uservisits import (_AGENT, _LOWER, _UPPER, _URL, POOL, _fixed, _number,
                        _text, job_seed)

RANK_FILES = 4
ROW_BYTES_MEAN = 129
URL_BYTES_MIN, URL_BYTES_MAX = 19, 59
#: The rankings' stream of the job's seed (a visits file's is its ordinal).
PAGES_STREAM = 1_000_000
_PREFIX = np.frombuffer(b"http://", np.uint8)


def pages(n: int, rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` distinct page URLs: ``uint8[n, 59]`` (zero past a URL's end)
    and their lengths."""
    urls = np.zeros((0, URL_BYTES_MAX), np.uint8)
    while len(urls) < n:
        more = n - len(urls)
        lengths = rng.integers(URL_BYTES_MIN, URL_BYTES_MAX + 1, more)
        block, keep = _text(_URL, lengths, URL_BYTES_MAX, rng)
        block[:, :len(_PREFIX)] = _PREFIX
        urls = np.concatenate([urls, np.where(keep, block, 0)])
        _, first = np.unique(urls.view(f"S{URL_BYTES_MAX}").ravel(),
                             return_index=True)
        urls = urls[np.sort(first)]
    return urls, np.count_nonzero(urls, axis=1)


def ranking_rows(urls: np.ndarray, lengths: np.ndarray,
                 rng: np.random.Generator) -> np.ndarray:
    """A ``Rankings`` row a URL, flat, as ``uint8``."""
    n = len(urls)
    sep = _fixed(np.full((n, 1), 0x7C, np.uint8))
    pieces = [(urls, np.arange(URL_BYTES_MAX) < lengths[:, None]), sep,
              _number(rng.integers(1, 10_000, n), 4), sep,
              _number(rng.integers(1, 1_000, n), 3),
              _fixed(np.full((n, 1), 0x0A, np.uint8))]
    block = np.concatenate([b for b, _ in pieces], axis=1)
    return block[np.concatenate([keep for _, keep in pieces], axis=1)]


def visit_rows(n: int, rng: np.random.Generator, urls: np.ndarray,
               lengths: np.ndarray, pool: int = POOL) -> np.ndarray:
    """``n`` ``UserVisits`` rows, flat, as ``uint8``: ``uservisits.rows``'
    columns in its order of draws, the destURL one of ``urls``."""
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
    page = rng.integers(0, len(urls), n)
    url = (urls[page], np.arange(URL_BYTES_MAX) < lengths[page][:, None])
    pieces = (ip[:-1] + [sep, url, sep] + date + [sep] + revenue
              + [sep, agent, sep, country, sep] + language
              + [sep, word, sep, duration,
                 _fixed(np.full((n, 1), 0x0A, np.uint8))])
    block = np.concatenate([b for b, _ in pieces], axis=1)
    return block[np.concatenate([keep for _, keep in pieces], axis=1)]


def job_files(corpus_files: List[str]) -> Tuple[List[str], List[str]]:
    """The job's two tables beside the corpus, written once a seed:
    ``rankings/r<3 digits>.txt`` and ``visits/v<3 digits>.txt`` (one a
    corpus file, of as many whole rows as fit its bytes); the paths of
    both in input order."""
    root = os.path.dirname(corpus_files[0])
    ranks = [os.path.join(root, "rankings", f"r{i:03d}.txt")
             for i in range(RANK_FILES)]
    visits = [os.path.join(root, "visits", f"v{i:03d}.txt")
              for i in range(len(corpus_files))]
    done = os.path.join(root, "visits", "DONE")
    if not os.path.exists(done):
        for path in (ranks[0], visits[0]):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        seed = job_seed(corpus_files)
        sizes = [os.path.getsize(path) for path in corpus_files]
        rng = np.random.default_rng([seed, PAGES_STREAM])
        urls, lengths = pages(
            max(1, round(sum(sizes) // ROW_BYTES_MEAN * 18 / 155)), rng)
        for i, path in enumerate(ranks):
            part = slice(i, None, RANK_FILES)
            with open(path, "wb") as f:
                f.write(ranking_rows(urls[part], lengths[part],
                                     rng).tobytes())

        def write(i: int) -> int:
            data = visit_rows(sizes[i] // 100 + 1,
                              np.random.default_rng([seed, i]), urls,
                              lengths)
            ends = np.flatnonzero(data == 0x0A) + 1
            whole = int(np.searchsorted(ends, sizes[i], side="right"))
            with open(visits[i], "wb") as f:
                f.write(data[:ends[whole - 1] if whole else 0].tobytes())
            return whole

        # a file is a function of (seed, i) and the pages alone
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            total = sum(pool.map(write, range(len(visits))))
        with open(done, "w") as f:
            f.write(f"{len(urls)} {total}\n")
    return ranks, visits
