"""``gensort -a`` records as remembered, NumPy only: one helper, for the
plain reference and for the driver alike.

The Sort Benchmark's generator writes 100-byte ASCII records (the Indy
rule: uniform keys).  As remembered, and listed under ``assumed`` in the
configuration because there is no tool here to check it against:

    bytes  0- 9  the key: 10 bytes drawn uniformly from the 95 printable
                 ASCII characters (0x20 ' ' to 0x7E '~')
    bytes 10-11  two spaces
    bytes 12-43  the record number, 32 upper-case hex digits
    bytes 44-45  two spaces
    bytes 46-97  52 bytes of filler, printable, a function of the record
                 number: 13 groups of 4 equal hex digits, group g the
                 g-th nibble of the record number from the low end
    bytes 98-99  "\\r\\n"

File ``i`` of a job is seeded from the CRC-32 of the corpus's first
generated file and ``i`` (:func:`job_files`), because the reference and
the driver are handed the same corpus files and no seed; it holds as many
whole records as corpus file ``i`` has hundreds of bytes, so the corpus
block of a configuration (and of its rehearsal) sizes the job.  Record
numbers run on from file to file, so a record's number is its input
ordinal.

``distinct_keys`` (the tests' duplicate-key inputs) draws every key from a
pool of that many keys instead.
"""

from __future__ import annotations

import os
import zlib
from typing import List, Optional

import numpy as np

RECORD_BYTES = 100
KEY_BYTES = 10
_HEX = np.frombuffer(b"0123456789ABCDEF", np.uint8)


def records(n: int, first_number: int, rng: np.random.Generator,
            distinct_keys: Optional[int] = None) -> np.ndarray:
    """``n`` records numbered from ``first_number``, as ``uint8[n, 100]``."""
    out = np.full((n, RECORD_BYTES), 0x20, np.uint8)
    if distinct_keys:
        pool = rng.integers(0x20, 0x7F, (int(distinct_keys), KEY_BYTES),
                            dtype=np.uint8)
        out[:, :KEY_BYTES] = pool[rng.integers(0, len(pool), n)]
    else:
        out[:, :KEY_BYTES] = rng.integers(0x20, 0x7F, (n, KEY_BYTES),
                                          dtype=np.uint8)
    number = np.arange(first_number, first_number + n, dtype=np.uint64)
    out[:, 12:28] = _HEX[0]  # the record number's high 64 bits
    for digit in range(16):
        nibble = ((number >> np.uint64(4 * digit))
                  & np.uint64(15)).astype(np.intp)
        out[:, 43 - digit] = _HEX[nibble]
        if digit < 13:
            out[:, 46 + 4 * digit:50 + 4 * digit] = _HEX[nibble][:, None]
    out[:, 98] = 0x0D
    out[:, 99] = 0x0A
    return out


def job_seed(corpus_files: List[str]) -> int:
    """The CRC-32 of the corpus's first generated file."""
    with open(corpus_files[0], "rb") as f:
        return zlib.crc32(f.read())


def job_files(corpus_files: List[str]) -> List[str]:
    """The job's record files beside the corpus, written once a seed: one
    record file a corpus file, of as many whole records as the corpus file
    has hundreds of bytes, named ``r<3 digits>.dat``; their paths in input
    order."""
    directory = os.path.join(os.path.dirname(corpus_files[0]), "gensort")
    paths = [os.path.join(directory, f"r{i:03d}.dat")
             for i in range(len(corpus_files))]
    done = os.path.join(directory, "DONE")
    if not os.path.exists(done):
        os.makedirs(directory, exist_ok=True)
        seed = job_seed(corpus_files)
        first = 0
        for i, (path, text) in enumerate(zip(paths, corpus_files)):
            n = os.path.getsize(text) // RECORD_BYTES
            rng = np.random.default_rng([seed, i])
            with open(path, "wb") as f:
                f.write(records(n, first, rng).tobytes())
            first += n
        with open(done, "w") as f:
            f.write(f"{first}\n")
    return paths
