"""The plain reference of the sort chain: what a correct job's ``mr-out-*``
must hold, in straightforward Python over the input bytes (nothing here
imports ``dsi_tpu``).

A record is 100 bytes, its key the first 10, compared as unsigned bytes;
the rest is opaque.  The answer is every record of the input, ordered by
``(key, input ordinal)``: ties keep input order (file order, then offset).

:func:`lines` returns the records as the harness reads them back:
``reference.read_output`` opens ``mr-out-*`` as ASCII text with universal
newlines and strips the line end, so a ``gensort -a`` record (which ends in
``"\\r\\n"``) comes back as its first 98 characters.  The harness compares
sorted lists of lines, which cannot see the order of the output; the
driver's own condition reads the committed bytes for that.

:func:`partitions` is the whole committed answer, byte for byte: the
records cut into ``n_reduce`` partitions by TeraSort's sampled range
partitioner, written out here independently of the program's: ``sample``
keys (fewer where the input holds fewer) at evenly spaced record ordinals
``j * n // m``, sorted; split point ``r`` the sample's key at position
``r * m // n_reduce`` (``r`` = 1 .. ``n_reduce`` - 1); a record's
partition the number of split points that are less than or equal to its
key.
"""

from __future__ import annotations

import bisect
from typing import Dict, List

RECORD_BYTES = 100
KEY_BYTES = 10


def read_records(paths: List[str]) -> List[bytes]:
    """Every record of the files, in input order."""
    out: List[bytes] = []
    for path in paths:
        with open(path, "rb") as f:
            data = f.read()
        if len(data) % RECORD_BYTES:
            raise ValueError(f"{path}: {len(data)} bytes is not a whole "
                             f"number of {RECORD_BYTES}-byte records")
        out.extend(data[i:i + RECORD_BYTES]
                   for i in range(0, len(data), RECORD_BYTES))
    return out


def ordered(records: List[bytes]) -> List[bytes]:
    """The records by ``(key, input ordinal)``."""
    keyed = sorted((record[:KEY_BYTES], i)
                   for i, record in enumerate(records))
    return [records[i] for _, i in keyed]


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    """The ordered records as ``read_output`` gives them back."""
    return [record[:RECORD_BYTES - 2].decode("ascii")
            for record in ordered(read_records(paths))]


def split_points(records: List[bytes], n_reduce: int,
                 sample: int = 100_000) -> List[bytes]:
    n = len(records)
    m = min(int(sample), n)
    keys = sorted(records[j * n // m][:KEY_BYTES] for j in range(m))
    return [keys[r * m // n_reduce] for r in range(1, n_reduce)] if m else []


def partitions(paths: List[str], n_reduce: int,
               sample: int = 100_000) -> List[bytes]:
    """``mr-out-0`` .. ``mr-out-<n_reduce - 1>`` as bytes."""
    records = read_records(paths)
    splits = split_points(records, n_reduce, sample)
    parts: List[List[bytes]] = [[] for _ in range(n_reduce)]
    for record in ordered(records):
        parts[bisect.bisect_right(splits, record[:KEY_BYTES])].append(record)
    return [b"".join(part) for part in parts]
