"""The plain reference of the aggregation chain: what a correct job's
``mr-out-*`` must hold, in straightforward Python over the input bytes
(nothing here imports ``dsi_tpu``).

``SELECT f0, SUM(f3) FROM rows GROUP BY f0`` (Pavlo et al., SIGMOD'09,
the Aggregation Task: ``sourceIP``, ``adRevenue`` of ``UserVisits``), and
with ``prefix`` ``GROUP BY SUBSTR(f0, 1, prefix)``.  A file is rows
``f0|f1|...\\n`` (the last may lack its newline); the key is field 0, 1-16
bytes of printable ASCII; the value is field 3,
``[0-9]{1,3}(\\.[0-9]{1,6})?``, taken through integer arithmetic on its
digits as a count of 10^-6 units: no ``float``, so the sum does not depend
on the order a MapReduce leaves open, and can be compared byte for byte.
A line of the answer is ``<key> <units // 10^6>.<units % 10^6, six
digits>``; a key's partition is FNV-1a 32 of its bytes, ``& 0x7fffffff``,
modulo ``n_reduce`` (the lab's ``ihash``), and a partition's lines are in
key order.  Any other row raises ``ValueError``: the job it stands for
fails.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional

DECIMALS = 6
_VALUE = re.compile(rb"([0-9]{1,3})(?:\.([0-9]{1,6}))?")
_KEY = re.compile(rb"[\x20-\x7e]{1,16}")  # split took the '|' out


def units(field: bytes) -> int:
    """A value field as 10^-6 units."""
    m = _VALUE.fullmatch(field)
    if not m:
        raise ValueError(f"value {field!r} is not [0-9]{{1,3}}(.[0-9]{{1,6}})?")
    fraction = (m.group(2) or b"").ljust(DECIMALS, b"0")
    return int(m.group(1)) * 10 ** DECIMALS + int(fraction)


def sums_of_rows(rows: Iterable[bytes], prefix: int = 0,
                 total: Optional[Dict[bytes, int]] = None, where: str = ""
                 ) -> Dict[bytes, int]:
    """The rows' sums by key, added to ``total``."""
    total = {} if total is None else total
    for number, row in enumerate(rows, 1):
        fields = row.split(b"|")
        if len(fields) < 4:
            raise ValueError(f"{where}:{number}: {len(fields)} fields")
        key = fields[0]
        if not _KEY.fullmatch(key):
            raise ValueError(f"{where}:{number}: key {key!r}")
        try:
            value = units(fields[3])
        except ValueError as e:
            raise ValueError(f"{where}:{number}: {e}") from None
        if prefix:
            key = key[:prefix]
        total[key] = total.get(key, 0) + value
    return total


def sums(paths: List[str], prefix: int = 0) -> Dict[bytes, int]:
    total: Dict[bytes, int] = {}
    for path in paths:
        with open(path, "rb") as f:
            rows = f.read().split(b"\n")
        if rows[-1] == b"":
            rows.pop()  # the newline behind the last row
        sums_of_rows(rows, prefix, total, path)
    return total


def line(key: bytes, total: int) -> str:
    return (f"{key.decode('ascii')} {total // 10 ** DECIMALS}."
            f"{total % 10 ** DECIMALS:0{DECIMALS}d}")


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    """The answer's lines, sorted, as ``reference.read_output`` gives a
    job's (a pass more multiplies every sum)."""
    passes = int(params.get("passes", 1))
    return sorted(line(key, total * passes) for key, total
                  in sums(paths, int(params.get("prefix", 0))).items())


def ihash(key: bytes) -> int:
    h = 0x811C9DC5
    for b in key:
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def partitions(paths: List[str], n_reduce: int, prefix: int = 0
               ) -> List[bytes]:
    """``mr-out-0`` .. ``mr-out-<n_reduce - 1>`` as bytes."""
    parts: List[List[str]] = [[] for _ in range(n_reduce)]
    for key, total in sorted(sums(paths, prefix).items()):
        parts[ihash(key) % n_reduce].append(line(key, total) + "\n")
    return ["".join(part).encode("ascii") for part in parts]
