"""The plain reference of the join chain: what a correct job's ``mr-out-*``
and ``plan-top.json`` must hold, in straightforward Python over the input
bytes (nothing here imports ``dsi_tpu``).

Pavlo et al., SIGMOD'09, the Join Task::

    SELECT sourceIP, SUM(adRevenue), AVG(pageRank)
      FROM Rankings, UserVisits
     WHERE pageURL = destURL AND visitDate BETWEEN <first> AND <last>
     GROUP BY sourceIP;
    -- and the row of the largest SUM(adRevenue)

A ``Rankings`` file is rows ``pageURL|pageRank|...\\n`` (the key 1-100
bytes of printable ASCII and a primary key: a second row with one
``pageURL`` raises; the rank ``[0-9]{1,9}``), a ``UserVisits`` file rows
``sourceIP|destURL|visitDate|adRevenue|...\\n`` (``sourceIP`` 1-16 bytes,
``destURL`` 1-100, the date ``YYYY-MM-DD`` and compared as its ten bytes,
both ends of the window inclusive, the revenue ``reference_agg.units``'
decimal); a file's last row may lack its newline.  Every row of both
tables has to be readable, inside the window or not: any other raises
``ValueError``, and the job it stands for fails.  The sums are ``int``;
the average is ``rank sum * 10^6 // rows``, truncated: no ``float``, so the
answer does not depend on the order a MapReduce leaves open and is compared
byte for byte.  A line of the answer is ``<sourceIP> <revenue> <average>``,
both numbers ``<integer part>.<six digits>``; a key's partition is the
lab's ``ihash``, and a partition's lines are in key order.  The second
statement's row is one more line, ``#top <sourceIP> <revenue> <average>``
(ties: the least ``sourceIP``), which no joined row leaves out.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from reference_agg import DECIMALS, ihash, units

_URL = re.compile(rb"[\x20-\x7e]{1,100}")  # split took the '|' out
_IP = re.compile(rb"[\x20-\x7e]{1,16}")
_RANK = re.compile(rb"[0-9]{1,9}")
_DATE = re.compile(rb"[0-9]{4}-[0-9]{2}-[0-9]{2}")

#: The window of a job whose traffic names none: the paper's.
DATES = "2000-01-15:2000-01-22"


def _rows(path: str) -> List[bytes]:
    with open(path, "rb") as f:
        rows = f.read().split(b"\n")
    if rows[-1] == b"":
        rows.pop()  # the newline behind the last row
    return rows


def rankings(paths: List[str]) -> Dict[bytes, int]:
    """``pageURL`` to ``pageRank``."""
    ranks: Dict[bytes, int] = {}
    seen: Dict[bytes, str] = {}
    for path in paths:
        for number, row in enumerate(_rows(path), 1):
            fields = row.split(b"|")
            if len(fields) < 2 or not _URL.fullmatch(fields[0]) \
                    or not _RANK.fullmatch(fields[1]):
                raise ValueError(f"{path}:{number}: bad row {row[:120]!r}")
            if fields[0] in ranks:
                raise ValueError(f"{seen[fields[0]]} and {path}:{number} "
                                 f"hold one key {fields[0]!r}")
            ranks[fields[0]] = int(fields[1])
            seen[fields[0]] = f"{path}:{number}"
    return ranks


def sums(build_paths: List[str], probe_paths: List[str], dates: str = DATES
         ) -> Tuple[Dict[bytes, List[int]], Dict[str, int]]:
    """``sourceIP`` to ``[revenue units, rank sum, rows]`` over the joined
    rows, and the counts a job's counters are held to: rows of either
    table, rows inside the window, rows matched."""
    first, last = (d.encode("ascii") for d in dates.split(":"))
    ranks = rankings(build_paths)
    total: Dict[bytes, List[int]] = {}
    counts = {"build_rows": len(ranks), "probe_rows": 0, "window_rows": 0,
              "matched_rows": 0}
    for path in probe_paths:
        for number, row in enumerate(_rows(path), 1):
            fields = row.split(b"|")
            try:
                if len(fields) < 4 or not _IP.fullmatch(fields[0]) \
                        or not _URL.fullmatch(fields[1]) \
                        or not _DATE.fullmatch(fields[2]):
                    raise ValueError(f"bad row {row[:160]!r}")
                revenue = units(fields[3])
            except ValueError as e:
                raise ValueError(f"{path}:{number}: {e}") from None
            counts["probe_rows"] += 1
            if not first <= fields[2] <= last:
                continue
            counts["window_rows"] += 1
            rank = ranks.get(fields[1])
            if rank is None:
                continue
            counts["matched_rows"] += 1
            group = total.setdefault(fields[0], [0, 0, 0])
            group[0] += revenue
            group[1] += rank
            group[2] += 1
    return total, counts


def _decimal(value: int) -> str:
    return f"{value // 10 ** DECIMALS}.{value % 10 ** DECIMALS:0{DECIMALS}d}"


def line(key: bytes, group: List[int]) -> str:
    revenue, rank, rows = group
    return (f"{key.decode('ascii')} {_decimal(revenue)} "
            f"{_decimal(rank * 10 ** DECIMALS // rows)}")


def top(total: Dict[bytes, List[int]]) -> Optional[bytes]:
    """The key of the largest revenue, the least among equals."""
    return min(total, key=lambda k: (-total[k][0], k)) if total else None


def tables(corpus_files: List[str]) -> Tuple[List[str], List[str]]:
    """The two tables ``rankvisits.py`` writes beside a corpus."""
    import rankvisits

    return rankvisits.job_files(corpus_files)


def lines_of(total: Dict[bytes, List[int]]) -> List[str]:
    """The answer's lines, sorted, as ``reference.read_output`` gives a
    job's, the ``#top`` line among them."""
    out = [line(key, group) for key, group in total.items()]
    best = top(total)
    if best is not None:
        out.append("#top " + line(best, total[best]))
    return sorted(out)


def lines(corpus_files: List[str], params: Dict[str, object]) -> List[str]:
    """The harness's reference of kind ``join``: it gets the corpus's
    files, beside which the tables are."""
    build_paths, probe_paths = tables(corpus_files)
    return lines_of(sums(build_paths, probe_paths,
                         str(params.get("dates", DATES)))[0])


def partitions(build_paths: List[str], probe_paths: List[str], dates: str,
               n_reduce: int) -> List[bytes]:
    """``mr-out-0`` .. ``mr-out-<n_reduce - 1>`` as bytes."""
    total, _ = sums(build_paths, probe_paths, dates)
    parts: List[List[str]] = [[] for _ in range(n_reduce)]
    for key in sorted(total):
        parts[ihash(key) % n_reduce].append(line(key, total[key]) + "\n")
    return ["".join(part).encode("ascii") for part in parts]
