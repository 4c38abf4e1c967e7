"""The plain reference for a ``grepstream`` job: what its ``mr-out-0`` must
hold.  Straightforward Python over the input bytes; imports nothing of the
program and shares no code with the program's own host scan.

The job, in words:

* The input is one byte stream: the named files in argument order, with one
  newline byte between consecutive files (the entry point's documented
  stream).  With ``passes`` > 1 the file list is that many copies of itself,
  as the harness passes it.
* A record is a newline-delimited line of that stream.  A trailing newline
  opens no final empty record; an unterminated tail is a record.  Records
  are numbered from 0 through the whole stream.
* A record's match count is the number of positions at which the literal
  pattern starts in it: overlapping occurrences count (``aaa`` holds ``aa``
  twice).
* The answer: the number of records (``lines``), of records with a count
  above 0 (``matched``), the sum of the counts (``occurrences``); a
  histogram of ``bins`` buckets, a record falling in bucket
  ``min(count, bins - 1)``; and the ``topk`` records with the highest
  counts, ties to the lower record number, as rank, record number, count.

The lines come back as the entry point commits them (``lines <n>``,
``matched <n>``, ``occurrences <n>``, ``hist <bucket> <n>``,
``top <rank> <line_no> <occurrences>``), sorted, as ``reference.read_output``
reads a job's output.
"""

from __future__ import annotations

from typing import Dict, Iterator, List


def _count(record: bytes, pattern: bytes) -> int:
    """Positions of ``record`` at which ``pattern`` starts."""
    n, at = 0, record.find(pattern)
    while at >= 0:
        n, at = n + 1, record.find(pattern, at + 1)
    return n


def _records(paths: List[str]) -> Iterator[bytes]:
    """The records of the stream that the files make, in order."""
    tail = b""        # the stream's unterminated last record so far
    for i, path in enumerate(paths):
        with open(path, "rb") as f:
            data = tail + (b"\n" if i else b"") + f.read()
        *whole, tail = data.split(b"\n")
        yield from whole
    if tail:
        yield tail


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    pattern = str(params["pattern"]).encode("ascii")
    bins, topk = int(params["bins"]), int(params["topk"])
    hist = [0] * bins
    hits = []         # (count, record number) of every matching record
    number = -1
    for number, record in enumerate(
            _records(list(paths) * int(params.get("passes", 1)))):
        count = _count(record, pattern) if pattern in record else 0
        hist[min(count, bins - 1)] += 1
        if count:
            hits.append((count, number))
    hits.sort(key=lambda h: (-h[0], h[1]))
    out = [f"lines {number + 1}", f"matched {len(hits)}",
           f"occurrences {sum(c for c, _ in hits)}"]
    out += [f"hist {b} {n}" for b, n in enumerate(hist)]
    out += [f"top {rank} {line_no} {count}"
            for rank, (count, line_no) in enumerate(hits[:topk])]
    return sorted(out)
