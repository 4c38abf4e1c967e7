"""Documents cut from the generated files: one helper, for the plain
reference and for the driver alike.

``corpus.py`` gives every generated file a vocabulary of its own, so files
used as documents would give nearly every term a document frequency of 1.
A generated file is therefore a shelf: it is cut into consecutive
documents, which share the shelf's vocabulary as the books of one language
share theirs.

The cut, in words (:func:`spans`):

* A document's length is drawn log-uniformly from ``doc_min_bytes`` to
  ``doc_max_bytes`` (the end excluded); the cut then moves back to just
  after the last whitespace byte before it, so that no word is cut in two,
  or forward to the next one where moving back would leave the document
  shorter than ``doc_min_bytes``.
* A draw that reaches the file's end makes the file's tail the last
  document.  A tail shorter than ``doc_min_bytes`` joins the document
  before it; where the two together reach ``doc_max_bytes``, they are cut
  in half instead (at whitespace, as above).  So every document of a file
  longer than ``doc_min_bytes`` is ``doc_min_bytes`` to ``doc_max_bytes``
  long, and no program shape depends on where a file happens to end.
* The draw is seeded by the CRC-32 of the first file, because the
  reference and the driver are handed the same files and no seed.

The documents partition the files: every byte of every file is in exactly
one document, in file order.  A document's name is ``d<ordinal, 5
digits>.txt``, the ordinal counted over all files in the order given.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, Iterator, List, Tuple

import numpy as np

_WHITE = b" \n\t\r"


def name(ordinal: int) -> str:
    return f"d{ordinal:05d}.txt"


def _settle(data: bytes, start: int, end: int, least: int) -> int:
    """The cut at ``end`` moved back to just after the last whitespace
    byte of ``data[start:end]``, or forward where that leaves less than
    ``least`` bytes."""
    cut = end
    while cut > start and data[cut - 1] not in _WHITE:
        cut -= 1
    if cut - start >= least:
        return cut
    cut = end
    while cut < len(data) and data[cut - 1] not in _WHITE:
        cut += 1
    return cut


def cuts(data: bytes, rng: np.random.Generator, lo: int, hi: int
         ) -> List[int]:
    """Where one file's documents end, the file's end last."""
    n, pos, ends = len(data), 0, []
    log_lo, log_hi = math.log(lo), math.log(hi)
    while True:
        length = min(hi - 1, int(math.exp(rng.uniform(log_lo, log_hi))))
        if pos + length >= n:
            break
        pos = _settle(data, pos, pos + length, lo)
        if pos >= n:
            break
        ends.append(pos)
    if n - pos < lo and ends:
        # a short tail: to the document before it, or the two halved
        ends.pop()
        pos = ends[-1] if ends else 0
        if n - pos >= hi:
            ends.append(_settle(data, pos, pos + (n - pos) // 2, lo))
    ends.append(n)
    return ends


def spans(paths: List[str], params: Dict[str, object]
          ) -> Iterator[Tuple[str, bytes]]:
    """``(name, bytes)`` of every document of the files, in order."""
    lo = int(params["doc_min_bytes"])
    hi = int(params["doc_max_bytes"])
    rng = None
    ordinal = 0
    for path in paths:
        with open(path, "rb") as f:
            data = f.read()
        if rng is None:
            rng = np.random.default_rng(zlib.crc32(data))
        start = 0
        for end in cuts(data, rng, lo, hi):
            yield name(ordinal), data[start:end]
            ordinal += 1
            start = end
