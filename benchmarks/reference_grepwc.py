"""The plain reference for a ``planrun --chain grep-wc`` job: what its
``mr-out-*`` must hold.  Straightforward Python over the input bytes;
imports nothing of the program and shares no code with the program's own
host scan or with its staged mode.

The job, in words:

* The input is one byte stream: the named files in argument order, with one
  newline byte between consecutive files (the entry point's documented
  stream).  With ``passes`` > 1 every count is that many times what one
  pass gives, as for ``reference.wc_lines``.
* A record is a newline-delimited line of that stream.
* A record passes if the literal pattern occurs in it.
* A word is a maximal run of ASCII letters.  No letter is a newline, so no
  word reaches from one record into the next.
* The answer is one line ``"<word> <count>"`` per distinct word of the
  records that passed, the count being the word's occurrences in them.

The lines come back sorted, as ``reference.read_output`` reads a job's
output.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List

_WORD = re.compile(rb"[A-Za-z]+")


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    pattern = str(params["pattern"]).encode("ascii")
    parts = []
    for path in paths:
        with open(path, "rb") as f:
            parts.append(f.read())
    total: collections.Counter = collections.Counter()
    for record in b"\n".join(parts).split(b"\n"):
        if pattern in record:
            total.update(_WORD.findall(record))
    passes = int(params.get("passes", 1))
    return sorted(f"{word.decode('ascii')} {count * passes}"
                  for word, count in total.items())
