"""The plain reference: what a correct job's ``mr-out-*`` must hold.

Straightforward Python over the input bytes, independent of the program
(nothing here imports ``dsi_tpu``):

* ``wc``: a word is a maximal run of ASCII letters; the answer is one line
  ``"<word> <count>"`` per distinct word (MIT 6.5840 ``mrapps/wc.go``
  semantics on ASCII text).
* ``grep``: a record is a line (split on ``"\\n"``, the unterminated tail
  included); the answer is one line ``"<line> <count>"`` per distinct line
  that Python's ``re`` finds the pattern in.

The lines come back sorted, as ``sort mr-out-* | grep .`` gives them
(MIT ``test-mr.sh``); :func:`read_output` reads a job's output the same way.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List

_WORD = re.compile(rb"[A-Za-z]+")


def wc_lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    total: collections.Counter = collections.Counter()
    for path in paths:
        with open(path, "rb") as f:
            total.update(_WORD.findall(f.read()))
    passes = int(params.get("passes", 1))
    return sorted(f"{w.decode('ascii')} {c * passes}"
                  for w, c in total.items())


def grep_lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    pat = re.compile(str(params["pattern"]))
    total: collections.Counter = collections.Counter()
    for path in paths:
        with open(path, "rb") as f:
            text = f.read().decode("ascii")
        total.update(line for line in text.split("\n") if pat.search(line))
    passes = int(params.get("passes", 1))
    return sorted(f"{line} {c * passes}" for line, c in total.items())


#: ``reference`` in a traffic file names one of these.
KINDS = {"wc": wc_lines, "grep": grep_lines}


def read_output(workdir: str) -> List[str]:
    """Every non-empty line of ``workdir/mr-out-*``, sorted."""
    lines: List[str] = []
    for path in glob.glob(os.path.join(workdir, "mr-out-*")):
        with open(path, encoding="ascii") as f:
            lines.extend(l.rstrip("\n") for l in f if l.strip())
    return sorted(lines)
