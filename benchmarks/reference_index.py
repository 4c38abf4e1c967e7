"""The plain reference for a ``planrun --chain indexer`` job: what its
``mr-out-*`` and its ``plan-join.json`` must hold.  Straightforward Python
over the input bytes; imports nothing of the program.  ``docs.py``, the
benchmark's own helper, cuts the generated files into the job's documents,
for this file and for the driver alike.

The job, in words (Dean & Ghemawat, OSDI'04 sec. 2.3; MIT 6.5840
``mrapps/indexer.go``):

* A document is one input file of the job, named by its basename.
* A word is a maximal run of ASCII letters.
* The index has one line per distinct word of the collection,
  ``"<word> <n> <doc>,<doc>,..."``: the names of the documents that hold
  the word, sorted and unique, and their number ``n`` (the word's document
  frequency).
* The chain's second and third stages: the ``topk`` words of highest
  document frequency, frequency descending and word ascending, as
  ``"#top <rank> <df> <word>"`` (rank from 1), and each one's postings
  again as ``"#join <word> <df> <doc>,<doc>,..."``.  No word starts with
  ``#``, so the three kinds of line cannot be mistaken for one another.

The lines come back sorted, as ``reference.read_output`` reads a job's
output (the driver renders ``plan-join.json`` into the ``#`` lines beside
the committed ``mr-out-<r>``).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

import docs

_WORD = re.compile(rb"[A-Za-z]+")


def index_lines(documents: Iterable[Tuple[str, bytes]], topk: int
                ) -> List[str]:
    """The lines for ``(name, bytes)`` documents."""
    postings: Dict[bytes, set] = {}
    for name, data in documents:
        for word in set(_WORD.findall(data)):
            postings.setdefault(word, set()).add(name)
    rows = {word.decode("ascii"): sorted(names)
            for word, names in postings.items()}
    lines = [f"{word} {len(names)} {','.join(names)}"
             for word, names in rows.items()]
    leaders = sorted(rows, key=lambda word: (-len(rows[word]), word))[:topk]
    for rank, word in enumerate(leaders, 1):
        names = rows[word]
        lines.append(f"#top {rank} {len(names)} {word}")
        lines.append(f"#join {word} {len(names)} {','.join(names)}")
    return sorted(lines)


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    """``reference.KINDS['index']``: the generated files cut into the
    job's documents (``docs.spans``), then :func:`index_lines`."""
    return index_lines(docs.spans(paths, params), int(params["topk"]))
