"""TPU grep tier 3: top-level alternation of fixed-length branches.

Widens the device scope one more step past ``ops/regexk.py``: a pattern
that is a top-level ``|``-alternation whose every branch is itself
device-eligible — a plain literal (``ops/grepk.py``) or a fixed-length
class pattern (``ops/regexk.py``) — runs as one kernel pass PER BRANCH
with the branches' matched-line-end bit words OR-ed on device.
``the|and``, ``[Cc]at|[Dd]og``, ``^\\d\\d|total`` all land here;
variable-length operators, groups, or an ineligible branch still fall
back to the host app (``backends/tpu.py`` contract: correctness never
depends on a kernel).

Python ``re`` semantics hold exactly: alternation binds loosest, so
``re.search(a|b, line)`` is ``search(a) or search(b)`` per line, i.e. the
bitwise OR of the branches' hit words; per-branch anchors
(``^a|b$`` parses as ``(^a)|(b$)``) are handled by each branch's own
parser.  No new kernels and no new AOT entries beyond the branch programs
themselves — an alternation of already-warmed branch shapes reuses their
cached executables as-is.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from dsi_tpu.ops.grepk import (
    _grep_jit,
    ascii_text,
    is_literal_pattern,
    lines_from_hits,
    pad_chunk,
    run_kernel,
    upload_chunk,
)
from dsi_tpu.ops.regexk import _classgrep_compiled, parse_class_pattern


def split_top_level(pat: str) -> Optional[List[str]]:
    """Split ``pat`` on top-level ``|`` (escape-aware; ``|`` inside a
    ``[...]`` class is a literal) into branches, in order and without
    dedup.  None on an unterminated class or any empty branch (``a|`` —
    the empty regex matches every line; host handles it).  A pattern
    with no top-level ``|`` returns a single-element list.  Shared with
    the NFA tier (``ops/nfak.py``), which accepts single branches."""
    branches, cur, in_class, i = [], [], False, 0
    while i < len(pat):
        c = pat[i]
        if c == "\\" and i + 1 < len(pat):
            cur += [c, pat[i + 1]]
            i += 2
            continue
        if c == "[" and not in_class:
            in_class = True
        elif c == "]" and in_class:
            in_class = False
        elif c == "|" and not in_class:
            branches.append("".join(cur))
            cur = []
            i += 1
            continue
        cur.append(c)
        i += 1
    branches.append("".join(cur))
    if in_class or any(not b for b in branches):
        return None
    return branches


def split_alternation(pat: str) -> Optional[List[str]]:
    """Split ``pat`` on top-level ``|`` into >= 2 non-empty branches, or
    None when it isn't a plain alternation.  Duplicate branches add
    kernel passes but never change the OR, so they are removed; a
    pattern that collapses to one distinct branch ('a|a') is not a real
    alternation — tiers 1/2 or the host own it, keeping the >= 2
    contract exact for callers."""
    branches = split_top_level(pat)
    if branches is None:
        return None
    branches = list(dict.fromkeys(branches))
    if len(branches) < 2:
        return None
    return branches


def _branch_hits(chunk, branch: str):
    """(hit_bits, n_lines) for one branch — literal branches via the
    shifted-compare kernel, class branches via the range-compare kernel."""
    if is_literal_pattern(branch):
        pat = jnp.asarray(
            np.frombuffer(branch.encode("ascii"), dtype=np.uint8))
        return _grep_jit(chunk, pat)
    ranges, anchor_start, anchor_end = parse_class_pattern(branch)
    return _classgrep_compiled(int(chunk.shape[0]), ranges, anchor_start,
                               anchor_end)(chunk)


def altgrep_host_result(data: bytes, pattern: str) -> Optional[List[str]]:
    """Matching lines of ``data`` (split on '\\n', in order), or None when
    the pattern or data needs the host regex path."""
    branches = split_alternation(pattern)
    if branches is None:
        return None
    any_class = False
    for b in branches:
        if is_literal_pattern(b):
            continue
        if parse_class_pattern(b) is None:
            return None  # branch outside both device tiers
        any_class = True
    text = ascii_text(data, nul_ok=not any_class)
    if text is None:
        return None
    # A literal longer than the DATA (not the padded chunk: padding is
    # zeros, unmatchable by printable literals) cannot match: it adds no
    # words and compiles no dead kernel.
    live = [b for b in branches
            if not (is_literal_pattern(b) and len(b) > len(data))]
    if not live:
        return []
    chunk = upload_chunk(pad_chunk(data))

    def run():
        hits = [_branch_hits(chunk, b) for b in live]
        total = hits[0][0]
        for words, _ in hits[1:]:
            total |= words
        return total, hits[0][1]  # n_lines is chunk-derived: same each

    hit_bits, nl = run_kernel("altgrep", run)
    return lines_from_hits(text, hit_bits, nl)
