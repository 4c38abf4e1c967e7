"""TPU grep kernel for character-class regex patterns.

``ops/grepk.py`` accelerates plain literals; this module widens the device
scope to the next regex tier: patterns that are a
fixed-length **sequence of byte classes** — literal characters, ``.``,
``[...]`` / ``[^...]`` classes with ranges, ``\\d``/``\\w``/``\\s``, escaped
literals — optionally anchored with a leading ``^`` or trailing ``$``
(the reference's own harness pattern ``[Tt]he``, ``test-mr.sh:47``, lands
exactly here).  Variable-length operators (``* + ? {} |``) and groups
still fall back to the host app; correctness never depends on the kernel
(``backends/tpu.py`` contract, same as every kernel in this package).

TPU-first shape: each pattern position compiles to a handful of
``lo <= byte <= hi`` range tests over the shifted chunk — static unroll,
vector compares only, no gathers — then the same two-scan line
machinery as the literal kernel (``grepk.line_flags_from_match``:
matched line ends as packed bits).  The pattern is STATIC (baked into
the compiled program and its cache key): a grep job runs one pattern
over many splits, so one compile serves the whole job.

Cross-line discipline: every class excludes ``\\n`` (byte 10) and ``\\0``
(padding), so a match window can never span lines or leak into padding —
the per-line ``re.search`` host semantics (``apps/grep.py:34``) are
preserved exactly; inputs containing NUL bytes route to the host.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dsi_tpu.ops.grepk import (
    ascii_text,
    line_flags_from_match,
    lines_from_hits,
    pad_chunk,
    run_kernel,
    upload_chunk,
)
from dsi_tpu.ops.wordcount import _shift_left

# Ranges per pattern position beyond which the unrolled compare chain
# stops being a win (a pathological negated class alternates up to ~128
# ranges); and an overall pattern-length cap for the shift unroll.
_MAX_RANGES = 8
_MAX_PATTERN = 32

_ESCAPE_CLASSES = {
    "d": [(0x30, 0x39)],
    "w": [(0x30, 0x39), (0x41, 0x5A), (0x5F, 0x5F), (0x61, 0x7A)],
    # Python re's \s on str matches [ \t\n\v\f\r\x1c-\x1f] within ASCII;
    # \n is excluded here because lines are newline-split before matching.
    "s": [(0x09, 0x09), (0x0B, 0x0D), (0x1C, 0x1F), (0x20, 0x20)],
}


def _find_class_end(pat: str, start: int) -> int:
    """Index of the closing ']' of a class opened at ``start`` ('['),
    honoring backslash escapes (``[a\\]b]`` closes at the FINAL bracket);
    -1 when unterminated.  A ']' directly after '[' or '[^' is literal in
    re, which the caller's empty-body check rejects to the host path."""
    i = start + 1
    if pat[i:i + 1] == "^":
        i += 1
    while i < len(pat):
        if pat[i] == "\\":
            i += 2
        elif pat[i] == "]":
            return i
        else:
            i += 1
    return -1


def _compress(members: set) -> List[Tuple[int, int]]:
    """Sorted byte set -> minimal (lo, hi) range list."""
    out: List[Tuple[int, int]] = []
    for b in sorted(members):
        if out and b == out[-1][1] + 1:
            out[-1] = (out[-1][0], b)
        else:
            out.append((b, b))
    return out


#: Characters that cannot START an atom in any device tier: modifiers,
#: bounded reps, groups, stray anchors.  (Tier 4 consumes ``* + ?`` as
#: modifiers AFTER a valid atom and splits ``|`` before parsing, so one
#: set serves every tier — see ops/nfak.py.)
ATOM_REJECT = "*+?{}()|^$"


def atom_members(pat: str, i: int):
    """Parse one atom starting at ``pat[i]`` — ``.``, an escape, a
    ``[...]`` class, or a literal character — into its byte-member set.

    Returns ``(members, next_i)`` or None when the atom needs the host
    regex engine.  The SINGLE definition of atom/class semantics shared
    by the class tier (here) and the NFA tier (``ops/nfak.py``), so the
    tiers can never disagree on what a class means.  Callers reject
    ``ATOM_REJECT`` characters first.  Members are raw — callers
    subtract ``{0, 10}`` per their padding/newline discipline."""
    c = pat[i]
    if c == ".":
        return set(range(1, 256)) - {10}, i + 1
    if c == "\\":
        if i + 1 >= len(pat):
            return None
        e = pat[i + 1]
        if e in _ESCAPE_CLASSES:
            return ({b for lo, hi in _ESCAPE_CLASSES[e]
                     for b in range(lo, hi + 1)}, i + 2)
        if not e.isalnum():  # \. \[ \\ etc: escaped literal
            return {ord(e)}, i + 2
        return None  # \b \A \Z back-refs etc.: host
    if c == "[":
        j = _find_class_end(pat, i)
        if j == -1:
            return None
        body = pat[i + 1:j]
        negate = body.startswith("^")
        if negate:
            body = body[1:]
        members: set = set()
        k = 0
        while k < len(body):
            if body[k] == "\\" and k + 1 < len(body):
                e = body[k + 1]
                if e in _ESCAPE_CLASSES:
                    members |= {b for lo, hi in _ESCAPE_CLASSES[e]
                                for b in range(lo, hi + 1)}
                elif not e.isalnum():
                    members.add(ord(e))
                else:
                    return None
                k += 2
            elif k + 2 < len(body) and body[k + 1] == "-":
                lo, hi = ord(body[k]), ord(body[k + 2])
                if lo > hi:
                    return None
                members |= set(range(lo, hi + 1))
                k += 3
            else:
                members.add(ord(body[k]))
                k += 1
        if not members:
            return None
        if negate:
            members = set(range(1, 256)) - members
        return members, j + 1
    return {ord(c)}, i + 1


def parse_class_pattern(pat: str):
    """Parse the supported regex subset.

    Returns ``(ranges, anchor_start, anchor_end)`` where ``ranges`` is one
    tuple of ``(lo, hi)`` byte pairs per pattern position, or ``None``
    when the pattern needs the host regex engine.  Every position's class
    excludes bytes 0 and 10 (see module docstring).
    """
    if not pat or not all(0x01 <= ord(c) <= 0x7E for c in pat):
        return None
    anchor_start = pat.startswith("^")
    if anchor_start:
        pat = pat[1:]
    anchor_end = pat.endswith("$") and not pat.endswith("\\$")
    if anchor_end:
        pat = pat[:-1]
    if not pat:
        return None

    positions: List[Tuple[Tuple[int, int], ...]] = []
    i = 0
    while i < len(pat):
        if pat[i] in ATOM_REJECT:
            return None  # variable-length / group / stray anchor: host
        parsed = atom_members(pat, i)
        if parsed is None:
            return None
        members, i = parsed
        members -= {0, 10}
        if not members:
            return None  # class can only match padding/newline: host
        ranges = _compress(members)
        if len(ranges) > _MAX_RANGES:
            return None
        positions.append(tuple(ranges))

    if not positions or len(positions) > _MAX_PATTERN:
        return None
    return tuple(positions), anchor_start, anchor_end


@jax.named_scope("match")
def _class_match(chunk: jax.Array, ranges, anchor_start: bool,
                 anchor_end: bool) -> jax.Array:
    """Per-position mask: the class pattern matches starting here."""
    m = len(ranges)
    match = jnp.ones(chunk.shape[0], jnp.bool_)
    for j, rs in enumerate(ranges):
        c = _shift_left(chunk, j)
        pos_ok = jnp.zeros(chunk.shape[0], jnp.bool_)
        for lo, hi in rs:
            if lo == hi:
                pos_ok |= c == jnp.uint8(lo)
            else:
                pos_ok |= (c >= jnp.uint8(lo)) & (c <= jnp.uint8(hi))
        match &= pos_ok
    if anchor_start:
        prev = jnp.concatenate(
            [jnp.full((1,), 10, jnp.uint8), chunk[:-1]])
        match &= prev == jnp.uint8(10)
    if anchor_end:
        nxt = _shift_left(chunk, m)  # byte just past the window
        match &= (nxt == jnp.uint8(10)) | (nxt == jnp.uint8(0))
    return match


def classgrep_kernel(chunk: jax.Array, *, ranges, anchor_start: bool,
                     anchor_end: bool):
    """Match lines of ``chunk`` containing the class pattern.

    Same contract as ``grepk.grep_kernel``: returns (hit_bits uint32
    [n / 32], n_lines i32).
    """
    match = _class_match(chunk, ranges, anchor_start, anchor_end)
    return line_flags_from_match(chunk, match)


@functools.lru_cache(maxsize=64)
def _classgrep_compiled(n: int, ranges, anchor_start: bool,
                        anchor_end: bool):
    from dsi_tpu.backends.aotcache import cached_compile

    return cached_compile(
        "classgrep_kernel", classgrep_kernel,
        (jax.ShapeDtypeStruct((n,), np.uint8),),
        static={"ranges": ranges, "anchor_start": anchor_start,
                "anchor_end": anchor_end})


def classgrep_host_result(data: bytes, pattern: str) -> Optional[List[str]]:
    """Matching lines of ``data`` (split on '\\n', in order), or None when
    the pattern or data needs the host regex path."""
    parsed = parse_class_pattern(pattern)
    if parsed is None:
        return None
    ranges, anchor_start, anchor_end = parsed
    text = ascii_text(data, nul_ok=False)
    if text is None:
        return None
    chunk = upload_chunk(pad_chunk(data))
    hit_bits, nl = run_kernel(
        "classgrep_kernel", lambda: _classgrep_compiled(
            int(chunk.shape[0]), ranges, anchor_start, anchor_end)(chunk))
    return lines_from_hits(text, hit_bits, nl)
