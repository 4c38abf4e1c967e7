"""The aggregation map: delimited records in, (key, decimal sum) out.

``SELECT key, SUM(value) ... GROUP BY key`` over newline-terminated rows
of delimited fields (Pavlo et al., SIGMOD'09, the Aggregation Task over
``UserVisits``: ``sourceIP|destURL|visitDate|adRevenue|...``).  The map a
``WordcountStep`` runs in place of the tokenizer when it is given a
:class:`FieldSum`: per row, the key field (or its first ``prefix`` bytes)
packed into the word-count key lanes, and the value field, a decimal
``[0-9]{1,3}(\\.[0-9]{1,6})?``, read as an integer count of 10^-6 units
(``12.5`` is 12,500,000, below 2^30).  Rows are then grouped exactly as
tokens are (``lex_sort`` + ``group_sorted``), the values where the ones
were, and a key's total leaves the step 64 bits wide as two ``uint32``
lanes: 8,100 rows of 10^9 units pass 2^32.

How a row is read, in three named scopes a device trace tells apart,
each a function of its own that the join's two maps (``ops/joink.py``)
call as well (:func:`find_fields`, :func:`key_lanes`,
:func:`decimal_units`; a key of up to 16 bytes is :class:`FieldSum`'s
limit, not the finder's: ``key_lanes`` packs as many lanes as it is asked
for):

* ``fields``: a row starts behind a newline (or at byte 0) and ends at
  the next one, or where the chunk's content ends (a last row without a
  newline; the zero tail behind it holds no row).  One reverse running
  minimum gives every position its next terminator and its kind
  (delimiter or row end); the row starts are compacted by
  ``_move_left``'s shifted selects over the positions, and a row's
  fields are then found by walking from terminator to terminator with
  one small gather a field, over the rows and not over the chunk.
* ``key_lanes``: every position's next four bytes as one big-endian
  word; a key's lanes are four gathers of it, masked by the key's length.
* ``decimal``: the ten bytes behind the value field's start (three
  gathers of the same words), the dot looked for at bytes 1-3, every
  digit weighed by a constant that follows the dot's place alone: a
  fixed window, no scan.

No scatter and no 64-bit operation anywhere in the map (Design 15): the
sums are ``running_sum_pair``'s three 32-bit scans.

A row with fewer fields than the value's, a key of 0 or over
``max_word_len`` bytes or with a byte outside printable ASCII, or a value
outside the grammar is a bad row: the step reports which came first, and
the engine fails the job (:class:`BadRow`); no row is skipped and none is
parsed on the host.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsi_tpu.ops.wordcount import (
    _PAD_KEY,
    _byte_mask,
    _move_left,
    _shift_left,
    fnv1a32_packed,
    group_sorted,
    lex_sort,
)

#: Digits behind the decimal point of a value, and of a rendered sum.
DECIMALS = 6
#: Most bytes of a value: three digits, the dot, six digits.
_VALUE_BYTES = 3 + 1 + DECIMALS


def file_rows(path: str) -> int:
    """Rows of a file of newline-terminated rows: its newlines, and a last
    row without one."""
    data = np.fromfile(path, np.uint8)
    return int(np.count_nonzero(data == 10)) + int(
        len(data) > 0 and data[-1] != 10)


def row_place(paths: Sequence[str], row: int) -> Optional[str]:
    """``<file>:<line>`` (lines from 1) of row ``row`` of the stream the
    files make, one behind the other (:func:`file_rows` a file); None for
    an ordinal the files do not hold."""
    for path in paths:
        rows = file_rows(path)
        if 0 <= row < rows:
            return f"{path}:{row + 1}"
        row -= rows
    return None


class BadRow(ValueError):
    """A row a map over delimited rows cannot read: the job fails and
    commits nothing.  ``row`` is the row's ordinal in the job's input
    (from 0), until :meth:`at` names its file and line."""

    def __init__(self, message: str, row: int = -1):
        super().__init__(message)
        self.row = row

    def at(self, paths: Sequence[str]) -> "BadRow":
        """The same failure with the row's file and line (from 1) in the
        message (:func:`row_place`)."""
        place = row_place(paths, self.row)
        return self if place is None else BadRow(f"{place}: {self}",
                                                 self.row)


class FieldSum(NamedTuple):
    """The aggregation map as a static parameter of the word-count step:
    group by field ``key_field`` (its first ``prefix`` bytes where
    ``prefix`` is not 0), sum field ``value_field``, fields separated by
    the byte ``delim``."""

    prefix: int = 0
    key_field: int = 0
    value_field: int = 3
    delim: int = 0x7C

    #: ``uint32`` lanes of a step's total (a count's is one).
    value_lanes = 2
    #: Digits behind the point of a committed sum.
    decimals = DECIMALS
    #: Row-buffer rungs, as the tokenizer's (4, 2): rows of 64 bytes and
    #: over fit the first (UserVisits' are 120-140); no chunk of rows that
    #: can be read overflows the second (the shortest is ``k|||0\n``).
    fracs = (64, 4)

    def group_core(self, chunk: jax.Array, *, max_word_len: int, u_cap: int,
                   t_cap_frac: int):
        return fieldsum_group_core(chunk, spec=self,
                                   max_word_len=max_word_len, u_cap=u_cap,
                                   t_cap_frac=t_cap_frac)

    def bad_row(self, scal: np.ndarray, rows_before: int) -> BadRow:
        """The failure a step's scalar block reports: the devices' rows
        follow one another in the stream, so the first bad row's ordinal
        is the rows before the step, the rows of the devices before its
        device, and its place in its chunk."""
        d = int(np.flatnonzero(scal[:, 3])[0])
        row = rows_before + int(scal[:d, 5].sum()) + int(scal[d, 6])
        return BadRow(
            f"bad row: fewer than {self.value_field + 1} fields, a key of "
            "0 or over 16 bytes or not printable ASCII, or a value that is "
            "not [0-9]{1,3}(.[0-9]{1,6})?", row)


def _words(chunk: jax.Array) -> jax.Array:
    """Every position's next four bytes as a big-endian ``uint32`` (zeros
    past the chunk's end), and one zero word behind the last position."""
    b = chunk.astype(jnp.uint32)
    w = ((b << 24) | (_shift_left(b, 1) << 16) | (_shift_left(b, 2) << 8)
         | _shift_left(b, 3))
    return jnp.concatenate([w, jnp.zeros((1,), jnp.uint32)])


def _bytes_of(words: List[jax.Array], count: int) -> List[jax.Array]:
    """The first ``count`` bytes of big-endian words, each a ``uint32``."""
    return [(words[p // 4] >> (8 * (3 - p % 4))) & jnp.uint32(0xFF)
            for p in range(count)]


def _row_starts(is_start: jax.Array, size: int) -> jax.Array:
    """The first ``size`` set positions of ``is_start`` in ascending
    order, then 0: ``compact_positions``' values, by ``_move_left``'s
    shifted selects over the positions themselves (one array to move) in
    place of its sort of them: 0.7 ms less of a 5.3 ms step over a chunk
    of 2^20 on a TPU v5e (``scripts/agg_micro.py``; PERF.md, PR 49)."""
    m = is_start.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    live = is_start.astype(jnp.int32)
    before = jnp.cumsum(live, dtype=jnp.int32) - live
    (moved,) = _move_left(jnp.where(is_start, pos - before, 0), [pos])
    if size > m:
        moved = jnp.concatenate([moved, jnp.zeros((size - m,), jnp.int32)])
    return jnp.where(jnp.arange(size, dtype=jnp.int32)
                     < jnp.sum(live, dtype=jnp.int32), moved[:size], 0)


def find_fields(chunk: jax.Array, *, delim: int, last_field: int,
                t_cap: int):
    """The rows of a chunk and their fields 0 to ``last_field``, in input
    order (scope ``fields``): ``(starts, ends, valid, fields_ok,
    n_rows)``.  ``starts[f]`` and ``ends[f]``: ``int32[t_cap]``, field
    ``f``'s first byte and its terminator's place; ``valid``: the rows
    that are rows (the first ``n_rows``; of more rows than ``t_cap`` the
    first ``t_cap`` are kept and ``n_rows`` says so); ``fields_ok``: those
    of them with a delimiter, not the row's end, behind every field before
    the last.  What every map over delimited rows shares: the
    aggregation's (:func:`field_rows`) and the join's two
    (``ops/joink.py``)."""
    n = chunk.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    with jax.named_scope("fields"):
        content = jnp.max(jnp.where(chunk != 0, pos, -1)) + 1
        is_end = (chunk == 10) | (pos >= content)
        is_start = (pos < content) & jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), is_end[:-1]])
        n_rows = jnp.sum(is_start, dtype=jnp.int32)
        # Every position's next terminator, and in the low bit whether it
        # ends the row (1) or only a field (0: a delimiter), so that one
        # gather tells both; position n ends whatever is open there (a
        # full chunk's last row).
        is_delim = chunk == jnp.uint8(delim)
        nxt = jnp.concatenate([
            lax.cummin(jnp.where(
                is_end | is_delim, 2 * pos + (~is_delim).astype(jnp.int32),
                jnp.int32(2 * n + 1)), reverse=True),
            jnp.full((1,), 2 * n + 1, jnp.int32)])
        valid = jnp.arange(t_cap, dtype=jnp.int32) < n_rows
        begin = _row_starts(is_start, t_cap)
        fields_ok = valid
        starts, ends = [], []
        for f in range(last_field + 1):
            code = nxt[begin]
            starts.append(begin)
            ends.append(code >> 1)
            if f < last_field:  # a delimiter, not the row's end, behind it
                fields_ok &= (code & 1) == 0
                begin = jnp.minimum((code >> 1) + 1, n)
    return starts, ends, valid, fields_ok, n_rows


def key_lanes(words: jax.Array, at: jax.Array, field_len: jax.Array,
              valid: jax.Array, *, k: int, prefix: int = 0):
    """A key field packed into ``k`` lanes (scope ``key_lanes``):
    ``(key_cols, key_lens, key_ok)``.  ``words`` are :func:`_words` of
    the chunk, ``at`` the field's first byte and ``field_len`` its bytes,
    a row each.  ``key_cols``: ``k`` columns ``uint32``, the key's (its
    first ``prefix`` bytes' where that is not 0) bytes big-endian, zero
    past its length, ``_PAD_KEY`` where ``valid`` is not set;
    ``key_lens``: ``int32``, 0 in those rows; ``key_ok``: a valid row
    whose field is 1 to ``4 k`` bytes of printable ASCII."""
    n = words.shape[0] - 1
    with jax.named_scope("key_lanes"):
        lanes = [words[jnp.minimum(at + 4 * j, n)] for j in range(k)]
        printable = valid
        for p, b in enumerate(_bytes_of(lanes, 4 * k)):
            printable &= (p >= field_len) | ((b >= 0x20) & (b <= 0x7E))
        key_ok = (field_len >= 1) & (field_len <= 4 * k) & printable
        key_lens = jnp.where(
            valid, jnp.minimum(field_len, prefix) if prefix
            else field_len, 0)
        key_cols = tuple(
            jnp.where(valid,
                      lanes[j] & _byte_mask(jnp.clip(key_lens - 4 * j, 0, 4)),
                      jnp.uint32(_PAD_KEY))
            for j in range(k))
    return key_cols, key_lens, key_ok


def decimal_units(words: jax.Array, at: jax.Array, length: jax.Array,
                  valid: jax.Array):
    """A decimal field ``[0-9]{1,3}(\\.[0-9]{1,6})?`` as 10^-6 units (scope
    ``decimal``): ``(values, value_ok)``, ``uint32`` (0 where ``valid``
    is not set) and whether the field is in the grammar.  ``at`` is the
    field's first byte, ``length`` its bytes, a row each."""
    n = words.shape[0] - 1
    t_cap = at.shape[0]
    with jax.named_scope("decimal"):
        window = _bytes_of(
            [words[jnp.minimum(at + 4 * j, n)] for j in range(3)],
            _VALUE_BYTES)
        digits = [b - jnp.uint32(0x30) for b in window]
        # the dot, if any, stands at byte 1, 2 or 3; without one the
        # value is its integer digits, and the dot's place is its length
        dot = length
        for p in (3, 2, 1):
            dot = jnp.where((window[p] == 0x2E) & (p < length), p, dot)
        value = jnp.zeros((t_cap,), jnp.uint32)
        all_digits = valid
        for d in (1, 2, 3):
            total = jnp.zeros((t_cap,), jnp.uint32)
            for p in range(min(d + 1 + DECIMALS, _VALUE_BYTES)):
                if p == d:
                    continue
                weight = 10 ** (DECIMALS + d - 1 - p if p < d
                                else DECIMALS - (p - d))
                total += jnp.where(p < length, digits[p] * jnp.uint32(weight),
                                   jnp.uint32(0))
            value = jnp.where(dot == d, total, value)
        for p in range(_VALUE_BYTES):
            all_digits &= (p >= length) | (p == dot) | (digits[p] <= 9)
        fraction = length - dot - 1  # -1 without a dot
        value_ok = (all_digits & (dot >= 1) & (dot <= 3)
                    & (fraction != 0) & (fraction <= DECIMALS))
        values = jnp.where(valid, value, jnp.uint32(0))
    return values, value_ok


def first_bad_row(valid: jax.Array, ok: jax.Array) -> jax.Array:
    """The place of the first valid row that is not ``ok`` (scope
    ``fields``); the rows' capacity without one."""
    t_cap = valid.shape[0]
    with jax.named_scope("fields"):
        bad = valid & ~ok
        return jnp.min(jnp.where(
            bad, jnp.arange(t_cap, dtype=jnp.int32), jnp.int32(t_cap)))


def field_rows(chunk: jax.Array, *, spec: FieldSum, max_word_len: int,
               t_cap_frac: int):
    """The rows of a chunk, in input order: ``(key_cols, key_lens, values,
    n_rows, first_bad)``.

    ``key_cols``: ``max_word_len / 4`` columns ``uint32[t_cap]``, the key's
    (prefix's) bytes big-endian, zero past its length, ``_PAD_KEY`` in the
    rows past the last (``t_cap = n // t_cap_frac + 1``; of more rows than
    that the first ``t_cap`` are kept and ``n_rows`` says so);
    ``key_lens``: ``int32[t_cap]``, 0 in those rows; ``values``:
    ``uint32[t_cap]``, 10^-6 units, 0 in those rows; ``first_bad``: the
    place of the first row that cannot be read (``t_cap`` without one)."""
    t_cap = chunk.shape[0] // t_cap_frac + 1
    starts, ends, valid, fields_ok, n_rows = find_fields(
        chunk, delim=spec.delim,
        last_field=max(spec.key_field, spec.value_field), t_cap=t_cap)
    words = _words(chunk)
    at = starts[spec.key_field]
    key_cols, key_lens, key_ok = key_lanes(
        words, at, ends[spec.key_field] - at, valid, k=max_word_len // 4,
        prefix=spec.prefix)
    at = starts[spec.value_field]
    values, value_ok = decimal_units(words, at, ends[spec.value_field] - at,
                                     valid)
    first_bad = first_bad_row(valid, fields_ok & key_ok & value_ok)
    return key_cols, key_lens, values, n_rows, first_bad


def fieldsum_group_core(chunk: jax.Array, *, spec: FieldSum,
                        max_word_len: int = 16, u_cap: int = 1 << 12,
                        t_cap_frac: int = 64):
    """Exact per-key sums over one uint8 chunk of whole rows (zero-padded
    tail): ``ops/wordcount.tokenize_group_core``'s results with the rows'
    values where the ones were.

    Returns (packed_u [u_cap, K] uint32, len_u [u_cap] i32, sum_u [u_cap,
    2] uint32 (low, high), fnv_u [u_cap] u32, n_unique i32, max_len i32,
    has_bad bool, row_overflow bool, n_rows i32, first_bad i32): where the
    tokenizer reports a byte it cannot count (``has_high``) this map
    reports a row it cannot read, and where it reports more tokens than
    the buffer holds, more rows; the callers' ladders run the chunk again
    at the next of ``FieldSum.fracs``."""
    t_cap = chunk.shape[0] // t_cap_frac + 1
    key_cols, key_lens, values, n_rows, first_bad = field_rows(
        chunk, spec=spec, max_word_len=max_word_len, t_cap_frac=t_cap_frac)
    *scols, slens, svals = lex_sort(key_cols, (key_lens, values))
    skeys, totals, upos, ovalid, n_unique = group_sorted(
        tuple(scols), (svals, None), u_cap)
    with jax.named_scope("group"):
        packed_u = jnp.where(ovalid[:, None], skeys[upos], jnp.uint32(0))
        len_u = jnp.where(ovalid, slens[upos], jnp.int32(0))
    fnv_u = fnv1a32_packed(packed_u, len_u, max_word_len)
    return (packed_u, len_u, totals, fnv_u, n_unique,
            jnp.max(key_lens, initial=0), first_bad < t_cap,
            n_rows > t_cap, n_rows, first_bad)
