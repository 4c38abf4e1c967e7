"""The join chain's device programs: a keyed table built on the device,
and rows of a second table matched with it, filtered and grouped.

Pavlo et al., SIGMOD'09, the Join Task: ``Rankings`` rows
``pageURL|pageRank|...`` are the build side, ``UserVisits`` rows
``sourceIP|destURL|visitDate|adRevenue|...`` the probe side; a visit
inside the date window whose ``destURL`` is a ``pageURL`` contributes its
``adRevenue``, that page's ``pageRank`` and a one to its ``sourceIP``'s
three sums.  The rows and fields of both are found by
``ops/fieldsum.find_fields``, as the aggregation's are.  Three programs,
each under a module name a device trace can tell apart:

* ``join_build_step`` reads one chunk of whole build rows: the key,
  1-100 bytes of printable ASCII, packed into 25 big-endian ``uint32``
  lanes (``ops/fieldsum.key_lanes``, scope ``key_lanes``), the rank,
  ``[0-9]{1,9}``, read as an integer (scope ``integer``), and appends the
  rows (25 lanes, length, rank, the step's ordinal, the row's place in
  its chunk) to a table that stays on the device for the whole job
  (scope ``append``: one ``dynamic_update_slice`` into the donated
  table at the fill the program itself carries).
* ``join_build_order`` puts the table in the order of a 64-bit hash of
  the key lanes (two ``uint32`` halves, :func:`key_hash`, two single-key
  passes of ``ops/wordcount.lex_sort`` and one gather of the rows) and
  looks at every pair of neighbours (scope ``unique``): two rows of one
  hash and one key are a duplicate key, which fails the job; two rows of
  one hash and two keys are a collision, and the caller orders again
  under the next salt.  So in the table the probe searches no two rows
  share a hash, and a key is in it exactly if the one row its hash finds
  holds its every lane.
* ``join_probe_step`` reads one chunk of whole probe rows: fields 0 to 3
  found, both keys' lengths and bytes checked (one running count of the
  bytes outside printable ASCII, read at the fields' ends), the date
  checked against its grammar and compared, as ten bytes, with the
  window's two ends (scope ``window``; the ends are an argument, not a
  constant: one program whatever the window), the decimal read
  (``ops/fieldsum.decimal_units``); the rows inside the window moved to
  the front by ``_move_left`` and cut to the window's buffer; their two
  keys packed there and only there; the join key's hash searched in the
  ordered table (scope ``lookup``: a binary search over the hashes, one
  gather of two words a round, then one gather of the found rows and a
  comparison of all 25 lanes); and the matched rows grouped by
  ``sourceIP`` (``lex_sort`` + ``group_sorted`` with three sums a key,
  each a (low, high) pair of ``uint32``: revenue in 10^-6 units, rank,
  rows).  What leaves the step is the word-count step's layout (keys,
  lengths, sums, partitions and a block of scalars), for the same pack,
  pull and merge.

No scatter and no 64-bit operation anywhere.  A row of either table that
cannot be read (too few fields, a key of 0 or over its width or with a
byte outside printable ASCII, a rank, date or value outside its grammar)
is a bad row: the step reports which came first, and the engine
(``parallel/joinstream.py``) fails the job.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dsi_tpu.ops.fieldsum import (_bytes_of, _words, decimal_units,
                                  find_fields, first_bad_row, key_lanes)
from dsi_tpu.ops.wordcount import (_PAD_KEY, _move_left, fnv1a32_packed,
                                   group_sorted, lex_sort)

#: Most bytes of a join key (``pageURL VARCHAR(100)``) and its lanes.
KEY_BYTES = 100
KEY_LANES = KEY_BYTES // 4
#: Most bytes of a group key (``sourceIP VARCHAR(16)``) and its lanes.
GROUP_BYTES = 16
GROUP_LANES = GROUP_BYTES // 4
#: Most digits of a rank: below 2^30.
RANK_DIGITS = 9
#: Bytes of a date, ``YYYY-MM-DD``.
DATE_BYTES = 10
#: The byte between the fields of both tables.
DELIM = 0x7C
#: A table row: the key's lanes, its length, the rank, and where the row
#: came from (the build step's ordinal and the row's place in its chunk).
COL_LEN, COL_RANK, COL_STEP, COL_ROW = (KEY_LANES + i for i in range(4))
TABLE_COLS = KEY_LANES + 4
#: ``uint32`` lanes of a step's sums: revenue, rank and rows, each (low,
#: high).
VALUE_LANES = 6
#: Row-buffer rungs, as ``FieldSum.fracs``: build rows of 32 bytes and
#: over fit the first (a ``Rankings`` row is 24 to 68); probe rows of 64
#: and over (``UserVisits``' are 87 to 159); no chunk of rows that can be
#: read overflows the second.  And the window's buffer as a share of the
#: rows': a sixteenth where the window is narrow, all of them otherwise.
BUILD_FRACS = (32, 4)
PROBE_FRACS = (64, 4)
WINDOW_FRACS = (16, 1)


def key_hash(cols, salt: jax.Array) -> tuple:
    """Two ``uint32`` hashes of every row's key lanes under ``salt``: what
    orders the table and finds a row in it, never what decides a match."""
    with jax.named_scope("hash"):
        salt = salt.astype(jnp.uint32)
        h1 = jnp.full(cols[0].shape, 0x9E3779B9, jnp.uint32) ^ salt
        h2 = (jnp.full(cols[0].shape, 0x85EBCA6B, jnp.uint32)
              + salt * jnp.uint32(0xC2B2AE35))
        for c in cols:
            h1 = (h1 ^ c) * jnp.uint32(0x01000193)
            h1 = (h1 << 13) | (h1 >> 19)
            h2 = (h2 + c) * jnp.uint32(0xCC9E2D51)
            h2 = h2 ^ (h2 >> 15)
        out = []
        for h in (h1, h2):  # murmur3's finalizer
            h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
            h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
            out.append(h ^ (h >> 16))
        return tuple(out)


def integer_units(words: jax.Array, at: jax.Array, length: jax.Array,
                  valid: jax.Array):
    """An integer field ``[0-9]{1,9}`` (scope ``integer``): ``(values,
    value_ok)``, as ``ops/fieldsum.decimal_units`` gives a decimal's."""
    n = words.shape[0] - 1
    with jax.named_scope("integer"):
        window = _bytes_of(
            [words[jnp.minimum(at + 4 * j, n)] for j in range(3)],
            RANK_DIGITS)
        value = jnp.zeros(at.shape, jnp.uint32)
        ok = valid & (length >= 1) & (length <= RANK_DIGITS)
        for p, b in enumerate(window):
            digit = b - jnp.uint32(0x30)
            inside = p < length
            value = jnp.where(inside, value * jnp.uint32(10) + digit, value)
            ok &= ~inside | (digit <= 9)
        return jnp.where(valid, value, jnp.uint32(0)), ok


def build_rows(chunk: jax.Array, *, t_cap: int):
    """The build rows of a chunk, in input order: ``(key_cols, key_lens,
    ranks, n_rows, first_bad)``, as ``ops/fieldsum.field_rows`` gives the
    aggregation's: the key is field 0, 25 lanes, the rank field 1."""
    starts, ends, valid, fields_ok, n_rows = find_fields(
        chunk, delim=DELIM, last_field=1, t_cap=t_cap)
    words = _words(chunk)
    key_cols, key_lens, key_ok = key_lanes(
        words, starts[0], ends[0] - starts[0], valid, k=KEY_LANES)
    ranks, rank_ok = integer_units(words, starts[1], ends[1] - starts[1],
                                   valid)
    return (key_cols, key_lens, ranks, n_rows,
            first_bad_row(valid, fields_ok & key_ok & rank_ok))


@functools.lru_cache(maxsize=None)
def build_fn(t_cap_frac: int):
    """``join_build_step(table, state, chunk)``: the donated table comes
    back with the chunk's rows at row ``state[0]``, ``state`` (``int32[2]``:
    the table's fill and the step's ordinal, which never leave the device)
    moved on, beside ``int32[4]``: the chunk's rows, the first unreadable
    one's place (the buffer's rows without one), whether the chunk holds
    more rows than the buffer, and the fill."""

    def join_build_step(table, state, chunk):
        chunk = chunk.reshape(-1)
        t_cap = chunk.shape[0] // t_cap_frac + 1
        key_cols, key_lens, ranks, n_rows, first_bad = build_rows(
            chunk, t_cap=t_cap)
        fill, step = state[0], state[1]
        with jax.named_scope("append"):
            rows = jnp.stack(
                [*key_cols, key_lens.astype(jnp.uint32), ranks,
                 jnp.broadcast_to(step.astype(jnp.uint32), (t_cap,)),
                 jnp.arange(t_cap, dtype=jnp.uint32)], axis=1)
            table = lax.dynamic_update_slice(table, rows, (fill, 0))
        fill = fill + jnp.minimum(n_rows, t_cap)
        return table, jnp.stack([fill, step + 1]), jnp.stack(
            [n_rows, first_bad, (n_rows > t_cap).astype(jnp.int32), fill])

    return jax.jit(join_build_step, donate_argnums=(0, 1))


def _rows_less(h: jax.Array, q1: jax.Array, q2: jax.Array) -> jax.Array:
    """Rows of ``h`` (``[n, 2]``) that sort before the hash (q1, q2)."""
    return (h[:, 0] < q1) | ((h[:, 0] == q1) & (h[:, 1] < q2))


@jax.jit
def join_build_order(table, state, salt):
    """The table in the order of its keys' hashes under ``salt`` (module
    docstring): ``(ordered, hashes, int32[6])``, the rows, their hashes
    (``uint32[capacity, 2]``; all-ones behind the last row) and the count
    of neighbours that hold one key, the count of those that share a hash
    and not the key, and for the first pair of one key the step and place
    of both rows."""
    capacity = table.shape[0]
    index = jnp.arange(capacity, dtype=jnp.int32)
    valid = index < state[0]
    h1, h2 = key_hash([table[:, j] for j in range(KEY_LANES)], salt)
    s1, s2, perm = lex_sort(
        (jnp.where(valid, h1, jnp.uint32(_PAD_KEY)),
         jnp.where(valid, h2, jnp.uint32(_PAD_KEY))), (index,))
    with jax.named_scope("gather"):
        ordered = jnp.take(table, perm, axis=0)
    with jax.named_scope("unique"):
        held = perm < state[0]
        same_hash = (held[1:] & held[:-1] & (s1[1:] == s1[:-1])
                     & (s2[1:] == s2[:-1]))
        same_key = jnp.all(
            ordered[1:, :KEY_LANES] == ordered[:-1, :KEY_LANES], axis=1)
        twice = same_hash & same_key
        at = jnp.min(jnp.where(twice, index[:-1], capacity - 2))
        pair = lax.dynamic_slice(ordered, (at, COL_STEP), (2, 2))
        scal = jnp.concatenate([
            jnp.stack([jnp.sum(twice, dtype=jnp.int32),
                       jnp.sum(same_hash & ~same_key, dtype=jnp.int32)]),
            pair.reshape(-1).astype(jnp.int32)])
    return ordered, jnp.stack([s1, s2], axis=1), scal


def _in_window(date: tuple, window: jax.Array) -> jax.Array:
    """Dates (three big-endian lanes of their ten bytes) between the
    window's two ends (``uint32[6]``: the first's lanes, the last's),
    both inclusive: byte order is date order."""
    def at_least(a, b):
        return (a[0] > b[0]) | ((a[0] == b[0]) & (
            (a[1] > b[1]) | ((a[1] == b[1]) & (a[2] >= b[2]))))

    return at_least(date, window[:3]) & at_least(window[3:], date)


def probe_rows(chunk: jax.Array, window: jax.Array, *, t_cap: int,
               w_cap: int):
    """The probe rows of a chunk that lie inside the window, at the front
    of a buffer of ``w_cap`` rows in input order: ``(group_cols,
    group_lens, key_cols, values, n_rows, first_bad, n_window)``.
    ``first_bad`` is over every row of the chunk, inside the window or
    not."""
    n = chunk.shape[0]
    starts, ends, valid, fields_ok, n_rows = find_fields(
        chunk, delim=DELIM, last_field=3, t_cap=t_cap)
    words = _words(chunk)
    with jax.named_scope("fields"):
        # Both keys' bytes at once: a row's two keys and the delimiter
        # between them hold no byte outside printable ASCII exactly if
        # the running count of such bytes stands at field 1's end where
        # it stood at field 0's start.
        odd = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
            ((chunk < 0x20) | (chunk > 0x7E)).astype(jnp.int32),
            dtype=jnp.int32)])
        group_len = ends[0] - starts[0]
        key_len = ends[1] - starts[1]
        keys_ok = ((group_len >= 1) & (group_len <= GROUP_BYTES)
                   & (key_len >= 1) & (key_len <= KEY_BYTES)
                   & (odd[jnp.minimum(ends[1], n)] == odd[starts[0]]))
    with jax.named_scope("window"):
        lanes = [words[jnp.minimum(starts[2] + 4 * j, n)] for j in range(3)]
        date_ok = (ends[2] - starts[2]) == DATE_BYTES
        for p, b in enumerate(_bytes_of(lanes, DATE_BYTES)):
            date_ok &= (b == 0x2D) if p in (4, 7) else (
                (b >= 0x30) & (b <= 0x39))
        inside = valid & _in_window(
            (lanes[0], lanes[1], lanes[2] & jnp.uint32(0xFFFF0000)), window)
    values, value_ok = decimal_units(words, starts[3], ends[3] - starts[3],
                                     valid)
    first_bad = first_bad_row(valid,
                              fields_ok & keys_ok & date_ok & value_ok)
    with jax.named_scope("window"):
        live = inside.astype(jnp.int32)
        n_window = jnp.sum(live, dtype=jnp.int32)
        before = jnp.cumsum(live, dtype=jnp.int32) - live
        moved = _move_left(
            jnp.where(inside, jnp.arange(t_cap, dtype=jnp.int32) - before, 0),
            [starts[0], group_len, starts[1], key_len, values])
        group_at, group_len, key_at, key_len, values = (
            x[:w_cap] for x in moved)
        passed = jnp.arange(w_cap, dtype=jnp.int32) < n_window
        values = jnp.where(passed, values, jnp.uint32(0))
    group_cols, group_lens, _ = key_lanes(words, group_at, group_len, passed,
                                          k=GROUP_LANES)
    key_cols, _, _ = key_lanes(words, key_at, key_len, passed, k=KEY_LANES)
    return (group_cols, group_lens, key_cols, values, n_rows, first_bad,
            n_window)


def lookup(table: jax.Array, hashes: jax.Array, n_table: jax.Array,
           key_cols: tuple, salt: jax.Array):
    """Where the keys are in the ordered table: ``(found, rows)``, whether
    a key is one of the table's and the table row its hash finds
    (``uint32[len, TABLE_COLS]``; another key's, or none's, where
    ``found`` is not set).  Equality is decided on every lane of the
    key."""
    capacity = table.shape[0]
    with jax.named_scope("lookup"):
        q1, q2 = key_hash(key_cols, salt)

        def halve(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) >> 1
            less = (lo < hi) & _rows_less(
                hashes[jnp.minimum(mid, capacity - 1)], q1, q2)
            return (jnp.where(less, mid + 1, lo),
                    jnp.where(less | (lo >= hi), hi, mid))

        lo, _ = lax.fori_loop(
            0, capacity.bit_length(), halve,
            (jnp.zeros(q1.shape, jnp.int32),
             jnp.broadcast_to(n_table, q1.shape)))
        rows = table[jnp.minimum(lo, capacity - 1)]
        found = (lo < n_table) & jnp.all(
            rows[:, :KEY_LANES] == jnp.stack(key_cols, axis=1), axis=1)
    return found, rows


@functools.lru_cache(maxsize=None)
def probe_fn(t_cap_frac: int, window_frac: int):
    """``join_probe_step(table, hashes, meta, window, chunk)`` over the
    ordered table (``meta``: ``int32[3]``, its rows, the salt it was
    ordered under and the job's reduce partitions; ``window``:
    ``uint32[6]``): the word-count step's five
    results for one device, keys ``[1, w_cap, 4]``, lengths, sums
    ``[1, w_cap, 6]``, partitions, and ``int32[1, 7]``: the groups, the
    chunk's rows, the first unreadable one's place, the rows inside the
    window, the rows matched, whether the chunk holds more rows than the
    buffer, and more rows inside the window than its."""

    def join_probe_step(table, hashes, meta, window, chunk):
        chunk = chunk.reshape(-1)
        t_cap = chunk.shape[0] // t_cap_frac + 1
        w_cap = min(t_cap, t_cap // window_frac + 1)
        (group_cols, group_lens, key_cols, values, n_rows, first_bad,
         n_window) = probe_rows(chunk, window, t_cap=t_cap, w_cap=w_cap)
        found, rows = lookup(table, hashes, meta[0], key_cols, meta[1])
        *scols, slens, srevenue, srank, sone = lex_sort(
            tuple(jnp.where(found, c, jnp.uint32(_PAD_KEY))
                  for c in group_cols),
            (group_lens, values, rows[:, COL_RANK],
             jnp.ones((w_cap,), jnp.uint32)))
        skeys, totals, upos, ovalid, n_unique = group_sorted(
            tuple(scols), [(srevenue, None), (srank, None), (sone, None)],
            w_cap)
        with jax.named_scope("group"):
            packed_u = jnp.where(ovalid[:, None], skeys[upos], jnp.uint32(0))
            len_u = jnp.where(ovalid, slens[upos], jnp.int32(0))
        fnv_u = fnv1a32_packed(packed_u, len_u, GROUP_BYTES)
        part = (fnv_u & jnp.uint32(0x7FFFFFFF)) % meta[2].astype(jnp.uint32)
        scal = jnp.stack([
            n_unique, n_rows, first_bad, n_window,
            jnp.sum(found, dtype=jnp.int32),
            (n_rows > t_cap).astype(jnp.int32),
            (n_window > w_cap).astype(jnp.int32)])
        return (packed_u[None], len_u[None], totals[None], part[None],
                scal[None])

    return jax.jit(join_probe_step)
