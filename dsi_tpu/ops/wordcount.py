"""TPU word-count kernel: tokenize + group + count, one fused XLA program.

This is the device replacement for the reference's map-side hot path
(``mrapps/wc.go:21-34`` tokenization, ``mr/worker.go:74-78`` bucketing) and
the reduce-side sort/group/count (``mr/worker.go:123-146``), re-designed for
the TPU execution model rather than translated:

* the whole file chunk lives in HBM as one ``uint8`` vector; every step is a
  vectorized op over it (no scalar loops, no dynamic shapes),
* tokens are *maximal runs of ASCII letters* — on ASCII text this is exactly
  Go's ``strings.FieldsFunc(contents, !unicode.IsLetter)`` (``wc.go:23``);
  any byte >= 0x80 is detected and reported so the caller can fall back to
  the host path, keeping Unicode parity without polluting the kernel,
* grouping is by **exact word bytes**, not by hash: each token's first
  ``max_word_len`` bytes are packed big-endian into ``max_word_len/4``
  ``uint32`` lanes and grouped with a multi-key lexicographic ``lax.sort`` +
  per-run sums (a prefix sum read at the run starts) — no collision risk,
  and the packed keys double as the exact word bytes for host-side
  detokenization (SURVEY.md §7 hard part 1),
* the partition hash is FNV-1a 32-bit, bit-identical to the reference's
  ``ihash`` (``mr/worker.go:33-37``), computed on-device per *unique* word.

TPU-shaped design decisions (what makes this fast, not just correct):

* **no gathers from the chunk**: the packed key lanes are built for every
  position at once from shifted copies of the chunk (pure elementwise
  shifts/ors), a token's length for every position from one reverse
  running minimum over the token ends, and the positions that start a
  token then move to the token buffer in ``log2 n`` steps of shifted
  selects (:func:`token_lanes`, :func:`_move_left`): no sort and no gather
  over the chunk's positions;
* **small sort buffer**: tokens are compacted to ``n // t_cap_frac + 1``
  slots (a token needs ≥ 1 letter + a separator ⇒ ``n//2+1`` is the hard
  bound; real text is ≥ 4 bytes/token, so the default frac=4 buffer is 2×
  smaller and the sort — the kernel's dominant cost — 2× cheaper).  If a
  pathological input overflows the compact buffer the kernel reports it and
  the wrapper retries at the exact ``n//2+1`` bound.

All shapes are static.  Overflow (words longer than ``max_word_len``, more
uniques than ``u_cap``, more tokens than the compact buffer, non-ASCII
bytes) is detected exactly and surfaced as scalars; the host wrapper retries
with a bigger kernel or falls back to the host implementation
(``exactness_retry``), so the result is always exact.
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsi_tpu.obs import span as _span
from dsi_tpu.utils.jaxcompat import enable_x64, x64_scoped

_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193
_PAD_KEY = 0xFFFFFFFF  # sorts after every real word (ASCII first byte < 0x80)
#: The byte between the documents of a packed chunk: ASCII's record
#: separator, a non-letter under 128, so it ends a word as a space does
#: and raises no ``has_high``.  The packer rewrites the byte as a space
#: where a document holds it (``parallel/grepstream.pack_chunk``).
DOC_SEP = 0x1E


def is_ascii_letter(b: jax.Array) -> jax.Array:
    """[A-Za-z] mask over uint8 bytes (== unicode.IsLetter on ASCII)."""
    return ((b >= 65) & (b <= 90)) | ((b >= 97) & (b <= 122))


def _shift_left(x: jax.Array, s: int) -> jax.Array:
    """x shifted left by s positions, zero-filled: out[i] = x[i+s]."""
    if s == 0:
        return x
    if s >= x.shape[0]:
        return jnp.zeros_like(x)
    return jnp.concatenate([x[s:], jnp.zeros((s,), x.dtype)])


def _byte_mask(keep: jax.Array) -> jax.Array:
    """uint32 mask keeping the first ``keep`` (0..4) big-endian bytes."""
    return jnp.where(
        keep >= 4, jnp.uint32(0xFFFFFFFF),
        jnp.where(keep == 3, jnp.uint32(0xFFFFFF00),
                  jnp.where(keep == 2, jnp.uint32(0xFFFF0000),
                            jnp.where(keep == 1, jnp.uint32(0xFF000000),
                                      jnp.uint32(0)))))


@jax.named_scope("hash")
def fnv1a32_packed(packed: jax.Array, lengths: jax.Array,
                   max_word_len: int) -> jax.Array:
    """FNV-1a 32-bit over the packed word bytes — bit-exact Go hash/fnv.New32a
    (mr/worker.go:33-37).  Unrolled over the static max_word_len."""
    h = jnp.full(packed.shape[:1], _FNV_OFFSET, jnp.uint32)
    for j in range(max_word_len):
        b = (packed[:, j // 4] >> ((3 - (j % 4)) * 8)) & jnp.uint32(0xFF)
        h = jnp.where(j < lengths, (h ^ b) * jnp.uint32(_FNV_PRIME), h)
    return h


def pack_key_lanes(cols: tuple) -> tuple:
    """Pack uint32 key lanes pairwise into uint64 keys (lane j is the
    high word, lane j+1 the low), preserving lexicographic order with
    half the sort operands and comparator keys — measured ~2x faster in
    XLA's CPU sort, and never slower on TPU (fewer tuple elements per
    comparator).  A missing odd tail lane is filled with the PAD
    constant: order-neutral for real rows (a constant low word) and it
    keeps pad rows at uint64-max so PAD still sorts last and
    ``group_sorted``'s max-value pad detection holds.

    uint64 exists only under the x64 flag; the scoped ``jax.enable_x64``
    context makes these ops real 64-bit without flipping the global
    default (which would change dtype inference package-wide)."""
    out = []
    with enable_x64(True):
        for j in range(0, len(cols), 2):
            hi = cols[j].astype(jnp.uint64) << 32
            lo = (cols[j + 1] if j + 1 < len(cols)
                  else jnp.full_like(cols[j], _PAD_KEY)).astype(jnp.uint64)
            out.append(hi | lo)
    return tuple(out)


# A pad row packs to all-ones in every uint64 column (see pack_key_lanes).
_PAD_KEY64 = 0xFFFFFFFFFFFFFFFF


def unpack_key_lanes(cols64, k: int) -> tuple:
    """Inverse of :func:`pack_key_lanes`: k uint32 lanes back out of the
    packed uint64 columns."""
    out = []
    with enable_x64(True):
        for j in range(k):
            w = cols64[j // 2]
            out.append(((w >> 32) if j % 2 == 0 else w).astype(jnp.uint32))
    return tuple(out)


@jax.named_scope("sort")
def lex_sort(keys: tuple, payloads: tuple = ()) -> tuple:
    """Stable lexicographic sort of rows by ``keys`` (uint32 columns, most
    significant first), carrying ``payloads`` (columns of any dtype).
    Returns ``(*sorted_keys, *sorted_payloads)``.

    Implemented as an LSD radix over the key columns: one stable
    SINGLE-key ``lax.sort`` per column, least significant first, every
    pass with the same operand signature (the pass's key first, the other
    key columns next, the payloads last).  The reason is the TPU
    compiler, not the run time: XLA:TPU's compile time for a sort grows
    with the comparator, not the data — measured on a v5e at 262,145
    rows, a 2xu64-key (or 4xu32-key) sort with one payload compiles in
    ~100 s, a single-u32-key sort in ~24 s, and four identical
    single-key passes in ~20 s together (the identical passes compile
    once), while every variant runs in 2-3 ms.  The same rows in the
    same order come out either way."""
    cols = list(keys)
    pays = tuple(payloads)
    for j in reversed(range(len(cols))):
        out = lax.sort((cols[j], *cols[:j], *cols[j + 1:], *pays),
                       num_keys=1, is_stable=True)
        cols = [*out[1:j + 1], out[0], *out[j + 1:len(cols)]]
        pays = tuple(out[len(cols):])
    return (*cols, *pays)


def compact_positions(mask: jax.Array, size: int,
                      fill_value: int) -> jax.Array:
    """The first ``size`` set positions of ``mask`` in ascending order,
    then ``fill_value``: the values of ``jnp.nonzero(mask, size=size,
    fill_value=fill_value)[0]``, as ``int32`` whatever the x64 scope says.

    One single-key ``lax.sort`` of ``where(mask, position, m)``: the set
    positions come first, in order, and every other row carries ``m``,
    which no position equals.  No scatter: ``jnp.nonzero(size=)`` ranks
    with a ``scatter-add`` of one update per input position, 64-bit
    under the scoped x64 flag of this package's programs, and that
    emulated scatter cost 66-89 ns a position on a TPU v5e where a sort
    pass costs about 1 (PERF.md, PR 35).  The sums over sorted ids went
    the same way in PR 39: :func:`group_sorted`, and the block starts of
    ``parallel/shuffle.shuffle_rows``."""
    (m,) = mask.shape
    if m >= 1 << 31:
        raise ValueError(f"compact_positions: {m} positions overflow int32")
    key = jnp.where(mask, jnp.arange(m, dtype=jnp.int32), jnp.int32(m))
    (key,) = lax.sort((key,), num_keys=1)
    if size > m:
        key = jnp.concatenate([key, jnp.full((size - m,), m, jnp.int32)])
    key = key[:size]
    return jnp.where(key < m, key, jnp.int32(fill_value))


def _move_left(d: jax.Array, arrays: list) -> list:
    """Every slot of ``arrays`` whose ``d`` is positive, moved ``d`` slots
    to the left; a slot with ``d`` 0 stays, and what a slot that nothing
    reaches holds afterwards is unspecified.  ``d`` is the distance of an
    ORDER-PRESERVING compaction: slot ``i`` minus the number of live slots
    before it, 0 for a slot that is not live.

    ``log2`` steps of one shifted select an array, low bit first: at step
    ``b`` a slot whose ``d`` has bit ``b`` set moves ``2^b``.  Low bits
    first routes a compaction without collisions: for live slots i < j,
    ``j - i > d_j - d_i >= 0``, so after any number of low bits two live
    slots still differ, and a moving slot lands only on one that has moved
    away or was never live.  ``d`` rides along whole; a vacated slot takes
    ``d`` 0.  No sort, no gather, no scatter: a step is an elementwise
    pass over the arrays, where a gather of the live rows cost 7.5 ns a
    row and lane on a TPU v5e (``scripts/pack_micro.py``; PERF.md, PR 46)."""
    for b in range(max(d.shape[0] - 1, 1).bit_length()):
        s = 1 << b
        bit = ((d >> b) & 1) != 0
        came = _shift_left(bit, s)
        arrays = [jnp.where(came, _shift_left(x, s), x) for x in arrays]
        d = jnp.where(came, _shift_left(d, s), jnp.where(bit, 0, d))
    return arrays


def token_lanes(chunk: jax.Array, *, max_word_len: int, t_cap_frac: int,
                doc_sep: Optional[int] = None, with_pos: bool = False):
    """The tokens of a chunk as rows of the token buffer, in the order of
    their first bytes: ``(packed_cols, lengths, n_tokens, doc_lane,
    start_pos)``.

    ``packed_cols``: ``max_word_len / 4`` columns ``uint32[t_cap]``, a
    token's first bytes big-endian, zero past its length, ``_PAD_KEY`` in
    the rows past the last token (``t_cap = n // t_cap_frac + 1``; of more
    tokens than that the first ``t_cap`` are kept and ``n_tokens`` says
    so); ``lengths``: ``int32[t_cap]``, 0 in those rows; ``doc_lane``
    (``doc_sep``): the count of separator bytes before the token,
    ``_PAD_KEY`` in those rows, else ``None``; ``start_pos``
    (``with_pos``): the position of the token's first byte, ``int32``,
    unspecified in those rows, else ``None``.

    A start is a letter behind a non-letter, so two adjacent positions
    hold at most one: the chunk is split once into its even and odd bytes
    and everything else runs over ``n / 2`` slots, a slot a pair of
    positions.  What a token hands over (its lanes as shifted copies of
    the bytes, its length by a reverse running minimum over the token
    ends, the separators before it by a running sum) is computed at every
    slot as if a token began there, and :func:`_move_left` then moves the
    slots that hold a start to the front of the buffer.
    """
    n = chunk.shape[0]
    k = max_word_len // 4
    t_cap = n // t_cap_frac + 1
    if n % 2:
        chunk = jnp.concatenate([chunk, jnp.zeros((1,), chunk.dtype)])
    m = chunk.shape[0] // 2

    with jax.named_scope("tokenize"):
        ev = lax.slice(chunk, (0,), (2 * m,), (2,))
        od = lax.slice(chunk, (1,), (2 * m,), (2,))
        let_ev, let_od = is_ascii_letter(ev), is_ascii_letter(od)
        start_ev = let_ev & ~jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), let_od[:-1]])
        start_od = let_od & ~let_ev
        live = start_ev | start_od
        n_tokens = jnp.sum(live, dtype=jnp.int32)
        slot = jnp.arange(m, dtype=jnp.int32)
        start = 2 * slot + start_od.astype(jnp.int32)
        # A pair holds at most one token end as well, and none before a
        # start of its own, so the first end at or behind a start's slot
        # is its token's.
        end = jnp.where(let_ev & ~let_od, 2 * slot,
                        jnp.where(let_od & ~_shift_left(let_ev, 1),
                                  2 * slot + 1, jnp.int32(2 * m)))
        length = lax.cummin(end, reverse=True) - start + 1

    with jax.named_scope("pack"):
        e, o = ev.astype(jnp.uint32), od.astype(jnp.uint32)
        e1, o1, e2 = _shift_left(e, 1), _shift_left(o, 1), _shift_left(e, 2)
        # The four bytes at an even position and at the odd one behind it;
        # lane j of a token lies 4j bytes on, two slots of its own parity.
        b_ev = (e << 24) | (o << 16) | (e1 << 8) | o1
        b_od = (o << 24) | (e1 << 16) | (o1 << 8) | e2
        moved = [jnp.where(start_od, _shift_left(b_od, 2 * j),
                           _shift_left(b_ev, 2 * j)) for j in range(k)]
        moved.append(length)
        if doc_sep is not None:
            sep_od = (od == jnp.uint8(doc_sep)).astype(jnp.int32)
            sep_ev = (ev == jnp.uint8(doc_sep)).astype(jnp.int32)
            # No letter is a separator; the odd byte of an even start's
            # pair lies behind the start.
            moved.append(jnp.cumsum(sep_ev + sep_od, dtype=jnp.int32)
                         - jnp.where(start_od, 0, sep_od))
        if with_pos:
            moved.append(start)

    with jax.named_scope("compact"):
        live32 = live.astype(jnp.int32)
        before = jnp.cumsum(live32, dtype=jnp.int32) - live32
        moved = _move_left(jnp.where(live, slot - before, 0), moved)
        if m < t_cap:  # t_cap_frac 2: one row more than there are pairs
            moved = [jnp.concatenate([x, jnp.zeros((t_cap - m,), x.dtype)])
                     for x in moved]
        moved = [x[:t_cap] for x in moved]
        valid = jnp.arange(t_cap, dtype=jnp.int32) < n_tokens
        lengths = jnp.where(valid, moved[k], 0)

    with jax.named_scope("pack"):
        packed_cols = tuple(
            jnp.where(valid,
                      moved[j] & _byte_mask(jnp.clip(lengths - 4 * j, 0, 4)),
                      jnp.uint32(_PAD_KEY))
            for j in range(k))
        doc_lane = None if doc_sep is None else jnp.where(
            valid, moved[k + 1].astype(jnp.uint32), jnp.uint32(_PAD_KEY))
    return (packed_cols, lengths, n_tokens, doc_lane,
            moved[-1] if with_pos else None)


def running_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array in its OWN dtype: the
    adds are modular, as a ``segment_sum``'s are, so differences of it
    are exact wherever the difference itself fits.

    32-bit and narrower: ``jnp.cumsum``.  64-bit (the device table's
    counts; call it under the x64 scope that made them): three 32-bit
    scans, since the chip emulates 64-bit integers.  The low halves are
    summed modulo 2^32; each step of that sum wraps at most once, and
    exactly when the sum falls, so a scan of the falls counts the carries
    into the high halves' sum.  On a TPU v5e at 1,310,720 rows this runs
    in 0.80 ms and compiles in 8 s, where ``jnp.cumsum`` of ``uint64``
    runs in 1.44 ms and compiles in 50-96 s and a
    ``lax.associative_scan`` over (lo, hi) pairs takes 3.3 ms
    (``scripts/segsum_micro.py``; PERF.md, PR 39)."""
    if x.dtype.itemsize < 8:
        return jnp.cumsum(x, dtype=x.dtype)
    lo = jnp.cumsum(x.astype(jnp.uint32), dtype=jnp.uint32)
    hi = jnp.cumsum((x >> 32).astype(jnp.uint32), dtype=jnp.uint32)
    fell = lo < jnp.concatenate([jnp.zeros((1,), jnp.uint32), lo[:-1]])
    hi = hi + jnp.cumsum(fell, dtype=jnp.uint32)
    return ((hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)).astype(
        x.dtype)


def running_sum_pair(lo: jax.Array, hi: Optional[jax.Array]) -> tuple:
    """:func:`running_sum`'s 64-bit form over numbers that come as their
    two ``uint32`` halves (``hi`` None: all zero) and leave as them: the
    same three 32-bit scans, with no 64-bit operation at all.  (Kept
    beside it and not under it: the programs that sum ``uint64`` counts
    lower to the text they had.)"""
    lo = jnp.cumsum(lo, dtype=jnp.uint32)
    if hi is not None:
        hi = jnp.cumsum(hi, dtype=jnp.uint32)
    fell = lo < jnp.concatenate([jnp.zeros((1,), jnp.uint32), lo[:-1]])
    carries = jnp.cumsum(fell, dtype=jnp.uint32)
    return lo, carries if hi is None else hi + carries


@jax.named_scope("group")
def group_sorted(skeys_cols: tuple, counts: jax.Array, out_cap: int):
    """Group adjacent equal rows of lexicographically sorted key columns.

    The shared reduce idiom (run-boundary detect + per-run sum + compact)
    used by the single-chunk kernel, by the sharded all_to_all merge
    (parallel/shuffle.py) and by the device table's folds
    (device/table.py).  No scatter: the compaction of the run starts is
    :func:`compact_positions` (one single-key int32 sort), and a run's
    total is the prefix sum of the counts (:func:`running_sum`, in the
    counts' own dtype) read at the run's start and at the next run's and
    differenced, the last run ending where the valid rows end.  Sums are
    modular, so a difference is exact wherever the total itself fits.
    ``skeys_cols``: k sorted unsigned key columns (uint32 lanes or uint64
    packed lane pairs), PAD rows last — a pad row is all-ones in every
    lane, i.e. the dtype's max in every column; ``counts``: per-row
    counts to sum within each group, or a pair ``(low, high)`` of
    ``uint32`` halves (``high`` None: all zero) of values whose totals
    pass 32 bits: the totals are then ``uint32[out_cap, 2]``, (low, high),
    summed by :func:`running_sum_pair`; or a list of such pairs, several
    sums a key side by side (the join's revenue, rank and row count): the
    totals are then ``uint32[out_cap, 2 * len(counts)]``, a pair's two
    lanes behind the pair's before it.

    Returns (keys2d [t,k], totals [out_cap], upos [out_cap] int32, ovalid
    [out_cap], n_unique) — callers gather their payloads at ``upos`` and
    mask with ``ovalid``.  With ``n_unique > out_cap`` the first
    ``out_cap`` runs are returned whole.
    """
    t = skeys_cols[0].shape[0]
    k = len(skeys_cols)
    dtype = skeys_cols[0].dtype
    with enable_x64(True):  # 64-bit constants need the scoped flag
        pad = jnp.array(jnp.iinfo(dtype).max, dtype)  # _PAD_KEY for u32
        keys = jnp.stack(skeys_cols, axis=1)
        valid = skeys_cols[0] != pad
        prev = jnp.concatenate(
            [jnp.full((1, k), pad, dtype), keys[:-1]], axis=0)
    is_new = jnp.any(keys != prev, axis=1) & valid
    n_unique = jnp.sum(is_new, dtype=jnp.int32)
    # One start more than out_cap: where the last returned run ends when
    # there are more runs than fit.
    starts = compact_positions(is_new, out_cap + 1, t - 1)
    upos = starts[:out_cap]
    live = jnp.arange(out_cap + 1, dtype=jnp.int32) < n_unique
    ovalid = live[:out_cap]
    bounds = jnp.where(live, starts, jnp.int32(t))

    def pair_totals(pair):
        lo, hi = (None if c is None else jnp.where(valid, c, jnp.uint32(0))
                  for c in pair)
        at = jnp.maximum(bounds - 1, 0)
        lo, hi = (jnp.where(bounds > 0, c[at], jnp.uint32(0))
                  for c in running_sum_pair(lo, hi))
        borrow = (lo[1:] < lo[:-1]).astype(jnp.uint32)
        return jnp.where(
            ovalid[:, None],
            jnp.stack([lo[1:] - lo[:-1], hi[1:] - hi[:-1] - borrow], axis=1),
            jnp.uint32(0))

    if isinstance(counts, list):
        totals = jnp.concatenate([pair_totals(pair) for pair in counts],
                                 axis=1)
        return keys, totals, upos, ovalid, n_unique
    if isinstance(counts, tuple):
        return keys, pair_totals(counts), upos, ovalid, n_unique
    with enable_x64(True):  # the counts may be 64-bit
        zero = jnp.zeros((), counts.dtype)
        csum = running_sum(jnp.where(valid, counts, zero))
        # The sum of every row before a boundary; pad rows add 0, so the
        # boundary t reads the sum of the valid rows.
        before = jnp.where(bounds > 0, csum[jnp.maximum(bounds - 1, 0)], zero)
        totals = jnp.where(ovalid, before[1:] - before[:-1], zero)
    return keys, totals, upos, ovalid, n_unique


def tokenize_group_core(chunk: jax.Array, *, max_word_len: int = 16,
                        u_cap: int = 1 << 17, t_cap_frac: int = 4,
                        doc_sep: Optional[int] = None):
    """Exact unique-word counts over one uint8 chunk (zero-padded tail).

    Returns (packed_u [u_cap, K] uint32, len_u [u_cap] i32, cnt_u [u_cap]
    i32, fnv_u [u_cap] u32, n_unique i32, max_len i32, has_high bool,
    token_overflow bool).

    ``doc_sep`` (a non-letter byte under 128, :data:`DOC_SEP`) makes the
    chunk a pack of whole documents with that byte between them
    (``parallel/grepstream.pack_chunk``), and the group key (word,
    document): one row for every distinct word of every document, and a
    ninth result, ``doc_u`` [u_cap] int32, the row's document as its
    place in the chunk.  A token's place is the count of separators
    before its first byte (:func:`token_lanes`), and it sorts as one
    more key lane behind the word's.

    Identical tokens are grouped by an exact lexicographic sort of the
    key lanes (:func:`lex_sort`) and a scan of the run boundaries
    (:func:`group_sorted`), on every platform.  ``token_overflow`` says
    the chunk holds more tokens than ``t_cap``; the callers' ladders then
    run it again at ``t_cap_frac=2``, which no chunk overflows.

    Not jitted itself so it can be inlined into larger programs (the
    ``shard_map`` SPMD step in ``dsi_tpu/parallel/shuffle.py`` traces it per
    device before the ``all_to_all`` shuffle); ``count_words_kernel`` below
    is the jitted single-chunk entry point.
    """
    n = chunk.shape[0]
    k = max_word_len // 4
    t_cap = n // t_cap_frac + 1

    packed_cols, lengths, n_tokens, doc_lane, _ = token_lanes(
        chunk, max_word_len=max_word_len, t_cap_frac=t_cap_frac,
        doc_sep=doc_sep)
    token_overflow = n_tokens > t_cap
    max_len = jnp.max(lengths, initial=0)

    # Group identical words: lexicographic sort over the key lanes
    # (lex_sort: one single-key pass per lane), then run boundaries.  A
    # packed chunk's document lane sorts behind the word's.
    docs = () if doc_sep is None else (doc_lane,)
    *scols, slens = lex_sort(packed_cols + docs, (lengths,))
    skeys, totals, upos, ovalid, n_unique = group_sorted(
        tuple(scols), jnp.ones(t_cap, jnp.int32), u_cap)
    with jax.named_scope("group"):
        packed_u = jnp.where(ovalid[:, None], skeys[upos], jnp.uint32(0))
        len_u = jnp.where(ovalid, slens[upos], 0)
        if doc_sep is not None:
            docs = (packed_u[:, k].astype(jnp.int32),)
            packed_u = packed_u[:, :k]
    fnv_u = fnv1a32_packed(packed_u, len_u, max_word_len)
    with jax.named_scope("tokenize"):
        has_high = jnp.any(chunk >= 128)
    return (packed_u, len_u, totals, fnv_u, n_unique, max_len, has_high,
            token_overflow, *docs)


count_words_kernel = x64_scoped(jax.jit(
    tokenize_group_core,
    static_argnames=("max_word_len", "u_cap", "t_cap_frac", "doc_sep")))


@functools.lru_cache(maxsize=256)
def _cached_kernel(n: int, max_word_len: int, u_cap: int, t_cap_frac: int):
    """The single-chunk kernel via the persistent AOT executable cache
    (backends/aotcache.py): a fresh worker process loads the serialized
    executable in milliseconds instead of re-paying the XLA compile —
    essential on platforms where jit compiles run to minutes and every
    mrworker is its own process (main/test-mr.sh:43-45 spawns three).
    lru_cached so repeat dispatches skip the cache-key fingerprinting."""
    from dsi_tpu.backends.aotcache import cached_compile

    example = (jax.ShapeDtypeStruct((n,), np.uint8),)
    static = {"max_word_len": max_word_len, "u_cap": u_cap,
              "t_cap_frac": t_cap_frac}
    return cached_compile("wc_kernel", tokenize_group_core, example,
                          static=static, x64=True)


def run_count_kernel(chunk: jax.Array, *, max_word_len: int, u_cap: int,
                     t_cap_frac: int):
    """Dispatch one chunk through the AOT-cached executable."""
    fn = _cached_kernel(int(chunk.shape[0]), max_word_len, u_cap, t_cap_frac)
    return fn(chunk)


def _pad_pow2(data: bytes, min_size: int = 256) -> np.ndarray:
    """Zero-pad to the next power of two so jit caches a few shapes only.
    Zero bytes are non-letters, so padding can't create or extend tokens."""
    n = max(min_size, len(data) + 1)
    size = 1 << (n - 1).bit_length()
    buf = np.zeros(size, dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    return buf


def decode_packed(packed_u: np.ndarray, len_u: np.ndarray,
                  n_unique: int) -> list:
    """Host detokenization: packed big-endian uint32 rows -> word strings.

    One bulk byteswap + tobytes for the whole table, then cheap slices —
    no per-row numpy scalar extraction (this sits on bench.py's timed path).
    """
    nu = int(n_unique)
    rows = np.ascontiguousarray(np.asarray(packed_u[:nu])).astype(">u4")
    buf = rows.tobytes()
    stride = rows.shape[1] * 4
    lens = np.asarray(len_u[:nu]).tolist()
    return [buf[i * stride:i * stride + lens[i]].decode("ascii")
            for i in range(nu)]


def rung0_cap(shard_len: int, u_cap: int) -> int:
    """exactness_retry's starting capacity: ``u_cap`` bounded by the
    token-count hard cap for this shard length (n//2+1, pow2-rounded to
    keep the jit shape-cache small), floored at 1 (a zero/negative start
    could never widen: 0 * 4 == 0)."""
    hard_cap = 1 << (shard_len // 2).bit_length()
    return max(1, min(u_cap, hard_cap))


def exactness_retry(run, shard_len: int, max_word_len: int, u_cap: int):
    """Shared overflow/retry discipline for the static-shape kernels.

    ``run(mwl, cap)`` executes a kernel attempt and returns
    ``(has_high, n_unique_max, max_len, payload)`` where the first three are
    host scalars summarising every shard of the attempt.  While uniques
    overflow, retries at the first ``cap*4^k`` rung that holds the count
    the overflowing attempt reported — the rungs stay the ``x4`` ladder
    (bounded by the token-count hard cap n//2+1, pow2-rounded to keep the
    jit shape-cache small), but a rung that is known not to fit is never
    compiled: on the chip every rung costs a minute or two of XLA:TPU
    compile.  Then retries with a 64-byte word window if a word overflowed
    the packed window.  Returns the successful payload, or None when the
    input needs the host path (non-ASCII bytes, or words longer than 64)."""
    ladder = (max_word_len, 64) if max_word_len < 64 else (max_word_len,)
    for mwl in ladder:
        cap = rung0_cap(shard_len, u_cap)
        while True:
            has_high, n_unique_max, max_len, payload = run(mwl, cap)
            if has_high:
                return None
            if n_unique_max > cap:
                while cap < n_unique_max:
                    cap *= 4
                continue
            break
        if max_len > mwl:
            continue  # a word overflowed the packed window: widen kernel
        return payload
    return None


def count_words_host_result(
        data: bytes, *, max_word_len: int = 16,
        u_cap: int = 1 << 17) -> Optional[Dict[str, tuple]]:
    """Run the kernel (retrying with wider kernels on overflow) and return
    ``{word: (count, ihash)}``.

    Returns None if and only if the text needs the host fallback (non-ASCII
    bytes, or words longer than 64 bytes); callers must test ``is None`` —
    letter-free input legitimately returns an empty dict."""
    with _span("materialize", lane="host", bytes=len(data)):
        chunk = _pad_pow2(data)
    with _span("upload", bytes=chunk.nbytes):
        dev_chunk = jnp.asarray(chunk)
    attempts = itertools.count()

    def run(mwl: int, cap: int):
        for frac in (4, 2):  # exact token bound is n//2+1
            # dispatch to the first blocking scalar read
            with _span("kernel", program="wc_kernel",
                       attempt=next(attempts), cap=cap):
                (packed_u, len_u, cnt_u, fnv_u, n_unique, max_len,
                 has_high, tok_of) = run_count_kernel(
                    dev_chunk, max_word_len=mwl, u_cap=cap,
                    t_cap_frac=frac)
                overflow = bool(tok_of)
            if not overflow:
                break
        nu = int(n_unique)

        def payload():
            with _span("pull") as sp:
                packed, lens = np.asarray(packed_u), np.asarray(len_u)
                counts = np.asarray(cnt_u[:nu])
                hashes = np.asarray(fnv_u[:nu]) & 0x7FFFFFFF
                sp.set(bytes=packed.nbytes + lens.nbytes + counts.nbytes
                       + hashes.nbytes)
            with _span("decode", lane="host", records=nu):
                words = decode_packed(packed, lens, nu)
                return {w: (int(counts[i]), int(hashes[i]))
                        for i, w in enumerate(words)}

        return bool(has_high), nu, int(max_len), payload

    payload = exactness_retry(run, len(chunk), max_word_len, u_cap)
    return None if payload is None else payload()


def count_words_many(datas, *, max_word_len: int = 16,
                     u_cap: int = 1 << 17) -> list:
    """Pipelined multi-split word count: launch the kernel for EVERY split
    before synchronizing on any, so host↔device transfers and device compute
    overlap (JAX async dispatch).  Splits whose optimistic first attempt
    overflowed re-run through the full retry ladder (rare).

    Returns one ``{word: (count, ihash)} | None`` per input, same contract
    as ``count_words_host_result``.
    """
    launches = []
    for data in datas:
        chunk = _pad_pow2(data)
        cap = rung0_cap(len(chunk), u_cap)
        launches.append((data, cap,
                         run_count_kernel(jnp.asarray(chunk),
                                          max_word_len=max_word_len,
                                          u_cap=cap, t_cap_frac=4)))
    results = []
    for data, cap, out in launches:
        (packed_u, len_u, cnt_u, fnv_u, n_unique, max_len, has_high,
         tok_of) = out
        if bool(has_high):
            results.append(None)
            continue
        if bool(tok_of) or int(n_unique) > cap or int(max_len) > max_word_len:
            results.append(count_words_host_result(
                data, max_word_len=max_word_len, u_cap=u_cap))
            continue
        nu = int(n_unique)
        words = decode_packed(np.asarray(packed_u), np.asarray(len_u), nu)
        counts = np.asarray(cnt_u[:nu])
        hashes = np.asarray(fnv_u[:nu]) & 0x7FFFFFFF
        results.append({w: (int(counts[i]), int(hashes[i]))
                        for i, w in enumerate(words)})
    return results
