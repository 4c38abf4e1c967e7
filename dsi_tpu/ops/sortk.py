"""The sort chain's device programs: records in, records in key order out.

A record is 100 bytes, 25 little-endian ``uint32`` words as the host's
``np.frombuffer`` views it (no byte is touched on the way up); its key is
bytes 0-9, compared as unsigned bytes.  Three programs, each under a
module name a device trace can tell apart:

* ``sort_ingest_step`` takes one uploaded chunk of whole records and
  appends it to the store that stays on the device for the whole job:
  the records as rows of ``[capacity, 25]``, their keys as three
  big-endian ``uint32`` lanes (:func:`key_lanes`: the order of the lanes,
  most significant first, is the order of the key's bytes); it finds
  every record's partition against the sampled split points
  (:func:`partition_of`, a ``searchsorted`` by compare-and-sum) and
  returns the chunk's count of records a partition.
* ``sort_order`` orders the whole store: the permutation by
  ``ops/wordcount.lex_sort``'s single-key passes over the three lanes
  (stable, so ties keep input order), then one gather of the rows.
  Ordering by key is ordering by (partition, key): a partition is a
  range of keys.  Rows past the job's last record carry lanes above
  every key and sort last.
* ``sort_pull_block`` cuts a fixed block of rows out of the ordered
  store for the way down; one program whatever the offset.

A ``[n, 25]`` ``uint32`` array is tiled with its minor dimension padded
to 32 words on the chip (1.28 times the bytes), not to 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dsi_tpu.ops.wordcount import lex_sort

#: Bytes, key bytes and 32-bit words of a record.
RECORD_BYTES = 100
KEY_BYTES = 10
RECORD_WORDS = RECORD_BYTES // 4
#: Single-key sort passes of the ordering: one a key lane.
ORDER_PASSES = 3
#: Every lane of a row past the job's end: the third is above every key's
#: (whose low half is zero), so the row sorts behind every record.
PAST_END = 0xFFFFFFFF


def _byteswap(w: jax.Array) -> jax.Array:
    """A little-endian word's bytes as a big-endian number."""
    return ((w << 24) | ((w << 8) & jnp.uint32(0x00FF0000))
            | ((w >> 8) & jnp.uint32(0x0000FF00)) | (w >> 24))


def key_lanes(rows: jax.Array) -> tuple:
    """The keys of ``rows`` (``uint32[n, 25]``) as three ``uint32[n]``
    lanes, most significant first: bytes 0-3, bytes 4-7, and bytes 8-9 in
    the high half of the third (its low half zero)."""
    with jax.named_scope("pack"):
        return (_byteswap(rows[:, 0]), _byteswap(rows[:, 1]),
                _byteswap(rows[:, 2]) & jnp.uint32(0xFFFF0000))


def partition_of(lanes: tuple, splits: jax.Array) -> jax.Array:
    """The partition of every key: how many of the split points
    (``uint32[n_reduce - 1, 3]``, non-decreasing) are less than or equal
    to it, as ``bisect_right`` counts them."""
    with jax.named_scope("partition"):
        l0, l1, l2 = (lane[:, None] for lane in lanes)
        s0, s1, s2 = splits[:, 0], splits[:, 1], splits[:, 2]
        at_or_past = (l0 > s0) | ((l0 == s0) & (
            (l1 > s1) | ((l1 == s1) & (l2 >= s2))))
        return jnp.sum(at_or_past, axis=1, dtype=jnp.int32)


def _ingest(store, lanes, chunk, offset, n_valid, splits, *,
            chunk_records: int):
    rows = chunk[:chunk_records * RECORD_WORDS].reshape(
        chunk_records, RECORD_WORDS)
    valid = jnp.arange(chunk_records, dtype=jnp.int32) < n_valid
    l0, l1, l2 = key_lanes(rows)
    part = partition_of((l0, l1, l2), splits)
    n_reduce = splits.shape[0] + 1
    hist = jnp.sum(
        (part[:, None] == jnp.arange(n_reduce, dtype=jnp.int32))
        & valid[:, None], axis=0, dtype=jnp.int32)
    new = jnp.where(valid, jnp.stack([l0, l1, l2]), jnp.uint32(PAST_END))
    with jax.named_scope("append"):
        store = lax.dynamic_update_slice(store, rows, (offset, 0))
        lanes = lax.dynamic_update_slice(lanes, new, (0, offset))
    return store, lanes, hist


@functools.lru_cache(maxsize=None)
def ingest_fn(chunk_records: int):
    """``sort_ingest_step(store, lanes, chunk, splits)`` for chunks of
    ``chunk_records`` records: the two resident arrays are donated and
    come back with the chunk's rows and lanes at row ``offset``, beside
    the chunk's count of records a partition (``int32[n_reduce]``).
    ``offset`` and the number of real records in the chunk ride in the
    two words behind the records (:func:`chunk_words`: in the chunk's own
    padding where it has 8 bytes of it): as two scalar arguments they
    were two more transfers a step, 0.16 s of a 513-step job's 0.55 s of
    dispatch on a v5e's host (``scripts/sort_micro.py ingest``, PERF.md
    section 6, PR 45)."""
    words = chunk_records * RECORD_WORDS

    def sort_ingest_step(store, lanes, chunk, splits):
        head = chunk[words:words + 2].astype(jnp.int32)
        return _ingest(store, lanes, chunk, head[0], head[1], splits,
                       chunk_records=chunk_records)

    return jax.jit(sort_ingest_step, donate_argnums=(0, 1))


def chunk_words(chunk_bytes: int) -> int:
    """Words of an uploaded chunk of ``chunk_bytes``: its whole records,
    the step's two header words behind them, the rest padding."""
    return max(chunk_bytes // 4,
               chunk_bytes // RECORD_BYTES * RECORD_WORDS + 2)


@jax.jit
def sort_order(store, lanes):
    """The store's rows in key order (module docstring)."""
    index = jnp.arange(store.shape[0], dtype=jnp.int32)
    perm = lex_sort((lanes[0], lanes[1], lanes[2]), (index,))[3]
    with jax.named_scope("gather"):
        return jnp.take(store, perm, axis=0)


#: Words of a pulled block's rows: a block goes down as ``[n, 128]``.
PULL_LANES = 128


@functools.lru_cache(maxsize=None)
def pull_block_fn(block_rows: int):
    """``sort_pull_block(ordered, start)``: ``block_rows`` rows from row
    ``start`` (clamped by ``dynamic_slice`` to the last whole block), as
    ``uint32[block_rows * 25 / 128, 128]``: the same words in the same
    order, in rows the chip tiles without padding, so that the host's
    copy is the records' bytes and nothing else.  ``block_rows`` is a
    multiple of 128.  (Flattened to one dimension the program compiled
    for 46 s on a v5e, in this shape in a second; left as ``[n, 25]`` the
    host's copy keeps the rows' padding to 32 words.)"""

    def sort_pull_block(ordered, start):
        block = lax.dynamic_slice(ordered, (start, 0),
                                  (block_rows, RECORD_WORDS))
        return block.reshape(block_rows * RECORD_WORDS // PULL_LANES,
                             PULL_LANES)

    return jax.jit(sort_pull_block)
