"""The sort chain's device programs: records in, records in key order out.

A record is 100 bytes, 25 little-endian ``uint32`` words as the host's
``np.frombuffer`` views it (no byte is touched on the way up); its key is
bytes 0-9, compared as unsigned bytes.  Four programs, each under a
module name a device trace can tell apart:

* ``sort_ingest_step`` (one device) takes one uploaded chunk of whole
  records and appends it to the store that stays on the device for the
  whole job:
  the records as rows of ``[capacity, 25]``, their keys as three
  big-endian ``uint32`` lanes (:func:`key_lanes`: the order of the lanes,
  most significant first, is the order of the key's bytes); it finds
  every record's partition against the sampled split points
  (:func:`partition_of`, a ``searchsorted`` by compare-and-sum) and
  returns the chunk's count of records a partition.
* ``sort_exchange_step`` (a mesh of several devices, one program over
  all of them under ``shard_map``) takes one chunk a device and routes
  before it appends: a record's owner is the device whose key range
  holds its key (:func:`partition_of` against the device split points),
  its row, the three key lanes before the 25 words, goes there through
  ``parallel/shuffle.shuffle_rows``' one scatter and one
  ``lax.all_to_all``, and every device appends what it received, source
  device by source device, to its own store at its own fill.
* ``sort_order`` orders a device's whole store (on a mesh every device
  its own, one SPMD program of the same name): the permutation by
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

from jax.sharding import Mesh, PartitionSpec as P

from dsi_tpu.ops.wordcount import lex_sort
from dsi_tpu.parallel.shuffle import AXIS, shuffle_rows
from dsi_tpu.utils.jaxcompat import shard_map

#: Bytes, key bytes and 32-bit words of a record.
RECORD_BYTES = 100
KEY_BYTES = 10
RECORD_WORDS = RECORD_BYTES // 4
#: Single-key sort passes of the ordering: one a key lane.
ORDER_PASSES = 3
#: Every lane of a row past the job's end: the third is above every key's
#: (whose low half is zero), so the row sorts behind every record.  It is
#: also ``shuffle_rows``' pad key: a row of an exchanged block is a record
#: if the low half of its third lane is zero, whatever its key.
PAST_END = 0xFFFFFFFF
#: Key lanes before the record's words in an exchanged row (112 bytes).
KEY_LANES = 3


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


def _read_chunk(chunk, n_valid, splits, *, chunk_records: int):
    """A chunk's rows, which of them are records, their key lanes and the
    chunk's count of records a partition."""
    rows = chunk[:chunk_records * RECORD_WORDS].reshape(
        chunk_records, RECORD_WORDS)
    valid = jnp.arange(chunk_records, dtype=jnp.int32) < n_valid
    lanes = key_lanes(rows)
    part = partition_of(lanes, splits)
    n_reduce = splits.shape[0] + 1
    hist = jnp.sum(
        (part[:, None] == jnp.arange(n_reduce, dtype=jnp.int32))
        & valid[:, None], axis=0, dtype=jnp.int32)
    return rows, valid, lanes, hist


def _ingest(store, lanes, chunk, offset, n_valid, splits, *,
            chunk_records: int):
    rows, valid, keys, hist = _read_chunk(chunk, n_valid, splits,
                                          chunk_records=chunk_records)
    new = jnp.where(valid, jnp.stack(keys), jnp.uint32(PAST_END))
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


def _exchange(store, lanes, fill, chunk, splits, device_splits, *,
              chunk_records: int, n_dev: int):
    """One device's part of ``sort_exchange_step`` (under ``shard_map``):
    its blocks of the sharded arguments in, its blocks of the results
    out."""
    chunk = chunk.reshape(-1)
    n_valid = chunk[chunk_records * RECORD_WORDS].astype(jnp.int32)
    rows, valid, keys, hist = _read_chunk(chunk, n_valid, splits,
                                          chunk_records=chunk_records)
    with jax.named_scope("route"):
        owner = partition_of(keys, device_splits)
        dest = jnp.where(valid, owner, n_dev)
        left = jnp.sum(valid & (owner != lax.axis_index(AXIS)),
                       dtype=jnp.int32)
        send = jnp.concatenate([jnp.stack(keys, axis=1), rows], axis=1)
    recv = shuffle_rows(send, dest, n_dev=n_dev, u_cap=chunk_records,
                        k=KEY_LANES)
    with jax.named_scope("land"):
        blocks = recv.reshape(n_dev, chunk_records, KEY_LANES + RECORD_WORDS)
        # a source's records lead its block; a pad row says so in the
        # low half of its third lane, which no key reaches
        took = jnp.sum((blocks[:, :, 2] & jnp.uint32(0xFFFF)) == 0, axis=1,
                       dtype=jnp.int32)
        ends = fill[0] + jnp.cumsum(took)
    with jax.named_scope("append"):
        # source by source, each block whole at the fill the one before
        # it left: its pad rows (zero words, PAST_END lanes) lie past the
        # store's end until the next block or the next step covers them
        for s in range(n_dev):
            at = ends[s] - took[s]
            store = lax.dynamic_update_slice(
                store, blocks[s, :, KEY_LANES:], (at, 0))
            lanes = lax.dynamic_update_slice(
                lanes, blocks[s, :, :KEY_LANES].T, (0, at))
    counts = jnp.concatenate([hist, left[None], ends[-1:]])
    return store, lanes, ends[-1:], counts[None]


@functools.lru_cache(maxsize=None)
def exchange_fn(chunk_records: int, mesh: Mesh):
    """``sort_exchange_step(store, lanes, fill, chunks, splits,
    device_splits)`` over ``mesh``, a chunk of ``chunk_records`` records a
    device (``chunks`` is ``uint32[n_dev, chunk_words]``, a row a device,
    the count of real records in the word behind them).  ``store``
    (``uint32[n_dev * capacity, 25]``), ``lanes`` (``uint32[3, n_dev *
    capacity]``) and ``fill`` (``int32[n_dev]``: the records a device
    holds) are sharded a device, donated, and come back with what each
    device received appended at its fill.  A device's store has to hold
    its fill and one block of ``chunk_records`` rows more: the caller
    reads the fills in the fourth result and fails the job that outgrew
    a store (a ``dynamic_update_slice`` that does not fit is moved, not
    refused).  The fourth result, ``int32[n_dev, n_reduce + 2]``: a
    device's count of its chunk's records a partition, how many of them
    it sent to another device, and its fill after the step."""
    n_dev = int(mesh.devices.size)
    body = functools.partial(_exchange, chunk_records=chunk_records,
                             n_dev=n_dev)

    def sort_exchange_step(store, lanes, fill, chunks, splits,
                           device_splits):
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(AXIS, None), P(None, AXIS), P(AXIS), P(AXIS, None),
                      P(), P()),
            out_specs=(P(AXIS, None), P(None, AXIS), P(AXIS),
                       P(AXIS, None)))(store, lanes, fill, chunks, splits,
                                       device_splits)

    return jax.jit(sort_exchange_step, donate_argnums=(0, 1, 2))


def chunk_words(chunk_bytes: int) -> int:
    """Words of an uploaded chunk of ``chunk_bytes``: its whole records,
    the step's two header words behind them, the rest padding."""
    return max(chunk_bytes // 4,
               chunk_bytes // RECORD_BYTES * RECORD_WORDS + 2)


def _order(store, lanes):
    index = jnp.arange(store.shape[0], dtype=jnp.int32)
    perm = lex_sort((lanes[0], lanes[1], lanes[2]), (index,))[3]
    with jax.named_scope("gather"):
        return jnp.take(store, perm, axis=0)


@jax.jit
def sort_order(store, lanes):
    """The store's rows in key order (module docstring)."""
    return _order(store, lanes)


@functools.lru_cache(maxsize=None)
def mesh_order_fn(mesh: Mesh):
    """``sort_order(store, lanes)`` over ``mesh``: every device's store in
    key order, one SPMD program under the one-device program's name."""

    def sort_order(store, lanes):
        return shard_map(_order, mesh=mesh,
                         in_specs=(P(AXIS, None), P(None, AXIS)),
                         out_specs=P(AXIS, None))(store, lanes)

    return jax.jit(sort_order)


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
