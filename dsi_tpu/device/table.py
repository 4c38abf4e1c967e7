"""Device-resident accumulator service: the cross-step merge table.

The streaming engine (``parallel/streaming.py``) historically mirrored
the reference MapReduce's host-centric shape: every step's reduce output
crossed D2H and was merged into the host accumulator before the next
step could retire — one pull per step, exactly the per-intermediate
round-trip the reference pays in JSON files on a shared filesystem
(``mr/worker.go:81-121``).  The depth-2 pipeline can hide the *merge*
but not the pull (its cost per step: not measured on the chip).

This module keeps the merged table ON DEVICE instead:

* :class:`DeviceTable` owns a persistent packed key/count table at a
  fixed per-device capacity rung — keys as big-endian uint32 lanes
  (``ops/wordcount.py`` layout, so the host decode path is unchanged),
  counts as uint64 (cross-step sums can exceed uint32 long before a
  sync), occupancy per device.  Every device holds only words of the
  reduce partitions it owns (``parallel/shuffle.py`` routing), so
  per-device tables are disjoint and a host drain is a concatenation.
* ``fold``: ONE compiled program (cached via ``backends/aotcache`` under
  ``aot``) merges a step's packed reduce output into the table in place:
  concat + lexicographic sort (``lex_sort``) + run detection + per-run sum —
  the same grouping idiom as the kernels' reduce, at table+step size.
  The table arrays are DONATED to the fold, so XLA updates the table in
  place and table residency never doubles; the step tensor is NOT
  donated — it is the recovery payload if the fold reports overflow.
* overflow never drops keys silently: a fold whose merged uniques exceed
  the capacity rung is a GLOBAL no-op (an on-device ``pmax`` makes every
  device keep its old shard — a mixed commit would double-count the
  folded devices when the step is recovered) and surfaces a widen signal
  in the fold's tiny ``[n_dev, 2]`` flags output.
* ``widen``: drain the table to the host accumulator (``PackedCounts``),
  reallocate at the next capacity rung (x4, the repo's rung discipline),
  and re-fold the orphaned steps — their packed tensors were kept alive
  exactly for this.  The same protocol re-keys the table when the word
  window widens mid-stream (kk changes, e.g. a >16-byte word forcing the
  64-byte rung).
* flag checks are LAGGED: blocking on a fold's flags the moment it is
  dispatched would wait out every kernel queued behind it on the
  in-order device stream — the serialization the pipeline exists to
  avoid.  Folds are confirmed ``lag`` folds late (the streaming engine
  passes its pipeline depth); folds are commutative count-sums and a
  failed fold is a no-op, so late detection loses nothing.

Sync cadence (pull every K folds) is owned by ``device/policy.py``; the
caller drives ``sync()``/``close()``.  Host pulls therefore number
``ceil(folds / K) + widens`` instead of one per step — the amortization
``pipeline_stats`` reports as ``sync_pulls``/``widens``.

``mesh_shards=n`` makes the table MESH-SHARDED: the fold program gains
an all-to-all exchange (``ops/meshroute.py``) that routes every step row
to its owning shard by the paper's partition rule — ``ihash(key) %
n_shards``, the reference-exact FNV-1a over the key bytes — BEFORE the
concat+sort+segsum merge, so each shard holds the complete, already-
merged state for its hash range and cross-step state scales with
aggregate HBM instead of per-device accidents (without it, key placement
follows the step's ``n_reduce % n_dev`` routing: with the default 10
partitions on 8 devices, two shards carry twice the keys of the rest).
What changes with it:

* the overflow signal becomes PER-SHARD: a fold commits on every shard
  whose merged uniques fit and no-ops only where they don't (safe
  because the exchange is deterministic — a re-fold under an ``apply``
  mask re-delivers exactly the failed shards' rows, and folds are
  commutative count-sums), so a hot shard never blocks the mesh;
* the widen protocol is per-shard: only hot shards drain to the host
  (a single-shard D2H via its addressable shard — cold shards never
  touch the wire), the reallocation copies cold shards ON DEVICE
  (compiled ``mesh_grow_*`` program; the physical rung is shared — XLA
  arrays are rectangular — but only hot shards' content moves), and
  only hot shards re-fold, counted per shard in ``shard_widens``;
* sync pulls the occupied prefix of ONE pre-merged, hash-balanced
  table (``pull_bytes`` counts the actual D2H payload both ways — the
  bench's mesh A/B row reads it), and ``shard_imbalance`` tracks
  max/mean shard occupancy (~1.0 under FNV routing; the skew evidence
  when a corpus is adversarial);
* fold spans land in the tracer's ``shuffle`` lane (the fold IS the
  shuffle there), with ``shard_widen`` events carrying the hot set.

Results are bit-identical to ``mesh_shards=0`` (and to the depth=1
host-merge path): routing changes WHICH shard holds a key, never the
key's count, and every drain ends in the same host accumulator.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import warnings
from typing import Deque, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.obs import enqueued as _enqueued, span as _span, \
    trace_event as _trace_event
from dsi_tpu.ops.meshroute import exchange_rows, route_dest
from dsi_tpu.ops.wordcount import _PAD_KEY, group_sorted, lex_sort
from dsi_tpu.parallel.shuffle import AXIS, occupied_prefix
from dsi_tpu.utils.jaxcompat import enable_x64, x64_scoped, shard_map

#: jax.jit donate_argnums for the fold/clear programs: the five table
#: arrays are consumed and rewritten in place.  Shared by the jit path,
#: the AOT compile, the warmer, and the cache-existence probe.
_TABLE_DONATE = (0, 1, 2, 3, 4)


@contextlib.contextmanager
def _quiet_unusable_donation():
    """On backends where XLA declines to alias a donated buffer (XLA:CPU
    does even for shape-matched donations) jax warns once per compiled
    program — expected for OUR dispatches, so the warning is suppressed
    around them only: a process-global filter would hide the same
    warning from the user's unrelated jax programs, where a silently
    unusable donation is real signal.  The single definition for every
    donating dispatch site (the streaming engine imports it from
    here)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _fold_device(tkeys, tlens, tcnts, tparts, tn, packed, scal, *,
                 cap: int, kk: int):
    """Per-device fold body (runs under shard_map).

    Table shard + this device's slice of the step's packed reduce output
    -> merged table shard + ``[overflow, occupancy]`` flags.  Pad rows
    carry ``_PAD_KEY`` in every lane so
    they sort last and ``group_sorted``'s max-value pad detection holds —
    the invariant every fold output re-establishes.
    """
    tkeys = tkeys.reshape(cap, kk)
    tlens = tlens.reshape(cap)
    tcnts = tcnts.reshape(cap)
    tparts = tparts.reshape(cap)
    tn0 = tn.reshape(())
    rows = packed.shape[-2]
    packed = packed.reshape(rows, kk + 3)
    scal = scal.reshape(-1)

    # Step rows beyond this device's merged-unique count are garbage
    # (zero keys, not pad): mask them to pad rows before the sort.
    sn = scal[0]
    svalid = jnp.arange(rows, dtype=jnp.int32) < sn
    skeys = jnp.where(svalid[:, None], packed[:, :kk], jnp.uint32(_PAD_KEY))
    slens = jnp.where(svalid, packed[:, kk].astype(jnp.int32), 0)
    sparts = jnp.where(svalid, packed[:, kk + 2].astype(jnp.int32), 0)

    with enable_x64(True):  # every op touching u64 operands needs it
        scnts = jnp.where(svalid, packed[:, kk + 1].astype(jnp.uint64),
                          jnp.uint64(0))
        allkeys = jnp.concatenate([tkeys, skeys], axis=0)
        alllens = jnp.concatenate([tlens, slens])
        allcnts = jnp.concatenate([tcnts, scnts])
        allparts = jnp.concatenate([tparts, sparts])
        *scols, mlens, mcnts, mparts = lex_sort(
            tuple(allkeys[:, j] for j in range(kk)),
            (alllens, allcnts, allparts))
        mkeys, tot, upos, ovalid, m_unique = group_sorted(
            tuple(scols), mcnts, cap)
        new_keys = jnp.where(ovalid[:, None], mkeys[upos],
                             jnp.uint32(_PAD_KEY))
        new_cnts = jnp.where(ovalid, tot, jnp.uint64(0))
    new_lens = jnp.where(ovalid, mlens[upos], 0)
    new_parts = jnp.where(ovalid, mparts[upos], 0)

    # Commit is all-or-nothing ACROSS devices: if any shard overflowed,
    # every shard keeps its old table (the step is recovered whole by the
    # widen path; a partial commit would double-count the folded shards).
    ov = lax.pmax((m_unique > cap).astype(jnp.int32), AXIS)
    keep_old = ov > 0
    out_keys = jnp.where(keep_old, tkeys, new_keys)
    out_lens = jnp.where(keep_old, tlens, new_lens)
    out_cnts = jnp.where(keep_old, tcnts, new_cnts)
    out_parts = jnp.where(keep_old, tparts, new_parts)
    out_n = jnp.where(keep_old, tn0, jnp.minimum(m_unique, cap))
    flags = jnp.stack([ov, out_n])
    return (out_keys[None], out_lens[None], out_cnts[None], out_parts[None],
            out_n[None], flags[None])


def _fold_impl(tkeys, tlens, tcnts, tparts, tn, packed, scal, *, mesh: Mesh):
    cap, kk = tkeys.shape[1], tkeys.shape[2]
    body = functools.partial(_fold_device, cap=cap, kk=kk)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                  P(AXIS, None), P(AXIS), P(AXIS, None, None), P(AXIS, None)),
        out_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                   P(AXIS, None), P(AXIS), P(AXIS, None)),
    )(tkeys, tlens, tcnts, tparts, tn, packed, scal)


#: In-process fold program for multi-device meshes / non-aot callers.
fold_step = x64_scoped(jax.jit(_fold_impl, static_argnames=("mesh",),
                               donate_argnums=_TABLE_DONATE))


def _mesh_fold_device(tkeys, tlens, tcnts, tparts, tn, packed, scal, apply,
                      *, cap: int, kk: int, n_dev: int, n_shards: int):
    """Per-shard mesh fold body (runs under shard_map): the paper's
    shuffle as the fold's prologue.  Every valid step row is routed to
    shard ``ihash(key) % n_shards`` over the mesh (one all_to_all), THEN
    merged into that shard's table slice — so the table is always the
    complete pre-merged state of each shard's hash range.  Commit is
    PER-SHARD: ``apply`` masks which shards merge at all (the re-fold
    path re-delivers an orphaned step only to the shards that no-op'd),
    and overflow no-ops only the shard it happened on."""
    tkeys = tkeys.reshape(cap, kk)
    tlens = tlens.reshape(cap)
    tcnts = tcnts.reshape(cap)
    tparts = tparts.reshape(cap)
    tn0 = tn.reshape(())
    rows = packed.shape[-2]
    packed = packed.reshape(rows, kk + 3)
    scal = scal.reshape(-1)
    apply0 = apply.reshape(()) > 0

    # Garbage rows beyond the step's merged-unique count are parked on
    # the exchange's dump row; valid rows route by the reference-exact
    # ihash over their actual key bytes (ops/meshroute.py).
    sn = scal[0]
    svalid = jnp.arange(rows, dtype=jnp.int32) < sn
    skeys = jnp.where(svalid[:, None], packed[:, :kk], jnp.uint32(_PAD_KEY))
    slens = jnp.where(svalid, packed[:, kk].astype(jnp.int32), 0)
    dest = route_dest(skeys, slens, svalid, n_shards=n_shards, park=n_dev)
    recv = exchange_rows(packed, dest, n_dev=n_dev, kk=kk)

    # Received rows are valid-prefix-per-source-block with PAD-key pad
    # rows (zero payload) — they sort last and group as empty, exactly
    # the invariant every fold output re-establishes.
    rlens = recv[:, kk].astype(jnp.int32)
    rparts = recv[:, kk + 2].astype(jnp.int32)
    with enable_x64(True):  # every op touching u64 operands needs it
        rcnts = recv[:, kk + 1].astype(jnp.uint64)
        allkeys = jnp.concatenate([tkeys, recv[:, :kk]], axis=0)
        alllens = jnp.concatenate([tlens, rlens])
        allcnts = jnp.concatenate([tcnts, rcnts])
        allparts = jnp.concatenate([tparts, rparts])
        *scols, mlens, mcnts, mparts = lex_sort(
            tuple(allkeys[:, j] for j in range(kk)),
            (alllens, allcnts, allparts))
        mkeys, tot, upos, ovalid, m_unique = group_sorted(
            tuple(scols), mcnts, cap)
        new_keys = jnp.where(ovalid[:, None], mkeys[upos],
                             jnp.uint32(_PAD_KEY))
        new_cnts = jnp.where(ovalid, tot, jnp.uint64(0))
    new_lens = jnp.where(ovalid, mlens[upos], 0)
    new_parts = jnp.where(ovalid, mparts[upos], 0)

    # Per-shard commit — no pmax: an overflowed shard keeps its old
    # slice and reports its own flag; everyone else commits.  Safe
    # because the exchange is deterministic (a re-fold re-delivers the
    # same rows to the same shards) and folds commute, so the recovery
    # re-fold under ``apply = failed shards`` double-counts nothing.
    ov = jnp.where(apply0, (m_unique > cap).astype(jnp.int32),
                   jnp.int32(0))
    keep_old = (ov > 0) | ~apply0
    out_keys = jnp.where(keep_old, tkeys, new_keys)
    out_lens = jnp.where(keep_old, tlens, new_lens)
    out_cnts = jnp.where(keep_old, tcnts, new_cnts)
    out_parts = jnp.where(keep_old, tparts, new_parts)
    out_n = jnp.where(keep_old, tn0, jnp.minimum(m_unique, cap))
    flags = jnp.stack([ov, out_n])
    return (out_keys[None], out_lens[None], out_cnts[None], out_parts[None],
            out_n[None], flags[None])


def _mesh_fold_impl(tkeys, tlens, tcnts, tparts, tn, packed, scal, apply, *,
                    mesh: Mesh, n_shards: int):
    cap, kk = tkeys.shape[1], tkeys.shape[2]
    n_dev = int(mesh.devices.size)
    body = functools.partial(_mesh_fold_device, cap=cap, kk=kk,
                             n_dev=n_dev, n_shards=n_shards)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                  P(AXIS, None), P(AXIS), P(AXIS, None, None), P(AXIS, None),
                  P(AXIS)),
        out_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                   P(AXIS, None), P(AXIS), P(AXIS, None)),
    )(tkeys, tlens, tcnts, tparts, tn, packed, scal, apply)


#: In-process mesh fold (the shuffle-fold) for non-aot callers.
mesh_fold_step = x64_scoped(
    jax.jit(_mesh_fold_impl, static_argnames=("mesh", "n_shards"),
            donate_argnums=_TABLE_DONATE))


def _grow_device(tkeys, tlens, tcnts, tparts, tn, keep, *, old_cap: int,
                 new_cap: int, kk: int):
    """Per-shard widen reallocation body: kept shards carry their rows
    into the wider allocation ON DEVICE (no wire), dropped (hot) shards
    come back empty — their rows were just drained to the host."""
    tkeys = tkeys.reshape(old_cap, kk)
    tlens = tlens.reshape(old_cap)
    tcnts = tcnts.reshape(old_cap)
    tparts = tparts.reshape(old_cap)
    tn0 = tn.reshape(())
    keep0 = keep.reshape(()) > 0

    gkeys = jnp.full((new_cap, kk), jnp.uint32(_PAD_KEY), jnp.uint32) \
        .at[:old_cap].set(tkeys)
    glens = jnp.zeros((new_cap,), jnp.int32).at[:old_cap].set(tlens)
    with enable_x64(True):
        gcnts = jnp.zeros((new_cap,), jnp.uint64).at[:old_cap].set(tcnts)
        out_cnts = jnp.where(keep0, gcnts, jnp.zeros_like(gcnts))
    gparts = jnp.zeros((new_cap,), jnp.int32).at[:old_cap].set(tparts)
    out_keys = jnp.where(keep0, gkeys,
                         jnp.full_like(gkeys, jnp.uint32(_PAD_KEY)))
    out_lens = jnp.where(keep0, glens, jnp.zeros_like(glens))
    out_parts = jnp.where(keep0, gparts, jnp.zeros_like(gparts))
    out_n = jnp.where(keep0, tn0, jnp.int32(0))
    return (out_keys[None], out_lens[None], out_cnts[None], out_parts[None],
            out_n[None])


def _grow_impl(tkeys, tlens, tcnts, tparts, tn, keep, *, mesh: Mesh,
               new_cap: int):
    old_cap, kk = tkeys.shape[1], tkeys.shape[2]
    body = functools.partial(_grow_device, old_cap=old_cap,
                             new_cap=new_cap, kk=kk)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                  P(AXIS, None), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                   P(AXIS, None), P(AXIS)),
    )(tkeys, tlens, tcnts, tparts, tn, keep)


grow_table = x64_scoped(
    jax.jit(_grow_impl, static_argnames=("mesh", "new_cap"),
            donate_argnums=_TABLE_DONATE))


@functools.partial(jax.jit, static_argnames=("mp",))
def _rows_prefix(rows, *, mp: int):
    """Fresh-buffer prefix slice of a ``[n_dev, rows, ...]`` tensor
    (shared with ``device/postings.py``): the output aliases nothing
    (no donation), so a retained slice — a delta capture, a snapshot
    pull — survives every later fold/clear/grow that donates the live
    state, and its D2H can drain under the next pipeline window."""
    return rows[:, :mp]


def _copy_to_host_async(arr) -> None:
    """Kick an async D2H on a jax array if the runtime supports it (the
    capture half of the overlapped snapshot); materialization later in
    the commit writer then finds the transfer already draining."""
    fn = getattr(arr, "copy_to_host_async", None)
    if fn is not None:
        try:
            fn()
        except Exception:
            pass  # overlap is an optimization; np.asarray still works


def _pull_shard(arr, d: int) -> np.ndarray:
    """D2H of ONE mesh shard: the per-shard widen's drain pulls only the
    hot shard's slice via its addressable shard — cold shards never
    touch the wire (the whole point of widening per shard)."""
    for s in arr.addressable_shards:
        idx = s.index[0]
        start = idx.start or 0
        if start == d and (idx.stop is None or idx.stop - start == 1):
            return np.asarray(s.data)[0]
    return np.asarray(arr[d])  # replicated/odd layout: plain slice pull


def _clear_device(tkeys, tlens, tcnts, tparts, tn):
    return (jnp.full_like(tkeys, jnp.uint32(_PAD_KEY)),
            jnp.zeros_like(tlens), jnp.zeros_like(tcnts),
            jnp.zeros_like(tparts), jnp.zeros_like(tn))


def _clear_impl(tkeys, tlens, tcnts, tparts, tn, *, mesh: Mesh):
    """Reset the table to empty ON DEVICE (donated, in place): a sync
    must not re-upload a capacity-sized block of pads just to start the
    next window."""
    return shard_map(
        _clear_device, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                  P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS, None, None), P(AXIS, None), P(AXIS, None),
                   P(AXIS, None), P(AXIS)),
    )(tkeys, tlens, tcnts, tparts, tn)


clear_table = x64_scoped(jax.jit(_clear_impl, static_argnames=("mesh",),
                                 donate_argnums=_TABLE_DONATE))


@functools.partial(jax.jit, static_argnames=("mp",))
def _pack_prefix_impl(tkeys, tlens, tparts, tcnts, *, mp: int):
    """Device-side prefix slice + pack for a table drain: one uint32
    tensor [D, mp, kk+2] (keys + len + part) plus the uint64 count
    prefix — two D2H transfers per SYNC, versus (historically) one pull
    per STEP.  ``mp`` is the pow2-rounded occupied prefix under jit,
    the full capacity under aot (deterministic shapes, same trade as the
    stream's pulls)."""
    packed = jnp.concatenate(
        [tkeys[:, :mp],
         tlens[:, :mp, None].astype(jnp.uint32),
         tparts[:, :mp, None].astype(jnp.uint32)], axis=2)
    return packed, tcnts[:, :mp]


_pack_prefix = x64_scoped(_pack_prefix_impl)


def _fold_program(*, mesh: Mesh, n_dev: int, cap: int, kk: int, rows: int):
    """(name, fn) for one compiled fold shape — single definition shared
    by the cached-compile path and the warmer (same discipline as
    ``streaming._step_program``)."""

    def fn(tkeys, tlens, tcnts, tparts, tn, packed, scal):
        return _fold_impl(tkeys, tlens, tcnts, tparts, tn, packed, scal,
                          mesh=mesh)

    return f"dacc_fold_d{n_dev}_c{cap}_k{kk}_r{rows}", fn


def _clear_program(*, mesh: Mesh, n_dev: int, cap: int, kk: int):
    def fn(tkeys, tlens, tcnts, tparts, tn):
        return _clear_impl(tkeys, tlens, tcnts, tparts, tn, mesh=mesh)

    return f"dacc_clear_d{n_dev}_c{cap}_k{kk}", fn


def _pack_program(*, n_dev: int, cap: int, kk: int, mp: int):
    def fn(tkeys, tlens, tparts, tcnts):
        return _pack_prefix_impl(tkeys, tlens, tparts, tcnts, mp=mp)

    return f"dacc_pack_d{n_dev}_c{cap}_k{kk}_m{mp}", fn


def _mesh_fold_program(*, mesh: Mesh, n_dev: int, n_shards: int, cap: int,
                       kk: int, rows: int):
    """(name, fn) for one compiled shuffle-fold shape — the ``mesh_*``
    warm-ladder entries, same single-definition discipline as
    :func:`_fold_program`."""

    def fn(tkeys, tlens, tcnts, tparts, tn, packed, scal, apply):
        return _mesh_fold_impl(tkeys, tlens, tcnts, tparts, tn, packed,
                               scal, apply, mesh=mesh, n_shards=n_shards)

    return (f"mesh_fold_d{n_dev}_s{n_shards}_c{cap}_k{kk}_r{rows}", fn)


def _grow_program(*, mesh: Mesh, n_dev: int, old_cap: int, new_cap: int,
                  kk: int):
    def fn(tkeys, tlens, tcnts, tparts, tn, keep):
        return _grow_impl(tkeys, tlens, tcnts, tparts, tn, keep,
                          mesh=mesh, new_cap=new_cap)

    return f"mesh_grow_d{n_dev}_c{old_cap}to{new_cap}_k{kk}", fn


def _table_structs(n_dev: int, cap: int, kk: int):
    sds = jax.ShapeDtypeStruct
    return (sds((n_dev, cap, kk), jnp.uint32),
            sds((n_dev, cap), jnp.int32),
            sds((n_dev, cap), jnp.uint64),
            sds((n_dev, cap), jnp.int32),
            sds((n_dev,), jnp.int32))


def _step_structs(n_dev: int, rows: int, kk: int):
    sds = jax.ShapeDtypeStruct
    return (sds((n_dev, rows, kk + 3), jnp.uint32),
            sds((n_dev, 5), jnp.int32))


def _apply_struct(n_dev: int):
    return jax.ShapeDtypeStruct((n_dev,), jnp.int32)


def _warm_mesh_fold_rung(mesh: Mesh, *, n_dev: int, n_shards: int,
                         cap: int, kk: int, rows: int,
                         grow: bool) -> None:
    """Compile + persist one mesh capacity rung: the ``mesh_fold_*``
    shuffle-fold at ``cap`` plus, with ``grow``, the ``mesh_grow_*``
    c→4c per-shard widen reallocation to the next rung.  The single
    source of the mesh warm-ladder shapes — ``warm_device_fold`` and
    ``topk.warm_topk_service`` both call it, so the compiled keys
    cannot drift between the word table and the top-k service."""
    from dsi_tpu.backends import aotcache

    table = _table_structs(n_dev, cap, kk)
    step = _step_structs(n_dev, rows, kk)
    name, fn = _mesh_fold_program(mesh=mesh, n_dev=n_dev,
                                  n_shards=n_shards, cap=cap, kk=kk,
                                  rows=rows)
    with _quiet_unusable_donation():
        aotcache.cached_compile(
            name, fn, table + step + (_apply_struct(n_dev),),
            donate_argnums=_TABLE_DONATE, x64=True)
    if grow:
        name, fn = _grow_program(mesh=mesh, n_dev=n_dev, old_cap=cap,
                                 new_cap=cap * 4, kk=kk)
        with _quiet_unusable_donation():
            aotcache.cached_compile(
                name, fn, table + (_apply_struct(n_dev),),
                donate_argnums=_TABLE_DONATE, x64=True)


def _warm_pack_shapes(*, n_dev: int, cap: int, kk: int,
                      mesh_shards: int) -> None:
    """Compile the drain pack program(s) for one capacity
    rung.  The non-mesh aot path pulls at the deterministic full
    capacity (one shape); mesh syncs pull the occupied PREFIX (the
    pre-merged table is hash-balanced, so the prefix tracks
    vocabulary/shards) — a data-dependent but pow2-bounded mp ladder
    (``occupied_prefix``: 64..cap, log2(cap) tiny slice+concat
    programs).  Warm the whole ladder so no prefix rung compiles
    mid-stream."""
    from dsi_tpu.backends import aotcache

    table = _table_structs(n_dev, cap, kk)
    mp = 64 if mesh_shards else cap
    while True:
        mp = min(mp, cap)
        name, fn = _pack_program(n_dev=n_dev, cap=cap, kk=kk, mp=mp)
        aotcache.cached_compile(
            name, fn, (table[0], table[1], table[3], table[2]), x64=True)
        if mp >= cap:
            break
        mp *= 2


def warm_device_fold(mesh: Mesh, *, u_cap: int, kk: int = 4,
                     table_rungs: int = 2, mesh_shards: int = 0) -> None:
    """Compile the fold/clear/pack shapes a device-accumulated
    stream reaches at this step capacity: the rung-0 table (cap = step
    rows) plus ``table_rungs - 1`` x4 widenings, from shape structs alone
    (no data, nothing executed).  Callers warm per step-cap rung, mirroring
    ``streaming.warm_stream_aot``'s caps ladder.  With ``mesh_shards``
    the mesh variants are warmed INSTEAD: the ``mesh_fold_*``
    shuffle-fold at each rung plus the ``mesh_grow_*`` per-shard widen
    reallocation between adjacent rungs."""
    from dsi_tpu.backends import aotcache

    n_dev = mesh.devices.size
    rows = n_dev * u_cap
    # Same rounding DeviceTable applies to its rung-0 capacity — warmed
    # keys must be, by construction, the keys a run compiles first.
    cap = _pow2(rows)
    for rung in range(max(1, table_rungs)):
        table = _table_structs(n_dev, cap, kk)
        step = _step_structs(n_dev, rows, kk)
        if mesh_shards:
            _warm_mesh_fold_rung(mesh, n_dev=n_dev, n_shards=mesh_shards,
                                 cap=cap, kk=kk, rows=rows,
                                 grow=rung + 1 < max(1, table_rungs))
        else:
            name, fn = _fold_program(mesh=mesh, n_dev=n_dev, cap=cap,
                                     kk=kk, rows=rows)
            with _quiet_unusable_donation():
                aotcache.cached_compile(name, fn, table + step,
                                        donate_argnums=_TABLE_DONATE,
                                        x64=True)
        name, fn = _clear_program(mesh=mesh, n_dev=n_dev, cap=cap, kk=kk)
        with _quiet_unusable_donation():
            aotcache.cached_compile(name, fn, table,
                                    donate_argnums=_TABLE_DONATE, x64=True)
        _warm_pack_shapes(n_dev=n_dev, cap=cap, kk=kk,
                          mesh_shards=mesh_shards)
        cap *= 4


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


class DeviceTable:
    """Persistent on-device merged word/count table, folded per step,
    drained per sync window.

    ``acc`` is the host :class:`~dsi_tpu.parallel.merge.PackedCounts`
    every drain merges into; ``stats``, if given, receives the service's
    counters (``folds``, ``fold_overflows``, ``sync_pulls``, ``widens``,
    ``table_cap``, and ``fold_s``/``sync_s``/``widen_s`` wall seconds).
    ``lag`` is how many folds may stay unconfirmed before the oldest's
    flags are checked (the streaming engine passes its pipeline depth);
    ``sync()``/``close()``/``widen`` flush the lag entirely.

    ``mesh_shards`` > 0 switches the fold to the mesh-sharded
    shuffle-fold (module docstring): keys route to ``ihash % n_shards``
    inside the compiled program, overflow flags and the widen protocol
    become per-shard (``shard_widens``), and ``shard_imbalance`` tracks
    max/mean occupancy.  ``pull_bytes`` counts every D2H drain payload
    in BOTH modes — the bench mesh A/B row's evidence.
    """

    def __init__(self, mesh: Mesh, *, kk: int, cap: int, acc,
                 aot: bool = False, lag: int = 1,
                 stats: Optional[dict] = None, mesh_shards: int = 0):
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.kk = int(kk)
        self.cap = _pow2(cap)
        self.acc = acc
        self.aot = bool(aot)
        self.lag = max(0, int(lag))
        self.mesh_shards = max(0, int(mesh_shards))
        if self.mesh_shards > self.n_dev:
            raise ValueError(
                f"mesh_shards={self.mesh_shards} exceeds the mesh size "
                f"({self.n_dev} devices); shards map 1:1 onto devices")
        self.stats = stats if stats is not None else {}
        for key in ("folds", "fold_overflows", "sync_pulls", "widens",
                    "pull_bytes"):
            self.stats.setdefault(key, 0)
        for key in ("fold_s", "sync_s", "widen_s"):
            self.stats.setdefault(key, 0.0)
        if self.mesh_shards:
            self.stats.setdefault("mesh_shards", self.mesh_shards)
            self.stats.setdefault("shard_widens", [0] * self.n_dev)
            self.stats.setdefault("shard_imbalance", 0.0)
        self._apply_dev = None  # cached all-shards apply mask (mesh mode)
        # Delta-checkpoint log (enable_delta): confirmed step payloads
        # retained since the last capture — the rows APPENDED to the
        # table, which is what an incremental save ships instead of the
        # whole image.  Step tensors are never donated (they are the
        # widen-recovery payload), so retaining the handles is safe.
        self._delta_log: list = []
        self._delta_max = 0
        self._delta_invalid = False
        self._state = self._alloc(self.cap, self.kk)
        # Occupancy per device after the last CONFIRMED fold (a no-op'd
        # fold reports the old occupancy, so this stays exact either way).
        self._nrows = np.zeros(self.n_dev, dtype=np.int64)
        # (flags_handle, packed_dev, scal_dev) per unconfirmed fold — the
        # step tensors stay referenced until their fold is proven clean,
        # so an overflowed (no-op) fold can be replayed after a widen.
        self._pending: Deque[Tuple] = collections.deque()
        self.stats["table_cap"] = self.cap

    # ── allocation / compiled-program plumbing ──

    def _alloc(self, cap: int, kk: int):
        """Fresh empty table arrays, sharded over the mesh.  One H2D
        upload per (re)allocation — allocation happens once per stream
        plus once per widen; per-sync resets go through the compiled
        ``clear`` program instead (no upload)."""
        sh3 = NamedSharding(self.mesh, P(AXIS, None, None))
        sh2 = NamedSharding(self.mesh, P(AXIS, None))
        sh1 = NamedSharding(self.mesh, P(AXIS))
        with enable_x64(True):  # keep the u64 counts u64 through the put
            return (
                jax.device_put(
                    np.full((self.n_dev, cap, kk), _PAD_KEY, np.uint32), sh3),
                jax.device_put(
                    np.zeros((self.n_dev, cap), np.int32), sh2),
                jax.device_put(
                    np.zeros((self.n_dev, cap), np.uint64), sh2),
                jax.device_put(
                    np.zeros((self.n_dev, cap), np.int32), sh2),
                jax.device_put(np.zeros((self.n_dev,), np.int32), sh1))

    def _fold_fn(self, rows: int):
        if not self.aot:
            return functools.partial(fold_step, mesh=self.mesh)
        from dsi_tpu.backends import aotcache

        name, fn = _fold_program(mesh=self.mesh, n_dev=self.n_dev,
                                 cap=self.cap, kk=self.kk, rows=rows)
        examples = (_table_structs(self.n_dev, self.cap, self.kk)
                    + _step_structs(self.n_dev, rows, self.kk))
        with _quiet_unusable_donation():  # a cold entry compiles here
            return aotcache.cached_compile(name, fn, examples,
                                           donate_argnums=_TABLE_DONATE,
                                           x64=True)

    def _mesh_fold_fn(self, rows: int):
        if not self.aot:
            return functools.partial(mesh_fold_step, mesh=self.mesh,
                                     n_shards=self.mesh_shards)
        from dsi_tpu.backends import aotcache

        name, fn = _mesh_fold_program(mesh=self.mesh, n_dev=self.n_dev,
                                      n_shards=self.mesh_shards,
                                      cap=self.cap, kk=self.kk, rows=rows)
        examples = (_table_structs(self.n_dev, self.cap, self.kk)
                    + _step_structs(self.n_dev, rows, self.kk)
                    + (_apply_struct(self.n_dev),))
        with _quiet_unusable_donation():
            return aotcache.cached_compile(name, fn, examples,
                                           donate_argnums=_TABLE_DONATE,
                                           x64=True)

    def _grow_fn(self, new_cap: int):
        if not self.aot:
            return functools.partial(grow_table, mesh=self.mesh,
                                     new_cap=new_cap)
        from dsi_tpu.backends import aotcache

        name, fn = _grow_program(mesh=self.mesh, n_dev=self.n_dev,
                                 old_cap=self.cap, new_cap=new_cap,
                                 kk=self.kk)
        examples = (_table_structs(self.n_dev, self.cap, self.kk)
                    + (_apply_struct(self.n_dev),))
        with _quiet_unusable_donation():
            return aotcache.cached_compile(name, fn, examples,
                                           donate_argnums=_TABLE_DONATE,
                                           x64=True)

    def _put_apply(self, mask: np.ndarray):
        """Upload a per-shard apply mask (tiny [n_dev] int32)."""
        sh1 = NamedSharding(self.mesh, P(AXIS))
        return jax.device_put(np.asarray(mask, np.int32), sh1)

    def _apply_all(self):
        """The all-shards apply mask, uploaded once and reused by every
        normal fold (it is never donated)."""
        if self._apply_dev is None:
            self._apply_dev = self._put_apply(
                np.ones(self.n_dev, np.int32))
        return self._apply_dev

    def _clear_fn(self):
        if not self.aot:
            return functools.partial(clear_table, mesh=self.mesh)
        from dsi_tpu.backends import aotcache

        name, fn = _clear_program(mesh=self.mesh, n_dev=self.n_dev,
                                  cap=self.cap, kk=self.kk)
        with _quiet_unusable_donation():
            return aotcache.cached_compile(
                name, fn, _table_structs(self.n_dev, self.cap, self.kk),
                donate_argnums=_TABLE_DONATE, x64=True)

    def _pack_fn(self, mp: int):
        if not self.aot:
            return functools.partial(_pack_prefix, mp=mp)
        from dsi_tpu.backends import aotcache

        name, fn = _pack_program(n_dev=self.n_dev, cap=self.cap, kk=self.kk,
                                 mp=mp)
        t = _table_structs(self.n_dev, self.cap, self.kk)
        return aotcache.cached_compile(name, fn, (t[0], t[1], t[3], t[2]),
                                       x64=True)

    # ── the fold path ──

    def fold(self, packed_dev, scal_dev, scal_np: np.ndarray) -> None:
        """Dispatch one confirmed step's fold (async, no blocking) and
        lazily confirm folds older than ``lag``.  ``packed_dev`` is the
        step's full-capacity packed reduce output ``[n_dev, rows, kk+3]``
        (``shuffle._slice_pack`` layout); ``scal_np`` is the already
        host-checked scalar block (the caller's exactness confirmation —
        the fold LAGS that window by construction, because only callers
        holding a confirmed step reach here)."""
        step_kk = int(packed_dev.shape[2]) - 3
        if step_kk != self.kk:
            # The word window widened mid-stream (e.g. 16 -> 64 bytes):
            # the table's key lanes can no longer represent this step's
            # words.  Re-key via the widen protocol: drain what we have,
            # reallocate at the new width, resume folding.
            self._rekey(step_kk, int(packed_dev.shape[1]))
        if self._delta_max:
            # Record the step's appended rows for the next delta save —
            # exactly once per confirmed step (recovery re-folds go
            # through _dispatch_fold and never re-enter here).  A log
            # outgrowing its cap invalidates THIS window only: the next
            # save falls back to a full image and re-arms the log —
            # and an already-invalid window retains nothing (take_delta
            # would discard it anyway; don't pin dead HBM).
            if self._delta_invalid:
                pass
            elif len(self._delta_log) >= self._delta_max:
                self._delta_invalid = True
                self._delta_log.clear()
            else:
                self._delta_log.append(
                    (packed_dev, scal_np[:, 0].astype(np.int64).copy()))
        with _span("fold", lane="shuffle" if self.mesh_shards else "fold",
                   stats=self.stats, key="fold_s",
                   fold=self.stats["folds"]):
            out = self._dispatch_fold(packed_dev, scal_dev)
            self._pending.append((out, packed_dev, scal_dev))
            self.stats["folds"] += 1
            while len(self._pending) > self.lag:
                self._confirm_oldest()

    def _dispatch_fold(self, packed_dev, scal_dev, apply_np=None):
        """Launch one fold (async).  ``apply_np`` restricts a MESH fold
        to the masked shards — the recovery re-fold's lever; normal
        folds apply everywhere."""
        if self.mesh_shards:
            fn = self._mesh_fold_fn(int(packed_dev.shape[1]))
            apply_dev = (self._apply_all() if apply_np is None
                         else self._put_apply(apply_np))
            with _quiet_unusable_donation():
                *state, flags = fn(*self._state, packed_dev, scal_dev,
                                   apply_dev)
        else:
            fn = self._fold_fn(int(packed_dev.shape[1]))
            with _quiet_unusable_donation():
                *state, flags = fn(*self._state, packed_dev, scal_dev)
        self._state = tuple(state)
        _enqueued(flags)
        return flags

    def _note_flags(self, flags_np: np.ndarray) -> None:
        self._nrows = flags_np[:, 1].astype(np.int64)
        if self.mesh_shards:
            occ = self._nrows[:self.mesh_shards]
            tot = int(occ.sum())
            if tot:
                self.stats["shard_imbalance"] = round(
                    float(occ.max()) * self.mesh_shards / tot, 3)

    def _landed(self, *arrs) -> list:
        """``arrs`` on the host: blocked until the device has made them
        (a fold's flags, the packed table), the part of ``fold_s``,
        ``sync_s`` and ``widen_s`` that is the device's."""
        with _span("wait", lane="sync", stats=self.stats,
                   key="sync_wait_s"):
            return [np.asarray(a) for a in arrs]

    def _confirm_oldest(self) -> None:
        flags, packed_dev, scal_dev = self._pending.popleft()
        (flags_np,) = self._landed(flags)  # until this fold lands
        self._note_flags(flags_np)
        if flags_np[:, 0].any():
            self.stats["fold_overflows"] += 1
            self._recover([(packed_dev, scal_dev, flags_np[:, 0] > 0)])

    def _flush_pending(self):
        """Confirm every outstanding fold; return the (packed, scal,
        overflow-mask) triples of folds that no-op'd, oldest first (the
        mask is per-shard in mesh mode, all-shards otherwise)."""
        orphans = []
        while self._pending:
            flags, packed_dev, scal_dev = self._pending.popleft()
            (flags_np,) = self._landed(flags)
            self._note_flags(flags_np)
            if flags_np[:, 0].any():
                self.stats["fold_overflows"] += 1
                orphans.append((packed_dev, scal_dev, flags_np[:, 0] > 0))
        return orphans

    # ── overflow / widen protocol ──

    def _recover(self, orphans) -> None:
        """A fold overflowed (and was therefore a no-op — globally
        without mesh sharding, on the overflowed shards with it).  Later
        folds may already sit in the queue — flush them first (successes
        merged into the old table and drain with it; further overflows
        join the orphan list), then widen and re-fold every orphan."""
        with _span("widen", stats=self.stats, key="widen_s"):
            orphans = list(orphans) + self._flush_pending()
            if self.mesh_shards:
                self._recover_mesh(orphans)
                return
            while orphans:
                rows = max(int(p.shape[1]) for p, _, _ in orphans)
                self._widen(_pow2(max(4 * self.cap, rows)), self.kk)
                still = []
                for packed_dev, scal_dev, _ in orphans:
                    flags_np = np.asarray(
                        self._dispatch_fold(packed_dev, scal_dev))
                    self._note_flags(flags_np)
                    if flags_np[:, 0].any():  # rung still too narrow
                        still.append((packed_dev, scal_dev, None))
                orphans = still

    def _recover_mesh(self, orphans) -> None:
        """Per-shard recovery: only the HOT shards (union of the
        orphans' overflow masks) drain to the host, come back empty in
        the wider allocation, and receive the orphaned steps' re-folds
        — each orphan re-applied ONLY to its failed shards, so the
        shards that committed the first time never double-count.  Cold
        shards are copied on device (``mesh_grow_*``) and never touch
        the wire."""
        while orphans:
            hot = np.zeros(self.n_dev, dtype=bool)
            for _, _, mask in orphans:
                hot |= np.asarray(mask, dtype=bool)
            rows = max(int(p.shape[1]) for p, _, _ in orphans)
            # Stay on the x4 rung ladder the warmer persists (worst-case
            # skew can deliver n_dev * rows rows to one shard, but
            # jumping straight to that bound would reach capacities
            # `warm_device_fold` never compiled — cold remote compiles
            # mid-widen).  The loop re-widens x4 while orphans remain,
            # so termination costs at most log4(n_dev) extra rounds.
            self._widen(_pow2(max(4 * self.cap, rows)), self.kk,
                        keep=~hot)
            hot_list = [int(s) for s in np.flatnonzero(hot)]
            for s in hot_list:
                self.stats["shard_widens"][s] += 1
            _trace_event("shard_widen", lane="shuffle", shards=hot_list,
                         cap=self.cap)
            still = []
            for packed_dev, scal_dev, mask in orphans:
                flags_np = np.asarray(self._dispatch_fold(
                    packed_dev, scal_dev,
                    apply_np=np.asarray(mask, dtype=bool)))
                self._note_flags(flags_np)
                if flags_np[:, 0].any():
                    still.append((packed_dev, scal_dev,
                                  flags_np[:, 0] > 0))
            orphans = still

    def _widen(self, new_cap: int, new_kk: int, keep=None) -> None:
        """Drain into the host accumulator and reallocate at
        ``new_cap``/``new_kk``.  Into an empty table at ``cap >= rows``
        a single step always fits (its uniques are bounded by its row
        count), so the re-fold loop above terminates in one widen per
        distinct rows shape.  With ``keep`` (the per-shard protocol)
        only the dropped shards drain — one single-shard D2H each — and
        kept shards carry over via the compiled grow program."""
        if keep is None or new_kk != self.kk:
            self._pull_merge()
            self.cap, self.kk = new_cap, new_kk
            self._state = self._alloc(self.cap, self.kk)
            self._nrows[:] = 0
        else:
            drain = ~np.asarray(keep, dtype=bool)
            self._pull_merge(only=drain)
            fn = self._grow_fn(new_cap)
            keep_dev = self._put_apply(np.asarray(keep, np.int32))
            with _quiet_unusable_donation():
                self._state = tuple(fn(*self._state, keep_dev))
            _enqueued(self._state[-1])
            self.cap = new_cap
            self._nrows[drain] = 0
        self.stats["widens"] += 1
        self.stats["table_cap"] = self.cap
        _trace_event("table_widen", lane="widen", cap=self.cap,
                     kk=self.kk)

    def _rekey(self, new_kk: int, rows: int) -> None:
        with _span("widen", stats=self.stats, key="widen_s", rekey=True):
            # Outstanding folds still match the OLD width: confirm them
            # first (overflow here recovers at the old width, which is
            # fine — their steps' words provably fit the old window).
            orphans = self._flush_pending()
            if orphans:
                self._recover(orphans)
            self._widen(_pow2(max(self.cap, rows)), new_kk)

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def checkpoint_capture(self):
        """Drain-free snapshot image, capture half: flush the lagged
        flags first (so the image reflects exactly the CONFIRMED folds
        — recovery of a late-detected overflow may widen, whose drain
        lands in ``acc``, which is why callers capture the device
        services BEFORE the host accumulator), then DISPATCH the
        occupied-prefix pack (a fresh buffer: later folds donate the
        live table arrays, never this) and kick its D2H — returning a
        deferred whose ``materialize()`` (in the commit writer, or
        inline for a sync save) reconstructs the five-array image the
        restore path has always consumed.  Rows beyond each device's
        occupancy are pad by the fold invariant, so prefix + pad
        reconstruction is the live image."""
        from dsi_tpu.ckpt.delta import Deferred

        orphans = self._flush_pending()
        if orphans:
            self._recover(orphans)
        n_dev, cap, kk = self.n_dev, self.cap, self.kk
        nrows = self._nrows.copy()
        m = int(nrows.max())
        if m:
            mp = cap if (self.aot and not self.mesh_shards) \
                else occupied_prefix(m, cap)
            tkeys, tlens, tcnts, tparts, _ = self._state
            packed_dev, cnts_dev = self._pack_fn(mp)(tkeys, tlens, tparts,
                                                     tcnts)
            _enqueued(cnts_dev)
            _copy_to_host_async(packed_dev)
            _copy_to_host_async(cnts_dev)
        else:
            packed_dev = cnts_dev = None

        def _image() -> dict:
            keys = np.full((n_dev, cap, kk), _PAD_KEY, np.uint32)
            lens = np.zeros((n_dev, cap), np.int32)
            cnts = np.zeros((n_dev, cap), np.uint64)
            parts = np.zeros((n_dev, cap), np.int32)
            if packed_dev is not None:
                p = np.asarray(packed_dev)
                c = np.asarray(cnts_dev)
                for d in range(n_dev):
                    n = int(nrows[d])
                    if n:
                        keys[d, :n] = p[d, :n, :kk]
                        lens[d, :n] = p[d, :n, kk].astype(np.int32)
                        parts[d, :n] = p[d, :n, kk + 1].astype(np.int32)
                        cnts[d, :n] = c[d, :n]
            return {"keys": keys, "lens": lens, "cnts": cnts,
                    "parts": parts, "tn": nrows.astype(np.int32),
                    "nrows": nrows.copy()}

        return Deferred(_image)

    def checkpoint_state(self) -> dict:
        """The synchronous spelling: capture + immediate materialize —
        what every PR-5 call site (and the sync save path) still
        gets."""
        return self.checkpoint_capture().materialize()

    # ── incremental (delta) checkpoints ──

    def enable_delta(self, max_steps: int = 64) -> None:
        """Arm the delta log: every confirmed fold retains its step
        payload handle until the next ``take_delta``.  ``max_steps``
        bounds the retained HBM (a window past it falls back to a full
        save)."""
        self._delta_max = max(1, int(max_steps))
        self._delta_log.clear()
        self._delta_invalid = False

    def take_delta(self):
        """The rows appended since the last capture, as ordered
        ``(sliced_rows_handle, nus)`` entries with their D2H already
        kicked — or None when this window cannot be expressed as a
        delta (log overflow), which tells the engine to write a full
        image instead.  Always re-arms the log for the next window."""
        if self._delta_invalid:
            self._delta_invalid = False
            self._delta_log.clear()
            return None
        entries = []
        for packed_dev, nus in self._delta_log:
            mp = occupied_prefix(int(nus.max()),
                                 int(packed_dev.shape[1]))
            sliced = _rows_prefix(packed_dev, mp=mp)
            _enqueued(sliced)
            _copy_to_host_async(sliced)
            entries.append((sliced, nus))
        self._delta_log.clear()
        return entries

    def restore_state(self, img: dict) -> None:
        """Re-upload a :meth:`checkpoint_state` image — re-entering
        ``device_accumulate`` mid-table on resume.  Capacity and key
        width follow the image (a widen before the crash is preserved,
        so the resumed stream starts at the rung that had already
        cleared)."""
        keys = np.asarray(img["keys"], dtype=np.uint32)
        self.cap = int(keys.shape[1])
        self.kk = int(keys.shape[2])
        sh3 = NamedSharding(self.mesh, P(AXIS, None, None))
        sh2 = NamedSharding(self.mesh, P(AXIS, None))
        sh1 = NamedSharding(self.mesh, P(AXIS))
        with enable_x64(True):  # keep the u64 counts u64 through the put
            self._state = (
                jax.device_put(keys, sh3),
                jax.device_put(np.asarray(img["lens"], np.int32), sh2),
                jax.device_put(np.asarray(img["cnts"], np.uint64), sh2),
                jax.device_put(np.asarray(img["parts"], np.int32), sh2),
                jax.device_put(np.asarray(img["tn"], np.int32), sh1))
        self._nrows = np.asarray(img["nrows"], dtype=np.int64).copy()
        self._pending.clear()
        self.stats["table_cap"] = self.cap

    # ── drains ──

    def _pull_merge(self, only=None) -> bool:
        """Pull the occupied table prefix and merge it into the host
        accumulator.  Returns True if anything crossed the wire.  With
        ``only`` (a per-shard bool mask — the per-shard widen's drain)
        just the masked shards' slices cross, one addressable-shard
        D2H each.  Mesh mode always pulls the occupied prefix (the
        pre-merged table is hash-balanced, so the prefix tracks
        vocabulary/n_shards); the non-mesh aot path keeps its
        deterministic full-capacity pulls.  ``pull_bytes`` counts the
        actual payload either way."""
        sel = self._nrows if only is None else \
            np.where(np.asarray(only, dtype=bool), self._nrows, 0)
        m = int(sel.max())
        if m == 0:
            return False
        mp = self.cap if (self.aot and not self.mesh_shards) \
            else occupied_prefix(m, self.cap)
        tkeys, tlens, tcnts, tparts, _ = self._state
        packed_dev, cnts_dev = self._pack_fn(mp)(tkeys, tlens, tparts, tcnts)
        if only is None:
            packed, cnts = self._landed(packed_dev, cnts_dev)
            self.stats["pull_bytes"] += packed.nbytes + cnts.nbytes
            for d in range(self.n_dev):
                n = int(self._nrows[d])
                if n == 0:
                    continue
                r = packed[d, :n]
                self.acc.add(r[:, :self.kk], r[:, self.kk],
                             cnts[d, :n].astype(np.int64),
                             r[:, self.kk + 1])
        else:
            for d in np.flatnonzero(np.asarray(only, dtype=bool)):
                d = int(d)
                n = int(self._nrows[d])
                if n == 0:
                    continue
                r = _pull_shard(packed_dev, d)
                c = _pull_shard(cnts_dev, d)
                self.stats["pull_bytes"] += r.nbytes + c.nbytes
                self.acc.add(r[:n, :self.kk], r[:n, self.kk],
                             c[:n].astype(np.int64), r[:n, self.kk + 1])
        return True

    @staticmethod
    def drain_image(acc, img: dict) -> None:
        """Merge a :meth:`checkpoint_state` image into a host
        accumulator WITHOUT re-uploading it — the resume path when the
        checkpoint's sharding degree differs from the live table's
        (``mesh_shards`` recorded in the manifest): the image's merged
        rows re-enter through the drain, the table starts empty at the
        new degree, and the next folds re-shuffle ownership."""
        keys = np.asarray(img["keys"], dtype=np.uint32)
        lens = np.asarray(img["lens"])
        cnts = np.asarray(img["cnts"])
        parts = np.asarray(img["parts"])
        nrows = np.asarray(img["nrows"], dtype=np.int64)
        for d in range(keys.shape[0]):
            n = int(nrows[d])
            if n:
                acc.add(keys[d, :n], lens[d, :n],
                        cnts[d, :n].astype(np.int64), parts[d, :n])

    def sync(self) -> bool:
        """The K-step host pull: flush the fold lag, drain the table
        into the accumulator, reset it to empty ON DEVICE (compiled
        clear, no upload).  Returns True when a pull happened (an empty
        window skips the wire and is not counted)."""
        with _span("sync", stats=self.stats, key="sync_s"):
            orphans = self._flush_pending()
            if orphans:
                self._recover(orphans)
            pulled = self._pull_merge()
            if pulled:
                self.stats["sync_pulls"] += 1
                with _quiet_unusable_donation():
                    self._state = tuple(self._clear_fn()(*self._state))
                _enqueued(self._state[-1])
                self._nrows[:] = 0
        return pulled

    def close(self) -> None:
        """Stream-end drain: flush + final pull, no reset (the table is
        dropped with the service)."""
        with _span("sync", stats=self.stats, key="sync_s", close=True):
            orphans = self._flush_pending()
            if orphans:
                self._recover(orphans)
            if self._pull_merge():
                self.stats["sync_pulls"] += 1
            self._state = None
