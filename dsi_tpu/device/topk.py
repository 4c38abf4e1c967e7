"""On-device top-k / histogram service for grep & indexer workloads.

The streaming grep and indexer engines (``parallel/grepstream.py``)
produce per-step *statistics* — per-line match-occurrence counts, and
per-word posting (document-frequency) increments — whose host merge is
tiny but whose per-step D2H pull carries a fixed transfer
cost every single step, exactly the cost shape ``DeviceTable`` solved
for the word-count stream.  This module grows the ROADMAP's named next
consumer on the same fold machinery:

* :class:`DeviceTopK` — a persistent donated (key, count) table with one
  compiled merge program per confirmed step, built directly ON
  :class:`~dsi_tpu.device.table.DeviceTable`: folds lag the engines'
  deferred-exactness window (``lag`` = pipeline depth), a fold whose
  merged uniques overflow the capacity rung is a global no-op recovered
  by the drain→realloc×4→re-fold orphan protocol, and counts are uint64
  (cross-step sums outlive uint32 long before a stream ends).  What the
  subclass changes is the SYNC shape: instead of drain+clear, a sync
  pulls a compiled count-sorted **top-k snapshot** — ``k`` rows over the
  wire, not capacity — leaving the table resident so the final
  ``close()`` drain (into the host accumulator) stays exact.  The engine
  therefore reports the current leaders every K folds for the price of
  k rows, and host *data* pulls drop from one-per-step to
  ``widens + 1`` (the close), with ``ceil(folds/K)`` snapshot pulls on
  top — the amortization ``step_pulls`` vs ``sync_pulls``/``widens``/
  ``topk_snapshots`` makes visible.
* :class:`DeviceHistogram` — a persistent uint64 slot vector (per-line
  match-count buckets plus running totals) folded with one compiled
  donated add per confirmed step.  Addition cannot overflow a rung
  (slots are static, counts uint64), so there is no widen path and no
  flags to confirm — the degenerate, always-exact end of the fold
  machinery.  Syncs pull the tiny vector without clearing (running
  totals stay device-resident); ``close`` returns the final totals.
* :class:`KeyCounts` — the host accumulator for DeviceTable drains whose
  keys are opaque u64 identities (grep's global line numbers) rather
  than word spellings; ``PackedCounts`` keeps serving the word-keyed
  tables (the indexer's document-frequency drain).

Exactness contract, same as every service here: the engines' results are
bit-identical to their depth=1 host-merge paths because folds consume
exactly the confirmed per-step tensors the host merge would, widen
drains never drop keys, and the final close drain hands the host the
complete remainder.  Snapshots are observability only — they are never
an input to the result.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.device.table import (
    DeviceTable,
    _clear_program,
    _fold_program,
    _pow2,
    _quiet_unusable_donation,
    _step_structs,
    _table_structs,
)
from dsi_tpu.obs import enqueued as _enqueued, span as _span
from dsi_tpu.parallel.shuffle import AXIS
from dsi_tpu.utils.jaxcompat import enable_x64, x64_scoped


class KeyCounts:
    """Host accumulator for drains whose kk=2 key lanes encode one opaque
    uint64 identity (hi, lo) — e.g. grep's global line numbers.  Mirrors
    the slice of the ``PackedCounts`` interface ``DeviceTable._pull_merge``
    drives (``add(keys, lens, cnts, parts)``); lens/parts are carried by
    the wire format but meaningless for opaque keys and ignored."""

    def __init__(self):
        self._counts: Dict[int, int] = {}

    def add(self, keys: np.ndarray, lens, cnts, parts) -> None:
        k = np.asarray(keys, dtype=np.uint64)
        key64 = (k[:, 0] << np.uint64(32)) | k[:, 1]
        for key, c in zip(key64.tolist(), np.asarray(cnts).tolist()):
            self._counts[key] = self._counts.get(key, 0) + int(c)

    def finalize(self) -> Dict[int, int]:
        return dict(self._counts)

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def snapshot(self) -> Dict[str, np.ndarray]:
        if not self._counts:
            return {}
        n = len(self._counts)
        return {"keys": np.fromiter(self._counts.keys(), dtype=np.uint64,
                                    count=n),
                "cnts": np.fromiter(self._counts.values(), dtype=np.int64,
                                    count=n)}

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        self._counts = {}
        if not arrays or "keys" not in arrays:
            return
        for k, c in zip(np.asarray(arrays["keys"], np.uint64).tolist(),
                        np.asarray(arrays["cnts"], np.int64).tolist()):
            self._counts[int(k)] = int(c)


def _topk_impl(tkeys, tlens, tcnts, *, k: int):
    """Count-descending top-``k`` slice of each device's table shard:
    sort along the capacity dimension by bitwise-NOT count (uint64
    descending as an ascending sort; empty rows carry count 0 → ~0 =
    u64-max → they sort last) with the key lanes as ascending
    tie-breakers, then take the first k rows.  Per-row sort along dim 1
    needs no cross-device communication, so the sharded table sorts in
    place."""
    kk = tkeys.shape[2]
    with enable_x64(True):
        neg = ~tcnts
        ops = (neg,) + tuple(tkeys[:, :, j] for j in range(kk)) + (tlens,)
        s = lax.sort(ops, dimension=1, num_keys=1 + kk)
        scnts = ~s[0][:, :k]
    skeys = jnp.stack([s[1 + j][:, :k] for j in range(kk)], axis=2)
    slens = s[1 + kk][:, :k]
    return skeys, slens, scnts


def _topk_program(*, n_dev: int, cap: int, kk: int, k: int):
    def fn(tkeys, tlens, tcnts):
        return _topk_impl(tkeys, tlens, tcnts, k=k)

    return f"topk_pack_d{n_dev}_c{cap}_k{kk}_t{k}", fn


_topk_jit = x64_scoped(jax.jit(_topk_impl, static_argnames=("k",)))


class DeviceTopK(DeviceTable):
    """Persistent on-device (key, count) table with count-sorted top-k
    snapshot syncs.

    Everything about folding, lagged confirmation, overflow recovery and
    the final drain is inherited verbatim from :class:`DeviceTable`; the
    one behavioral change is :meth:`sync`, which pulls the k heaviest
    rows (``snapshot``) instead of draining — the table stays resident
    so cross-window counts keep summing on device and the ``close()``
    drain remains the single exact hand-off to the host accumulator.

    Counting contract: ``topk_snapshots`` counts snapshot pulls (k rows
    each); ``sync_pulls`` counts DATA drains only (the close, inherited)
    and ``widens`` the recovery drains — so an engine's host pulls are
    ``topk_snapshots + widens + 1`` against ``steps`` on the per-step
    path.

    ``mesh_shards`` is inherited whole from :class:`DeviceTable`: folds
    become the shuffle-fold (keys — opaque line identities or word
    spellings alike — route to ``ihash(key bytes) % n_shards``), widens
    go per-shard.  The snapshot stays per-shard top-k + host merge of
    ``n_dev * k`` rows: a global winner is necessarily in its OWNING
    shard's top-k under the same order, so the pruning stays exact.
    """

    def __init__(self, mesh: Mesh, *, kk: int, cap: int, k: int, acc,
                 aot: bool = False, lag: int = 1,
                 stats: Optional[dict] = None, mesh_shards: int = 0):
        super().__init__(mesh, kk=kk, cap=cap, acc=acc, aot=aot, lag=lag,
                         stats=stats, mesh_shards=mesh_shards)
        self.k = int(k)
        self.stats.setdefault("topk_snapshots", 0)
        #: Last snapshot: ((count, key_lanes_tuple, len), ...) count
        #: desc, key asc — observability only, never a result input.
        self.snapshot: Tuple = ()

    def _topk_fn(self):
        if not self.aot:
            return functools.partial(_topk_jit, k=self.k)
        from dsi_tpu.backends import aotcache

        name, fn = _topk_program(n_dev=self.n_dev, cap=self.cap,
                                 kk=self.kk, k=self.k)
        t = _table_structs(self.n_dev, self.cap, self.kk)
        return aotcache.cached_compile(name, fn, (t[0], t[1], t[2]),
                                       x64=True)

    def sync(self) -> bool:
        """The K-fold snapshot pull: flush the fold lag (recovering any
        late-detected overflow), then pull the top-k rows — no drain, no
        clear.  Returns True when a snapshot crossed the wire (an empty
        table skips it)."""
        with _span("sync", stats=self.stats, key="sync_s",
                   snapshot=True):
            orphans = self._flush_pending()
            if orphans:
                self._recover(orphans)
            pulled = False
            if int(self._nrows.max()):
                tkeys, tlens, tcnts, _, _ = self._state
                skeys, slens, scnts = self._topk_fn()(tkeys, tlens, tcnts)
                keys_np = np.asarray(skeys)
                lens_np = np.asarray(slens)
                cnts_np = np.asarray(scnts)
                rows: List[Tuple] = []
                for d in range(self.n_dev):
                    # Rows past this shard's occupancy sorted last with
                    # count 0 (pad) — drop them by count, not by
                    # position, so a shard with < k rows contributes
                    # exactly its own.
                    for i in range(min(self.k, int(self._nrows[d]))):
                        c = int(cnts_np[d, i])
                        if c <= 0:
                            break
                        rows.append((c, tuple(keys_np[d, i].tolist()),
                                     int(lens_np[d, i])))
                rows.sort(key=lambda r: (-r[0], r[1]))
                self.snapshot = tuple(rows[:self.k])
                self.stats["topk_snapshots"] += 1
                pulled = True
        return pulled


def warm_topk_service(mesh: Mesh, *, kk: int, rows: int, cap: int, k: int,
                      table_rungs: int = 2, mesh_shards: int = 0) -> None:
    """Compile + persist the fold/clear/pack/snapshot shapes a
    :class:`DeviceTopK` reaches at this per-fold ``rows`` shape: the
    given capacity rung plus ``table_rungs - 1`` ×4 widenings, from
    shape structs alone — same discipline as ``table.warm_device_fold``
    (which also owns the ``mesh_fold_*``/``mesh_grow_*`` variants the
    ``mesh_shards`` flag switches to)."""
    from dsi_tpu.backends import aotcache
    from dsi_tpu.device.table import (_warm_mesh_fold_rung,
                                      _warm_pack_shapes)

    n_dev = mesh.devices.size
    cap = _pow2(cap)
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
                                        donate_argnums=(0, 1, 2, 3, 4),
                                        x64=True)
        name, fn = _clear_program(mesh=mesh, n_dev=n_dev, cap=cap, kk=kk)
        with _quiet_unusable_donation():
            aotcache.cached_compile(name, fn, table,
                                    donate_argnums=(0, 1, 2, 3, 4),
                                    x64=True)
        _warm_pack_shapes(n_dev=n_dev, cap=cap, kk=kk,
                          mesh_shards=mesh_shards)
        name, fn = _topk_program(n_dev=n_dev, cap=cap, kk=kk, k=k)
        aotcache.cached_compile(name, fn, (table[0], table[1], table[2]),
                                x64=True)
        cap *= 4


# ── histogram ──────────────────────────────────────────────────────────


def _hist_fold_impl(state, step):
    with enable_x64(True):
        return state + step.astype(jnp.uint64)


_hist_fold_jit = x64_scoped(jax.jit(_hist_fold_impl, donate_argnums=(0,)))


def _hist_program(*, n_dev: int, slots: int):
    def fn(state, step):
        return _hist_fold_impl(state, step)

    return f"topk_hist_fold_d{n_dev}_s{slots}", fn


def _hist_premerge_impl(state):
    """Cross-shard reduction ON DEVICE: the mesh-sharded pull sums the
    per-device slot vectors over the mesh (one all-reduce) so the host
    pulls ONE pre-merged ``[slots]`` vector instead of N partials —
    1/n_dev the bytes, zero host merge."""
    with enable_x64(True):
        return jnp.sum(state, axis=0, dtype=jnp.uint64)


_hist_premerge_jit = x64_scoped(jax.jit(_hist_premerge_impl))


def _hist_premerge_program(*, n_dev: int, slots: int):
    def fn(state):
        return _hist_premerge_impl(state)

    return f"mesh_hist_pull_d{n_dev}_s{slots}", fn


def _hist_structs(n_dev: int, slots: int):
    sds = jax.ShapeDtypeStruct
    return (sds((n_dev, slots), jnp.uint64), sds((n_dev, slots), jnp.uint32))


class DeviceHistogram:
    """Persistent ``[n_dev, slots]`` uint64 accumulation vector over the
    mesh, folded with one compiled donated add per confirmed step.  The
    engines use the slots for per-line match-count buckets plus running
    totals (lines/matched/occurrences ride the same vector, so one fold
    program and one pull cover all the stream's scalars).

    No flags, no lag, no widen: a uint64 add cannot overflow a rung and
    cannot fail, so confirmation is trivially the dispatch itself — the
    degenerate end of the fold machinery, by design.

    ``pull()`` returns the running totals summed over devices without
    clearing; ``close()`` is the final pull.  ``stats`` receives
    ``hist_folds``/``hist_pulls``/``hist_s``/``pull_bytes``.

    ``mesh_shards`` > 0 pre-merges the pull ON DEVICE (one all-reduce
    over the mesh): the host receives a single ``[slots]`` vector
    instead of the ``[n_dev, slots]`` partials it used to sum itself —
    the literal N-partial-tables → one-pre-merged-table reduction,
    visible in ``pull_bytes``.
    """

    def __init__(self, mesh: Mesh, *, slots: int, aot: bool = False,
                 stats: Optional[dict] = None, mesh_shards: int = 0):
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.slots = int(slots)
        self.aot = bool(aot)
        self.mesh_shards = max(0, int(mesh_shards))
        self.stats = stats if stats is not None else {}
        for key in ("hist_folds", "hist_pulls", "pull_bytes"):
            self.stats.setdefault(key, 0)
        self.stats.setdefault("hist_s", 0.0)
        if self.mesh_shards:
            self.stats.setdefault("mesh_shards", self.mesh_shards)
        sh = NamedSharding(mesh, P(AXIS, None))
        with enable_x64(True):
            self._state = jax.device_put(
                np.zeros((self.n_dev, self.slots), np.uint64), sh)

    def _fold_fn(self):
        if not self.aot:
            return _hist_fold_jit
        from dsi_tpu.backends import aotcache

        name, fn = _hist_program(n_dev=self.n_dev, slots=self.slots)
        with _quiet_unusable_donation():
            return aotcache.cached_compile(
                name, fn, _hist_structs(self.n_dev, self.slots),
                donate_argnums=(0,), x64=True)

    def fold(self, step_dev) -> None:
        """Add one confirmed step's ``[n_dev, slots]`` uint32 vector into
        the running totals (async, donated state)."""
        with _span("hist_fold", lane="fold", stats=self.stats,
                   key="hist_s"):
            with _quiet_unusable_donation():
                self._state = self._fold_fn()(self._state, step_dev)
            _enqueued(self._state)
            self.stats["hist_folds"] += 1

    def _premerge_fn(self):
        if not self.aot:
            return _hist_premerge_jit
        from dsi_tpu.backends import aotcache

        name, fn = _hist_premerge_program(n_dev=self.n_dev,
                                          slots=self.slots)
        return aotcache.cached_compile(
            name, fn, (_hist_structs(self.n_dev, self.slots)[0],),
            x64=True)

    def pull(self) -> np.ndarray:
        """Running totals summed over devices — ``[slots]`` int64.  No
        clear: the vector keeps accumulating on device.  Mesh-sharded
        mode sums on device first and pulls one pre-merged vector
        (lane: the shuffle is the merge)."""
        with _span("hist_pull", lane="sync", stats=self.stats,
                   key="hist_s"):
            if self.mesh_shards:
                merged = np.asarray(self._premerge_fn()(self._state))
                self.stats["pull_bytes"] += merged.nbytes
                out = merged.astype(np.int64)
            else:
                full = np.asarray(self._state)
                self.stats["pull_bytes"] += full.nbytes
                out = full.astype(np.int64).sum(axis=0)
            self.stats["hist_pulls"] += 1
        return out

    def close(self) -> np.ndarray:
        out = self.pull()
        self._state = None
        return out

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def checkpoint_state(self) -> dict:
        """Drain-free image of the running totals.  A histogram fold is
        a donated add with no flags, so the last dispatched fold IS
        confirmed the moment the pull lands — no lag to flush.  The
        pull is synchronous even under an async capture: the vector is
        KBs, and the live state is donated to the very next fold, so a
        deferred read could find the buffer gone."""
        return {"hist": np.asarray(self._state)}

    def checkpoint_capture(self):
        """Capture-API spelling (``ckpt/writer.py`` parts): the tiny
        vector is pulled eagerly, so the deferred is already ready."""
        from dsi_tpu.ckpt.delta import Deferred

        img = self.checkpoint_state()
        return Deferred(lambda: img)

    def restore_state(self, img: dict) -> None:
        sh = NamedSharding(self.mesh, P(AXIS, None))
        with enable_x64(True):  # keep the u64 totals u64 through the put
            self._state = jax.device_put(
                np.asarray(img["hist"], np.uint64), sh)


def warm_histogram(mesh: Mesh, *, slots: int, mesh_shards: int = 0) -> None:
    """Compile + persist the histogram fold at this slot count (plus,
    with ``mesh_shards``, the pre-merged ``mesh_hist_pull_*`` pull)."""
    from dsi_tpu.backends import aotcache

    name, fn = _hist_program(n_dev=mesh.devices.size, slots=slots)
    with _quiet_unusable_donation():
        aotcache.cached_compile(name, fn,
                                _hist_structs(mesh.devices.size, slots),
                                donate_argnums=(0,), x64=True)
    if mesh_shards:
        name, fn = _hist_premerge_program(n_dev=mesh.devices.size,
                                          slots=slots)
        aotcache.cached_compile(
            name, fn, (_hist_structs(mesh.devices.size, slots)[0],),
            x64=True)
