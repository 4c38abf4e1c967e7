"""Append-only device-resident postings buffer for the TF-IDF wave walk.

TF-IDF's per-wave output is postings — (word, len, tf, doc, part) rows
that accumulate rather than merge — so the word-count ``DeviceTable``'s
sort+segment-sum fold is the wrong program.  What the wave walk shares
with the stream is the COST SHAPE: one D2H pull per wave, each charged
a fixed per-transfer cost regardless of size.  This buffer batches those pulls: waves append their
valid rows into a persistent on-device buffer with a compiled scatter
(same dump-row idiom as ``shuffle.shuffle_rows``), and the host pulls
once per K waves (``device/policy.py`` cadence) or when the buffer
fills.

Append flags are confirmed ``lag`` appends late (the wave walk passes
its pipeline depth, ``parallel/pipeline.py``): blocking on an append's
tiny flags pull the moment it is dispatched would wait out every wave
kernel queued behind it on the in-order device stream — the
serialization the pipeline window exists to avoid.  Late detection is
safe because overflow is ORDER-PRESERVING: an append that overflows is
a global no-op that also sets a sticky ``dirty`` bit in device state,
so every LATER append no-ops too until the host drains — recovery
drains the committed prefix (strictly the waves before the first
overflow), resets, and re-appends the orphaned waves oldest-first.
Wave order in the per-device row streams is therefore an invariant,
which is what keeps the accumulated postings (``merge.PostingsTable``
preserves insertion order within a word) bit-identical to the per-wave
pull path.

Unlike the merge table the capacity has no standing *ladder*: a drain
empties the buffer, so overflow is normally just an early sync.  The
one exception — a single wave with more valid rows than the whole
buffer (a forced-tiny ``DSI_DEVICE_POSTINGS_CAP``, or a mid-walk
capacity-rung widening) — reallocates the empty buffer at the wave's
row count instead of failing: overflow is an early sync or a widen,
never a loss.
"""

from __future__ import annotations

import collections
import functools
from typing import Callable, Deque, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.obs import enqueued as _enqueued, span as _span
from dsi_tpu.ops.meshroute import compact_received, exchange_rows, route_dest
from dsi_tpu.ops.wordcount import _PAD_KEY
from dsi_tpu.parallel.shuffle import AXIS, occupied_prefix
from dsi_tpu.utils.jaxcompat import shard_map


def _append_device(buf, n, dirty, rows, scal, *, cap: int, width: int):
    """Per-device body: scatter this wave's valid rows at the write
    offset.  Rows beyond the wave's valid count and rows past the
    capacity land on the dump row / out of bounds (dropped — identical
    either way because a no-op'd append keeps the OLD buffer).  The
    ``dirty`` bit is the sticky overflow shadow: once any append
    no-ops, every later append no-ops too, so the committed buffer is
    always an order-exact prefix of the appended waves."""
    buf = buf.reshape(cap, width)
    n0 = n.reshape(())
    d0 = dirty.reshape(())
    r = rows.shape[-2]
    rows = rows.reshape(r, width)
    nr = scal.reshape(-1)[0]

    valid = jnp.arange(r, dtype=jnp.int32) < nr
    idx = jnp.where(valid, n0 + jnp.arange(r, dtype=jnp.int32), cap)
    target = jnp.concatenate([buf, jnp.zeros((1, width), jnp.uint32)], axis=0)
    new_buf = target.at[idx].set(rows)[:cap]
    new_n = n0 + nr
    ov = lax.pmax((new_n > cap).astype(jnp.int32), AXIS)
    # Commit is all-or-nothing across devices (pmax) AND across waves
    # (sticky dirty): a mixed commit would break either the exactly-once
    # guarantee or the wave order of the per-device row streams.
    no_op = jnp.maximum(ov, d0)
    keep_old = no_op > 0
    out_buf = jnp.where(keep_old, buf, new_buf)
    out_n = jnp.where(keep_old, n0, new_n)
    flags = jnp.stack([no_op, out_n])
    return out_buf[None], out_n[None], no_op[None], flags[None]


def _append_impl(buf, n, dirty, rows, scal, *, mesh: Mesh):
    cap, width = buf.shape[1], buf.shape[2]
    body = functools.partial(_append_device, cap=cap, width=width)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS), P(AXIS),
                  P(AXIS, None, None), P(AXIS, None)),
        out_specs=(P(AXIS, None, None), P(AXIS), P(AXIS), P(AXIS, None)),
    )(buf, n, dirty, rows, scal)


_append_step = jax.jit(_append_impl, static_argnames=("mesh",),
                       donate_argnums=(0, 1, 2))


def _mesh_append_device(buf, n, dirty, rows, scal, *, cap: int, width: int,
                        kk: int, n_dev: int, n_shards: int):
    """Mesh-sharded append body: the wave's rows are RE-ROUTED to their
    owning shard (``ihash(word) % n_shards``, ``ops/meshroute.py``)
    before the scatter, so a word's postings always buffer on one shard
    regardless of how ``n_reduce % n_dev`` placed them.  Per-word order
    survives: a word's rows arrive from exactly one source device (the
    step's shuffle already grouped them) and the exchange concatenates
    source blocks in device order.  Overflow stays GLOBAL (pmax +
    sticky dirty) — a postings overflow is an early sync, not a
    capacity ladder, so the per-shard machinery buys nothing here."""
    buf = buf.reshape(cap, width)
    n0 = n.reshape(())
    d0 = dirty.reshape(())
    r = rows.shape[-2]
    rows = rows.reshape(r, width)
    nr = scal.reshape(-1)[0]

    valid = jnp.arange(r, dtype=jnp.int32) < nr
    keys = jnp.where(valid[:, None], rows[:, :kk], jnp.uint32(_PAD_KEY))
    lens = jnp.where(valid, rows[:, kk].astype(jnp.int32), 0)
    dest = route_dest(keys, lens, valid, n_shards=n_shards, park=n_dev)
    recv = exchange_rows(rows, dest, n_dev=n_dev, kk=kk)
    crows, n_recv = compact_received(recv)

    idx = jnp.where(jnp.arange(n_dev * r, dtype=jnp.int32) < n_recv,
                    n0 + jnp.arange(n_dev * r, dtype=jnp.int32), cap)
    target = jnp.concatenate([buf, jnp.zeros((1, width), jnp.uint32)],
                             axis=0)
    new_buf = target.at[idx].set(crows)[:cap]
    new_n = n0 + n_recv
    ov = lax.pmax((new_n > cap).astype(jnp.int32), AXIS)
    no_op = jnp.maximum(ov, d0)
    keep_old = no_op > 0
    out_buf = jnp.where(keep_old, buf, new_buf)
    out_n = jnp.where(keep_old, n0, new_n)
    flags = jnp.stack([no_op, out_n])
    return out_buf[None], out_n[None], no_op[None], flags[None]


def _mesh_append_impl(buf, n, dirty, rows, scal, *, mesh: Mesh, kk: int,
                      n_shards: int):
    cap, width = buf.shape[1], buf.shape[2]
    body = functools.partial(_mesh_append_device, cap=cap, width=width,
                             kk=kk, n_dev=int(mesh.devices.size),
                             n_shards=n_shards)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS), P(AXIS),
                  P(AXIS, None, None), P(AXIS, None)),
        out_specs=(P(AXIS, None, None), P(AXIS), P(AXIS), P(AXIS, None)),
    )(buf, n, dirty, rows, scal)


_mesh_append_step = jax.jit(_mesh_append_impl,
                            static_argnames=("mesh", "kk", "n_shards"),
                            donate_argnums=(0, 1, 2))


# Fresh-buffer prefix slice shared with the table service (one jitted
# program for both consumers).
from dsi_tpu.device.table import _rows_prefix as _buf_prefix  # noqa: E402


class DevicePostings:
    """Persistent ``[n_dev, cap, width]`` uint32 append buffer over the
    mesh.  ``append`` scatters one wave's rows asynchronously; its flags
    are confirmed ``lag`` appends late.  Drains hand each device's
    occupied rows to ``sink`` (one callback per device, wave order
    preserved) — triggered by ``sync`` (the K-wave cadence), ``close``
    (end of walk), or overflow recovery.

    ``stats``, if given, receives ``appends``, ``append_overflows``,
    ``sync_pulls``, ``postings_widens``, ``append_s``, ``drain_s``.

    ``mesh_shards`` > 0 re-routes every appended row to shard
    ``ihash(word) % n_shards`` inside the compiled append (the
    shuffle-fold treatment; ``kk`` names the key-lane count, default
    ``width - 4`` — the (keys, len, payload...) row layout both wave
    walks use).  Buffered postings then shard by KEY rather than by the
    step's partition placement; the drain contract and the sticky
    global overflow protocol are unchanged.
    """

    def __init__(self, mesh: Mesh, *, width: int, cap: int,
                 sink: Callable[[np.ndarray], None],
                 lag: int = 0, stats: Optional[dict] = None,
                 mesh_shards: int = 0, kk: Optional[int] = None):
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.width = int(width)
        self.cap = 1 << max(0, int(cap) - 1).bit_length()
        self.sink = sink
        self.lag = max(0, int(lag))
        self.mesh_shards = max(0, int(mesh_shards))
        self.kk = int(kk) if kk is not None else self.width - 4
        if self.mesh_shards > self.n_dev:
            raise ValueError(
                f"mesh_shards={self.mesh_shards} exceeds the mesh size "
                f"({self.n_dev} devices)")
        self.stats = stats if stats is not None else {}
        for key in ("appends", "append_overflows", "sync_pulls",
                    "postings_widens", "pull_bytes"):
            self.stats.setdefault(key, 0)
        for key in ("append_s", "drain_s"):
            self.stats.setdefault(key, 0.0)
        self._alloc(self.cap)
        self._nrows = np.zeros(self.n_dev, dtype=np.int64)
        # (flags, rows_dev, scal_dev) per unconfirmed append — the wave
        # tensors stay referenced until their append is proven committed,
        # so a no-op'd append can be replayed after the drain.
        self._pending: Deque[Tuple] = collections.deque()
        # Delta-checkpoint log (enable_delta): wave payloads appended
        # since the last capture — wave tensors are never donated, so
        # retaining the handles is safe (same discipline as
        # ``DeviceTable``'s step log).
        self._delta_log: list = []
        self._delta_max = 0
        self._delta_invalid = False

    def _alloc(self, cap: int) -> None:
        sh3 = NamedSharding(self.mesh, P(AXIS, None, None))
        sh1 = NamedSharding(self.mesh, P(AXIS))
        self._buf = jax.device_put(
            np.zeros((self.n_dev, cap, self.width), np.uint32), sh3)
        self._n = jax.device_put(np.zeros((self.n_dev,), np.int32), sh1)
        self._dirty = jax.device_put(np.zeros((self.n_dev,), np.int32), sh1)

    # ── the append path ──

    def _dispatch(self, rows_dev, scal_dev):
        if self.mesh_shards:
            self._buf, self._n, self._dirty, flags = _mesh_append_step(
                self._buf, self._n, self._dirty, rows_dev, scal_dev,
                mesh=self.mesh, kk=self.kk, n_shards=self.mesh_shards)
        else:
            self._buf, self._n, self._dirty, flags = _append_step(
                self._buf, self._n, self._dirty, rows_dev, scal_dev,
                mesh=self.mesh)
        _enqueued(flags)
        return flags

    def append(self, rows_dev, scal_dev, nvalid=None) -> None:
        """Append one wave's valid rows (async) and lazily confirm
        appends older than ``lag``.  ``rows_dev`` is the wave's sorted
        received-row tensor ``[n_dev, r, width]``; ``scal_dev`` the
        per-device scalar block whose column 0 is the valid row count
        (already host-confirmed exact by the caller).  ``nvalid`` is
        that column as host ints — required only when the delta log is
        armed (it is the trim vector an incremental save ships with the
        wave's rows)."""
        if self._delta_max and not self._delta_invalid:
            # An already-invalid window retains nothing — take_delta
            # would discard it anyway; don't pin dead HBM.
            if nvalid is None or len(self._delta_log) >= self._delta_max:
                self._delta_invalid = True
                self._delta_log.clear()
            else:
                self._delta_log.append(
                    (rows_dev, np.asarray(nvalid, np.int64).copy()))
        with _span("append", lane="fold", stats=self.stats,
                   key="append_s"):
            flags = self._dispatch(rows_dev, scal_dev)
            self._pending.append((flags, rows_dev, scal_dev))
            while len(self._pending) > self.lag:
                self._confirm_oldest()

    def _confirm_oldest(self) -> None:
        flags, rows_dev, scal_dev = self._pending.popleft()
        flags_np = np.asarray(flags)  # blocks until this append lands
        if flags_np[:, 0].any():
            self.stats["append_overflows"] += 1
            self._recover([(rows_dev, scal_dev)])
        else:
            self._nrows = flags_np[:, 1].astype(np.int64)
            self.stats["appends"] += 1

    def _flush_pending(self) -> list:
        """Confirm every outstanding append; return the (rows, scal)
        pairs that no-op'd, oldest first."""
        orphans = []
        while self._pending:
            flags, rows_dev, scal_dev = self._pending.popleft()
            flags_np = np.asarray(flags)
            if flags_np[:, 0].any():
                self.stats["append_overflows"] += 1
                orphans.append((rows_dev, scal_dev))
            else:
                self._nrows = flags_np[:, 1].astype(np.int64)
                self.stats["appends"] += 1
        return orphans

    def _recover(self, orphans: list) -> None:
        """An append no-op'd.  Every append dispatched after it no-op'd
        too (the sticky dirty bit), so flushing collects the orphans in
        dispatch order: drain the committed prefix, then re-append the
        orphans oldest-first — wave order in the sink is preserved by
        construction."""
        orphans = orphans + self._flush_pending()
        self._drain()
        for rows_dev, scal_dev in orphans:
            flags_np = np.asarray(self._dispatch(rows_dev, scal_dev))
            if flags_np[:, 0].any():
                # Cumulative overflow mid-recovery (earlier orphans
                # refilled the buffer): drain what fit — in order — and
                # retry into the empty buffer at the CURRENT cap first.
                self._drain()
                flags_np = np.asarray(self._dispatch(rows_dev, scal_dev))
            if flags_np[:, 0].any():
                # Only now is this provably a lone wave larger than the
                # whole empty buffer (forced-tiny cap, or a capacity-rung
                # widening mid-walk): grow the buffer to hold it —
                # overflow widens, it never drops.  _alloc resets the
                # sticky dirty bit along with the rest of the state.
                # Mesh routing can deliver every device's rows of one
                # wave to a single shard, so its bound is n_dev * rows.
                wave_rows = int(rows_dev.shape[-2]) * (
                    self.n_dev if self.mesh_shards else 1)
                new_cap = max(4 * self.cap, wave_rows)
                self.cap = 1 << max(0, new_cap - 1).bit_length()
                self._alloc(self.cap)
                self._nrows[:] = 0
                self.stats["postings_widens"] += 1
                flags_np = np.asarray(self._dispatch(rows_dev, scal_dev))
                if flags_np[:, 0].any():  # cap >= rows: cannot happen
                    raise RuntimeError(
                        "device postings buffer smaller than one wave"
                        f" (cap={self.cap})")
            self._nrows = flags_np[:, 1].astype(np.int64)
            self.stats["appends"] += 1

    @property
    def pending_rows(self) -> int:
        return int(self._nrows.sum())

    # ── checkpoint image (dsi_tpu/ckpt) ──

    def checkpoint_capture(self):
        """Drain-free snapshot, capture half: flush the lagged append
        flags (an overflow recovery drains into the sink, so callers
        capture this buffer BEFORE the host table), then DISPATCH the
        committed-prefix slice (a fresh buffer — later appends donate
        the live buffer, never this) and kick its D2H; ``materialize``
        in the commit writer finds the transfer draining.  After the
        flush the sticky dirty bit is provably clear — a dirty buffer
        is resolved by recovery before this returns — so the image
        needs only rows + counts."""
        from dsi_tpu.ckpt.delta import Deferred

        orphans = self._flush_pending()
        if orphans:
            self._recover(orphans)
        n_dev, width, cap = self.n_dev, self.width, self.cap
        nrows = self._nrows.copy()
        m = int(nrows.max())
        if m:
            buf_dev = _buf_prefix(self._buf, mp=occupied_prefix(m, cap))
            from dsi_tpu.device.table import _copy_to_host_async

            _copy_to_host_async(buf_dev)
        else:
            buf_dev = None

        def _image() -> dict:
            buf = (np.asarray(buf_dev) if buf_dev is not None
                   else np.zeros((n_dev, 0, width), dtype=np.uint32))
            return {"buf": buf, "nrows": nrows.copy(),
                    "cap": np.array(cap, dtype=np.int64)}

        return Deferred(_image)

    def checkpoint_state(self) -> dict:
        """The synchronous spelling: capture + immediate materialize."""
        return self.checkpoint_capture().materialize()

    # ── incremental (delta) checkpoints ──

    def enable_delta(self, max_steps: int = 64) -> None:
        """Arm the delta log (``DeviceTable.enable_delta`` contract):
        every appended wave retains its payload handle until the next
        ``take_delta``; a window past ``max_steps`` falls back to a
        full save."""
        self._delta_max = max(1, int(max_steps))
        self._delta_log.clear()
        self._delta_invalid = False

    def take_delta(self):
        """The waves appended since the last capture, as ordered
        ``(sliced_rows_handle, nvalid)`` entries with their D2H kicked —
        or None when the window cannot be a delta (log overflow, or an
        append without ``nvalid``); always re-arms the log."""
        from dsi_tpu.device.table import _copy_to_host_async

        if self._delta_invalid:
            self._delta_invalid = False
            self._delta_log.clear()
            return None
        entries = []
        for rows_dev, nus in self._delta_log:
            mp = occupied_prefix(max(1, int(nus.max())),
                                 int(rows_dev.shape[1]))
            sliced = _buf_prefix(rows_dev, mp=mp)
            _copy_to_host_async(sliced)
            entries.append((sliced, nus))
        self._delta_log.clear()
        return entries

    @staticmethod
    def drain_image(sink, img: dict) -> None:
        """Feed a :meth:`checkpoint_state` image's committed rows to
        ``sink`` (one ``[n, width]`` block per device, device order)
        WITHOUT re-uploading it — the resume path when the checkpoint's
        sharding degree differs from the live buffer's (``mesh_shards``
        in the manifest): the rows re-enter through the host table and
        the buffer starts empty at the new routing.  Device order is
        per-word order for rows that predate every resumed wave, so the
        append-order invariant survives re-routing."""
        buf = np.asarray(img["buf"])
        nrows = np.asarray(img["nrows"])
        for d in range(buf.shape[0]):
            n = int(nrows[d])
            if n:
                sink(buf[d, :n])

    def restore_state(self, img: dict) -> None:
        """Re-upload a :meth:`checkpoint_state` image (resume):
        reallocate at the image's capacity (a pre-crash widen sticks),
        scatter the committed prefix back, clear the dirty bit."""
        self.cap = int(img["cap"])
        buf = np.asarray(img["buf"], dtype=np.uint32)
        full = np.zeros((self.n_dev, self.cap, self.width), dtype=np.uint32)
        if buf.shape[1]:
            full[:, :buf.shape[1]] = buf
        sh3 = NamedSharding(self.mesh, P(AXIS, None, None))
        sh1 = NamedSharding(self.mesh, P(AXIS))
        nrows = np.asarray(img["nrows"], dtype=np.int64)
        self._buf = jax.device_put(full, sh3)
        self._n = jax.device_put(nrows.astype(np.int32), sh1)
        self._dirty = jax.device_put(np.zeros(self.n_dev, np.int32), sh1)
        self._nrows = nrows.copy()
        self._pending.clear()

    # ── drains ──

    def _drain(self) -> None:
        """Pull every device's committed rows (ONE sliced transfer for
        the whole buffer), hand them to the sink, reset.  The reset
        re-uploads only the two tiny per-device scalars; buffer bytes
        beyond the write offset are never read and can stay stale."""
        with _span("drain", lane="sync", stats=self.stats,
                   key="drain_s"):
            m = int(self._nrows.max())
            if m:
                mp = occupied_prefix(m, self.cap)
                pulled = np.asarray(_buf_prefix(self._buf, mp=mp))
                self.stats["pull_bytes"] += pulled.nbytes
                for d in range(self.n_dev):
                    nr = int(self._nrows[d])
                    if nr:
                        self.sink(pulled[d, :nr])
                self.stats["sync_pulls"] += 1
            sh1 = NamedSharding(self.mesh, P(AXIS))
            self._n = jax.device_put(np.zeros((self.n_dev,), np.int32),
                                     sh1)
            self._dirty = jax.device_put(
                np.zeros((self.n_dev,), np.int32), sh1)
            self._nrows[:] = 0

    def sync(self) -> None:
        """The K-wave host pull: flush the append lag (recovering any
        late-detected overflow), then drain to the sink."""
        orphans = self._flush_pending()
        if orphans:
            self._recover(orphans)
        self._drain()

    def close(self) -> None:
        """End-of-walk drain; the buffer is dropped with the service."""
        self.sync()
        self._buf = None
