"""Device-resident stage relay: the plan layer's inter-stage byte buffer.

A multi-stage plan (``dsi_tpu/plan``) chains engines so that stage N+1's
upload IS stage N's device-resident output.  The unit of that handoff is
a byte stream in the engines' native batch layout — ``[n_dev, cap]``
uint8 rows, zero-padded past the fill point — and this module owns the
two relay flavors the plan driver chooses between:

* :class:`DeviceRelay` — the chained path.  A producing stage appends
  each confirmed step's compacted output (e.g. the grep emit kernel's
  matching-line bytes) WITHOUT pulling it: a compiled per-row pack
  program concatenates the new bytes after the current fill point of a
  device-resident accumulation buffer, sealing a buffer when the next
  append would overflow it and starting the next one from the appended
  chunk itself.  The consuming stage iterates :meth:`batches` and feeds
  the buffers straight into its step program — zero intermediate bytes
  cross the host (``plan_intermediate_bytes`` stays 0) unless a spill
  budget forces the oldest sealed buffers out (the spill-compacted
  fallback for intermediates wider than HBM).
* :class:`HostRelay` — the staged baseline.  Every append pulls the
  compacted bytes to the host (the full host round-trip the plan layer
  exists to remove), and the consumer reads a plain block stream.  Same
  byte content as the device path by construction, which is what makes
  the two modes bit-comparable end to end.

Byte-stream contract (what makes the handoff chunking-safe): producers
append whole newline-terminated lines per device row, so every relay row
boundary falls on a line boundary and the zero tail of a buffer row
terminates any final token — a downstream word-count over the relay sees
exactly the same token multiset as the staged baseline's contiguous
stream, whatever the buffer chunking.

Durability: :meth:`DeviceRelay.capture` pulls a NON-destructive image of
every live buffer (the stage-commit payload — device copies stay
resident for the downstream stage), and :meth:`DeviceRelay.restore`
rebuilds a relay from that image in host mode, which is how a crashed
chain resumes from the last completed stage's commit instead of from
zero.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.obs import count as _count, enqueued as _enqueued, \
    span as _span
from dsi_tpu.parallel.shuffle import AXIS
from dsi_tpu.utils.jaxcompat import shard_map

#: jax.jit donate_argnums for the pack program: the accumulation buffer
#: is rebound to the program's one output, so it is the one buffer that
#: output can alias.  The appended chunk is consumed too (the relay
#: drops its reference), but a second donation of the same shape has no
#: output left to alias and only earns jax's "not usable" warning.
_RELAY_DONATE = (0,)


def _pack_row(acc, off, new):
    """One device's row (the body under ``shard_map``: ``[1, n]``,
    ``[1]``, ``[1, n]``): ``out[i] = acc[i]`` for ``i < off`` else
    ``new[i - off]``.  The appended row moves right by ``off`` as ONE
    window of itself behind ``n`` zeros — a dynamic slice, which the
    TPU runs as a copy; as a ``take_along_axis`` over computed indices
    it stays a gather of ``n`` single bytes there (PERF.md §6, PR 30)."""
    with jax.named_scope("relay_pack"):
        n = acc.shape[1]
        o = off.reshape(()).astype(jnp.int32)
        behind = jnp.concatenate([jnp.zeros((n,), new.dtype), new[0]])
        shifted = lax.dynamic_slice(behind, (n - o,), (n,))
        idx = jnp.arange(n, dtype=jnp.int32)
        return jnp.where(idx < o, acc[0], shifted)[None]


def _relay_pack(mesh: Mesh):
    """The pack program over ``mesh``'s rows: under ``shard_map`` each
    device packs its own row at its own offset, no collectives.  The
    HLO module takes the traced function's name: a device trace shows
    ``jit_relay_pack``."""

    def relay_pack(acc, off, new):
        return shard_map(_pack_row, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS), P(AXIS, None)),
                         out_specs=P(AXIS, None))(acc, off, new)

    return relay_pack


@functools.lru_cache(maxsize=None)
def _pack_jit(mesh: Mesh):
    return jax.jit(_relay_pack(mesh), donate_argnums=_RELAY_DONATE)


def _pack_fn(aot: bool, *, mesh: Mesh, cap: int):
    if not aot:
        return _pack_jit(mesh)
    from dsi_tpu.backends import aotcache
    from dsi_tpu.device.table import _quiet_unusable_donation

    n_dev = int(mesh.devices.size)
    sds = jax.ShapeDtypeStruct
    structs = (sds((n_dev, cap), jnp.uint8), sds((n_dev,), jnp.int32),
               sds((n_dev, cap), jnp.uint8))
    with _quiet_unusable_donation():
        return aotcache.cached_compile(f"plan_pack_d{n_dev}_c{cap}",
                                       _relay_pack(mesh), structs,
                                       donate_argnums=_RELAY_DONATE)


class DeviceRelay:
    """Device-resident inter-stage byte buffer (module docstring).

    ``stats`` is the plan run's metrics scope: ``plan_intermediate_bytes``
    counts bytes that crossed the host on the HANDOFF path (0 here unless
    spilled), ``plan_relay_buffers`` the sealed-buffer count,
    ``plan_spilled_bytes`` the spill volume, and ``relay_appends`` /
    ``relay_seals`` / ``relay_append_s`` / ``relay_spill_s`` what the
    producer's appends cost the host (the ``relay_append`` and
    ``relay_spill`` spans, lane ``plan``).  ``spill_bytes`` bounds
    device residency: when the relay's buffer bytes exceed it, the oldest
    sealed buffers are pulled to the host (counted) until back under.
    """

    def __init__(self, mesh: Mesh, *, cap: int, aot: bool = False,
                 stats: Optional[dict] = None, spill_bytes: int = 0):
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.cap = int(cap)
        self.aot = bool(aot)
        self.stats = stats if stats is not None else {}
        self.stats.setdefault("plan_intermediate_bytes", 0)
        self.stats.setdefault("plan_handoff_bytes", 0)
        self.stats.setdefault("plan_relay_buffers", 0)
        self.stats.setdefault("plan_spilled_bytes", 0)
        self.stats.setdefault("relay_appends", 0)
        self.stats.setdefault("relay_seals", 0)
        self.spill_bytes = max(0, int(spill_bytes))
        self._sh = NamedSharding(mesh, P(AXIS, None))
        self._sh1 = NamedSharding(mesh, P(AXIS))
        #: Sealed buffers in append order: jax.Array (device-resident)
        #: or np.ndarray (spilled / restored), each with its fill lens.
        self._sealed: List = []
        self._sealed_lens: List[np.ndarray] = []
        self._acc = None
        self._lens = np.zeros(self.n_dev, dtype=np.int64)
        #: Total content bytes appended (the logical intermediate size).
        self.total_bytes = 0

    # ── producer side ──

    def append(self, comp_dev, kept: np.ndarray) -> None:
        """Append one confirmed step's compacted ``[n_dev, cap]`` output
        (fill ``kept[r]`` bytes per row, zero tail).  ``comp_dev`` is
        consumed (handed to the pack program or adopted as the next
        accumulation buffer) — the producer must not reuse it."""
        kept = np.asarray(kept, dtype=np.int64)
        content = int(kept.sum())
        self.stats["relay_appends"] += 1
        _count("relay_appends")
        with _span("relay_append", lane="plan", stats=self.stats,
                   bytes=content) as sp:
            sp.set(sealed=self._append(comp_dev, kept, content)
                   if content else 0)

    def _append(self, comp_dev, kept: np.ndarray, content: int) -> int:
        """The append proper; returns the buffers it sealed (0 or 1)."""
        self.total_bytes += content
        self.stats["plan_handoff_bytes"] += content
        sealed = 0
        if self._acc is None:
            self._acc = comp_dev
            self._lens = kept.copy()
        elif bool(((self._lens + kept) > self.cap).any()):
            self._seal()
            sealed = 1
            self._acc = comp_dev
            self._lens = kept.copy()
        else:
            off = jax.device_put(self._lens.astype(np.int32), self._sh1)
            fn = _pack_fn(self.aot, mesh=self.mesh, cap=self.cap)
            self._acc = fn(self._acc, off, comp_dev)
            _enqueued(self._acc)
            self._lens += kept
        self._maybe_spill()
        return sealed

    def _seal(self) -> None:
        self._sealed.append(self._acc)
        self._sealed_lens.append(self._lens.copy())
        self._acc = None
        self.stats["plan_relay_buffers"] += 1
        self.stats["relay_seals"] += 1
        _count("relay_seals")

    def _maybe_spill(self) -> None:
        if not self.spill_bytes:
            return
        buf_bytes = self.n_dev * self.cap

        def resident() -> int:
            live = sum(1 for b in self._sealed
                       if not isinstance(b, np.ndarray))
            return (live + (1 if self._acc is not None else 0)) * buf_bytes

        i = 0
        while resident() > self.spill_bytes and i < len(self._sealed):
            if not isinstance(self._sealed[i], np.ndarray):
                content = int(self._sealed_lens[i].sum())
                with _span("relay_spill", lane="plan", stats=self.stats,
                           bytes=content):
                    self._sealed[i] = np.asarray(self._sealed[i])
                self.stats["plan_spilled_bytes"] += content
                self.stats["plan_intermediate_bytes"] += content
            i += 1

    # ── consumer side ──

    def batches(self) -> Iterator:
        """Yield every buffer (sealed first, then the open tail) in
        append order, dropping the relay's own reference as each is
        handed over — the downstream stage owns (and may donate) it.
        Host-resident buffers (spills, restores) yield as np.ndarray;
        the consumer's upload of those is the counted fallback path."""
        if self._acc is not None:
            self._seal()
        while self._sealed:
            yield self._sealed.pop(0)
            self._sealed_lens.pop(0)

    def take_sealed(self) -> List:
        """Pop the currently SEALED buffers (append order) WITHOUT
        sealing the open accumulation buffer — the pipelined driver's
        seal-driven handoff: the consumer takes these while the
        producer keeps appending into the open tail.  Call
        :meth:`finish` then take once more when the producer is done."""
        out: List = []
        while self._sealed:
            out.append(self._sealed.pop(0))
            self._sealed_lens.pop(0)
        return out

    def finish(self) -> None:
        """Seal the open tail: the producer has appended its last byte,
        so the final partial buffer becomes consumable."""
        if self._acc is not None:
            self._seal()

    def host_blocks(self) -> Iterator[bytes]:
        """Destructively materialize every buffer as per-row byte
        blocks — the counted host-fallback consumption path for a
        downstream engine with no device-batch input mode (the
        grep→grep cascade).  Rows hold whole newline-terminated lines,
        so the blocks are a valid line stream in any order; the pull
        is charged to ``plan_intermediate_bytes`` like any other
        host-crossing handoff."""
        if self._acc is not None:
            self._seal()
        while self._sealed:
            buf = self._sealed.pop(0)
            lens = self._sealed_lens.pop(0)
            host = np.asarray(buf)
            self.stats["plan_intermediate_bytes"] += int(lens.sum())
            for r in range(host.shape[0]):
                k = int(lens[r])
                if k:
                    yield host[r, :k].tobytes()

    # ── durability (the stage-commit payload) ──

    def capture(self) -> Dict[str, np.ndarray]:
        """NON-destructive host image of every live buffer: the stage
        commit's payload.  Device copies stay resident — the downstream
        stage still consumes them directly; these pulls are durability
        cost (``plan_commit_bytes``), not handoff bytes."""
        arrays: Dict[str, np.ndarray] = {}
        bufs = list(self._sealed) + (
            [self._acc] if self._acc is not None else [])
        lens = list(self._sealed_lens) + (
            [self._lens] if self._acc is not None else [])
        for i, (b, ln) in enumerate(zip(bufs, lens)):
            arrays[f"rbuf{i}"] = np.asarray(b)
            arrays[f"rlen{i}"] = np.asarray(ln, dtype=np.int64)
        arrays["rcount"] = np.array([len(bufs)], dtype=np.int64)
        return arrays

    @classmethod
    def restore(cls, mesh: Mesh, arrays: Dict[str, np.ndarray], *,
                cap: int, stats: Optional[dict] = None) -> "DeviceRelay":
        """Rebuild a relay from a :meth:`capture` image, host-resident
        (the consumer re-uploads — the resume path's restaging cost,
        counted under ``plan_restored_bytes``)."""
        relay = cls(mesh, cap=cap, stats=stats)
        relay.stats.setdefault("plan_restored_bytes", 0)
        n = int(arrays.get("rcount", np.zeros(1))[0])
        for i in range(n):
            relay._sealed.append(np.asarray(arrays[f"rbuf{i}"],
                                            dtype=np.uint8))
            ln = np.asarray(arrays[f"rlen{i}"], dtype=np.int64)
            relay._sealed_lens.append(ln)
            relay.total_bytes += int(ln.sum())
            relay.stats["plan_restored_bytes"] += int(ln.sum())
        relay.stats["plan_relay_buffers"] += n
        return relay


class HostRelay:
    """The staged-baseline handoff: every append pulls the compacted
    bytes to the host; the consumer reads one contiguous block stream —
    the full host round-trip between stages, byte-identical content to
    :class:`DeviceRelay`'s by construction."""

    def __init__(self, stats: Optional[dict] = None):
        self.stats = stats if stats is not None else {}
        self.stats.setdefault("plan_intermediate_bytes", 0)
        self.stats.setdefault("plan_handoff_bytes", 0)
        self.stats.setdefault("relay_appends", 0)
        self._chunks: List[bytes] = []
        self.total_bytes = 0

    def append(self, comp_dev, kept: np.ndarray) -> None:
        kept = np.asarray(kept, dtype=np.int64)
        content = int(kept.sum())
        self.stats["relay_appends"] += 1
        _count("relay_appends")
        with _span("relay_append", lane="plan", stats=self.stats,
                   bytes=content, sealed=0):
            comp_np = np.asarray(comp_dev)
            for r in range(comp_np.shape[0]):
                k = int(kept[r])
                if k:
                    self._chunks.append(comp_np[r, :k].tobytes())
        self.total_bytes += content
        self.stats["plan_handoff_bytes"] += content
        self.stats["plan_intermediate_bytes"] += content

    def blocks(self) -> Iterator[bytes]:
        yield from self._chunks

    def capture(self) -> Dict[str, np.ndarray]:
        """Stage-commit payload: the materialized stream as one array."""
        joined = b"".join(self._chunks)
        return {"hbytes": np.frombuffer(joined, dtype=np.uint8).copy()}

    @classmethod
    def restore(cls, arrays: Dict[str, np.ndarray],
                stats: Optional[dict] = None) -> "HostRelay":
        relay = cls(stats=stats)
        raw = np.asarray(arrays.get("hbytes", np.zeros(0, np.uint8)),
                         dtype=np.uint8).tobytes()
        if raw:
            relay._chunks.append(raw)
            relay.total_bytes = len(raw)
        return relay
