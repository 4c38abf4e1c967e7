"""SPMD TF-IDF: per-document device map + all_to_all shuffle, host scoring.

The multi-chip composition BASELINE.json's last config calls for.  Documents
are processed in waves of ``n_dev`` (one document per device per wave):

* map   = per-device ``tokenize_group_core`` over its document — the same
  fused kernel as word count, but each unique word row carries the document
  id and in-document count (tf) as payload lanes,
* shuffle = ``jax.lax.all_to_all`` routes every (word, doc, tf) row to the
  device owning the word's reduce partition (``ihash % n_reduce % n_dev``,
  bit-identical to ``mr/worker.go:33-37,76``), replacing the reference's
  ``mr-X-Y`` intermediate files exactly as in ``parallel/shuffle.py``,
* reduce = per-device sort of received rows by word; the host buffers each
  wave's rows as raw uint32 tables (``parallel/merge.py`` PostingsTable),
  groups them once at the end (a stable merge of the runs the waves' rows
  arrive in, each source device's block in word order) + one bulk
  spelling decode, and computes ``df``/``tf·ln(N/df)`` at output time via
  the SAME ``apps.tfidf.format_value`` the host Reduce uses — so the SPMD
  job's ``mr-out-*`` files are byte-identical to the sequential oracle's.

Cross-wave state is a host dict, NOT device memory: a wave's device
footprint is bounded by (n_dev x that wave's longest document) regardless of
corpus size, which is what lets the same program scale to the 10 GB config
by adding waves.  Documents are processed longest-first so each wave's
chunk is padded to its OWN longest document's power of two — one 100 MB
outlier in a corpus of 1 MB documents costs one big wave, not big buffers
for every wave — and the power-of-two ladder bounds distinct compiled
shapes to log2(longest/shortest), not n_waves.

Host-memory story, stated honestly: the accumulator holds every posting as
a ~(4·kk+16)-byte uint32 row — O(total postings), the same asymptotic
footprint as the reference's reduce-side in-memory group
(``mr/worker.go:110-124`` holds every record of a partition at once), but
across ALL partitions and several times denser than the Python tuple lists
it replaced.  At the 10 GB config (~1e8 postings x 32 B) this needs GBs of
host RAM; the scale-out lever is implemented: pass
``tfidf_sharded(..., partitions={...})`` to accumulate only a slice of the
reduce partitions (the partition id is already on every row), dividing the
accumulator by the number of slices without touching device code — the
slices' union is exactly the full result.  Device memory is unaffected
either way.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.ckpt import (
    CheckpointPolicy,
    CheckpointStore,
    CheckpointWriter,
    DeltaSteps,
    HostDeltaLog,
    checkpoint_async_default,
    checkpoint_delta_default,
    drain_posting_steps,
    fault_point,
)
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.utils.jaxcompat import (enable_x64, x64_scoped,
                                     shard_map as _shard_map)

from dsi_tpu.ops.wordcount import (
    _PAD_KEY64,
    pack_key_lanes,
    rung0_cap,
    unpack_key_lanes,
)
from dsi_tpu.parallel.merge import PostingsTable
from dsi_tpu.parallel.pipeline import (StepPipeline, fold_source_stats,
                                       pipeline_depth)
from dsi_tpu.parallel.stepobj import EngineStep as _EngineStep
from dsi_tpu.parallel.shuffle import (
    AXIS,
    default_mesh,
    map_prologue,
    occupied_prefix,
    shuffle_rows,
)


def _tfidf_device_step(chunk: jax.Array, doc_id: jax.Array, *, n_dev: int,
                       n_reduce: int, max_word_len: int, u_cap: int,
                       t_cap_frac: int):
    """Per-device wave body: map its document, all_to_all, sort received."""
    k = max_word_len // 4
    chunk = chunk.reshape(-1)
    doc = doc_id.reshape(())

    packed_u, len_u, cnt_u, part, dest, (
        n_unique, max_len, has_high, token_overflow) = map_prologue(
        chunk, n_dev=n_dev, n_reduce=n_reduce, max_word_len=max_word_len,
        u_cap=u_cap, t_cap_frac=t_cap_frac)

    # Send rows: word key lanes + [len, tf, doc, part] payload, routed by
    # the shared shuffle primitive (parallel/shuffle.py shuffle_rows).
    rows = jnp.concatenate(
        [packed_u, len_u[:, None].astype(jnp.uint32),
         cnt_u[:, None].astype(jnp.uint32),
         jnp.broadcast_to(doc.astype(jnp.uint32), (u_cap,))[:, None],
         part[:, None]], axis=1)
    recv = shuffle_rows(rows, dest, n_dev=n_dev, u_cap=u_cap, k=k)

    # Partition received rows valid-first so the host's occupied-prefix
    # D2H slice works; the host accumulator (parallel/merge.py
    # PostingsTable) groups at finalize by merging the runs it finds
    # (each source device's block arrives in word order and this stable
    # sort keeps it), so the former full by-word device sort bought
    # nothing but the pad partition.  One boolean key with ALL columns
    # packed pairwise into u64 operands (operand count, not comparator
    # width, dominates XLA's CPU sort) measured +20% whole-soak
    # throughput at 256 MB (round 5).  Pad detection on the first
    # PACKED column: a pad row is all-ones in every lane, i.e.
    # uint64-max after packing (a real first lane can be 0xFFFFFFFF
    # only for non-ASCII bytes, which has_high rejects).
    with enable_x64(True):  # every op touching u64 operands needs it
        keys64 = pack_key_lanes(tuple(recv[:, j] for j in range(k)))
        pay64 = pack_key_lanes(tuple(recv[:, k + j] for j in range(4)))
        k64 = len(keys64)
        is_pad = (keys64[0] == jnp.array(_PAD_KEY64, jnp.uint64)) \
            .astype(jnp.uint8)
        sorted_cols = lax.sort((is_pad,) + keys64 + pay64, num_keys=1)
        srecv = jnp.stack(
            unpack_key_lanes(sorted_cols[1:1 + k64], k)
            + unpack_key_lanes(sorted_cols[1 + k64:], 4), axis=1)
    n_rows = jnp.sum(sorted_cols[0] == 0, dtype=jnp.int32)

    scalars = jnp.stack([n_rows, n_unique, max_len,
                         has_high.astype(jnp.int32),
                         token_overflow.astype(jnp.int32)])
    return srecv[None], scalars[None]


def _tfidf_wave_step_impl(chunks: jax.Array, doc_ids: jax.Array, *,
                          n_dev: int, n_reduce: int, max_word_len: int,
                          u_cap: int, mesh: Mesh, t_cap_frac: int = 4):
    """One SPMD wave: ``chunks`` [n_dev, L] uint8 (one zero-padded document
    per device), ``doc_ids`` [n_dev] int32.  Returns per-device sorted
    (word, len, tf, doc, part) rows [D, D*u_cap, K+4] and [D, 5] scalars
    (n_rows, n_unique, max_len, has_high, token_overflow)."""
    body = functools.partial(_tfidf_device_step, n_dev=n_dev,
                             n_reduce=n_reduce, max_word_len=max_word_len,
                             u_cap=u_cap, t_cap_frac=t_cap_frac)
    return _shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS, None, None), P(AXIS, None)))(chunks, doc_ids)


tfidf_wave_step = x64_scoped(jax.jit(
    _tfidf_wave_step_impl,
    static_argnames=("n_dev", "n_reduce", "max_word_len", "u_cap",
                     "t_cap_frac", "mesh")))

#: jax.jit donate_argnums for the pipelined wave program: the chunk
#: upload is consumed by the kernel (the window re-uploads per attempt),
#: so an in-flight window never doubles chunk residency in HBM.  The
#: tiny doc-id vector is not worth donating.
_WAVE_DONATE = (0,)


def _wave_program(*, n_dev: int, n_reduce: int, max_word_len: int,
                  u_cap: int, size: int, mesh: Mesh, t_cap_frac: int):
    """The (name, fn) pair for one compiled wave-step shape — same
    single-definition discipline as ``streaming._step_program``.
    ``size`` enters the name for readability only (the memo key already
    holds the example avals)."""

    def fn(chunk, ids):
        return _tfidf_wave_step_impl(chunk, ids, n_dev=n_dev,
                                     n_reduce=n_reduce,
                                     max_word_len=max_word_len,
                                     u_cap=u_cap, mesh=mesh,
                                     t_cap_frac=t_cap_frac)

    name = (f"tfidf_wave_d{n_dev}_r{n_reduce}_w{max_word_len}"
            f"_u{u_cap}_s{size}_f{t_cap_frac}")
    return name, fn


def _wave_fn(example_args, **kw):
    """Compiled wave step via the AOT executable cache
    (``backends/aotcache.py``), chunk donated.  On a single real device
    the compiled program persists to disk (a fresh process loads instead
    of re-paying the remote compile — the stream-step rationale); on the
    multi-device virtual mesh the cache compiles in-process and serves
    as the per-shape memo, skipping jit's per-call dispatch machinery on
    the wave hot path."""
    from dsi_tpu.backends import aotcache
    from dsi_tpu.device.table import _quiet_unusable_donation

    name, fn = _wave_program(**kw)
    with _quiet_unusable_donation():  # a cold entry compiles right here
        return aotcache.cached_compile(name, fn, example_args,
                                       donate_argnums=_WAVE_DONATE,
                                       x64=True)


def plan_waves(doc_lens: Sequence[int],
               n_dev: int) -> List[Tuple[List[int], int]]:
    """Assign documents to waves of ``n_dev``, longest-first.

    Returns ``[(doc_indices, chunk_size), ...]`` where ``chunk_size`` is the
    power of two holding that wave's OWN longest document (min 256).
    Longest-first grouping makes sizes non-increasing across waves, so the
    number of distinct compiled shapes is bounded by the log2 spread of
    document sizes — a single 10x outlier adds exactly one shape
    — and the peak device buffer of a wave tracks
    that wave's documents, not the global maximum.
    """
    order = sorted(range(len(doc_lens)), key=lambda i: doc_lens[i],
                   reverse=True)
    waves = []
    for w in range(0, len(order), n_dev):
        idxs = order[w:w + n_dev]
        longest = max(doc_lens[i] for i in idxs)
        waves.append((idxs, 1 << max(8, int(longest).bit_length())))
    return waves


def _wave_chunk(docs: Sequence[bytes], idxs: Sequence[int], n_dev: int,
                size: int) -> np.ndarray:
    """Materialise ONE wave's [n_dev, size] padded block lazily — padding
    the whole corpus up front would allocate n_docs x pow2(longest) bytes
    (one big document among many small ones inflates it catastrophically);
    per-wave blocks keep host memory O(wave's own longest)."""
    out = np.zeros((n_dev, size), dtype=np.uint8)
    for r, i in enumerate(idxs):
        out[r, :len(docs[i])] = np.frombuffer(docs[i], dtype=np.uint8)
    return out


class _AbortRung(Exception):
    """A wave proved this capacity/word-window rung's results will be
    discarded (non-ASCII input, or a word wider than the packed window):
    unwind the pipeline — dispatching more waves is pure waste."""


class TfidfStep(_EngineStep):
    """Resumable step object over the TF-IDF wave walk —
    :func:`tfidf_sharded`'s parameters and semantics behind the
    ``{advance, confirm, checkpoint, restore, close}`` lifecycle
    (``parallel/stepobj.py``).  The word-window rung ladder lives
    inside the lifecycle: a wave proving the rung too narrow tears it
    down and ``advance()`` restarts at the 64-byte rung; non-ASCII
    input (or a word wider than 64 bytes) routes to the host path."""

    _rung_excs = (_AbortRung,)

    def __init__(self, docs: Sequence[bytes], mesh: Mesh | None = None,
                 n_reduce: int = 10, max_word_len: int = 16,
                 u_cap: int = 1 << 15, partitions: Optional[set] = None,
                 packed: bool = False, device_accumulate: bool = False,
                 sync_every: Optional[int] = None,
                 mesh_shards: Optional[int] = None,
                 wave_stats: Optional[dict] = None,
                 depth: Optional[int] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_async: Optional[bool] = None,
                 checkpoint_delta: Optional[bool] = None,
                 resume: bool = False,
                 input_range: Optional[tuple] = None):
        super().__init__()
        _tfidf_setup(self, docs, mesh, n_reduce, max_word_len, u_cap,
                     partitions, packed, device_accumulate, sync_every,
                     mesh_shards, wave_stats, depth, checkpoint_dir,
                     checkpoint_every, checkpoint_async,
                     checkpoint_delta, resume, input_range)

    def _next_rung(self) -> bool:
        self._pipe.end()
        if self._writer is not None:
            self._writer.shutdown()  # a rung restart discards rung state
        if not self._outcome["high"]:
            nxt = [m for m in self._rungs if m > self._mwl]
            if nxt:
                self._begin_rung(nxt[0])
                return True
        # Non-ASCII, or a word wider than 64 bytes: the host path's job.
        self.result = None
        self._phase = "hostpath"
        return False


def tfidf_sharded(
        docs: Sequence[bytes], mesh: Mesh | None = None, n_reduce: int = 10,
        max_word_len: int = 16, u_cap: int = 1 << 15,
        partitions: Optional[set] = None, packed: bool = False,
        device_accumulate: bool = False, sync_every: Optional[int] = None,
        mesh_shards: Optional[int] = None,
        wave_stats: Optional[dict] = None, depth: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_async: Optional[bool] = None,
        checkpoint_delta: Optional[bool] = None, resume: bool = False,
):
    """Whole-corpus TF-IDF over the mesh, waves of n_dev documents,
    pipelined ``depth`` waves deep.

    Returns ``{word: (reduce_partition, [(doc_index, tf), ...])}`` — exact,
    or None when any document needs the host path (non-ASCII bytes, words
    longer than 64).  Same exactness discipline as ``wordcount_streaming``:
    waves dispatch optimistically at a sticky (capacity, frac)
    rung, their scalar checks are deferred until they leave the in-flight
    window (``depth - 1`` waves late), and a failed check replays exactly
    that wave through the ladder at the wider — then sticky — shape.
    Results are bit-identical to the ``depth=1`` lockstep path: the
    accumulator only ever ingests a wave already proven exact, in wave
    order, and a wave's valid rows (content and device-sorted order) do
    not depend on the capacity rung that produced them.

    ``depth`` (default ``DSI_STREAM_PIPELINE_DEPTH``, 2) is the in-flight
    wave window, driven by the shared dispatch/finish pipeline core
    (``parallel/pipeline.py``): a background materializer thread builds
    ``_wave_chunk`` blocks into a bounded queue while the main thread
    uploads (chunk DONATED to the kernel — an in-flight window holds at
    most ``depth`` chunk buffers in HBM) and dispatches ahead without
    synchronizing.  ``depth=1`` is fully synchronous: no thread,
    dispatch then check.

    ``partitions`` restricts the host accumulator to those reduce
    partitions — the module's large-corpus story made concrete: running the
    job once per partition slice divides the O(total postings) host memory
    by the number of slices (device work repeats per slice; the partition
    id rides every shuffled row, so filtering costs nothing extra).  The
    slices' union is exactly the unfiltered result.

    ``packed=True`` returns the ``merge.PackedPostings`` numpy tables
    instead of the dict — ~32 B/posting instead of ~250 B of Python
    objects, the difference between a bounded and an input-proportional
    host footprint at GB scale.  ``docs`` may be any sequence yielding
    bytes on ``__getitem__`` (e.g. :class:`FileDocs`, which reads each
    document from disk per wave instead of holding the corpus resident);
    a ``lengths`` attribute, when present, avoids loading documents just
    to size the waves.

    ``device_accumulate=True`` batches the wave walk's D2H through the
    device-resident accumulator service: each CONFIRMED wave's received
    rows APPEND into a persistent on-device postings buffer
    (``device/postings.py``, append flags lagged by the pipeline depth)
    and the host pulls once per ``sync_every`` waves
    (``DSI_STREAM_SYNC_EVERY`` default, 8) or when the buffer fills —
    amortizing the fixed per-pull cost exactly as the
    streaming engine's fold does.  Results are identical: the same rows
    reach the same ``PostingsTable`` in the same per-device order (the
    buffer's sticky-overflow protocol preserves wave order through
    recovery), and the padding-doc/partition filters run at drain time.
    ``mesh_shards`` (default ``DSI_STREAM_MESH_SHARDS``; implies
    ``device_accumulate``) re-routes the buffered rows by
    ``ihash(word) % n_shards`` inside the compiled append — the
    mesh-sharded service treatment (``device/table.py`` module docs),
    bit-identical results included.

    ``wave_stats``, if given, is populated with the per-phase wall
    seconds ``wave_phases`` mirrors of ``stream_phases``:
    ``materialize_s`` (background wave build), ``materialize_wait_s``
    (main-thread starvation), ``upload_s``, ``kernel_s`` (time blocked
    on a wave's deferred scalar check), ``pull_s``, ``merge_s``,
    ``replay_s`` — plus ``waves``, ``depth``, ``replays``,
    ``max_inflight_waves``, ``step_pulls``, and the device-accumulate
    counters (``appends``/``append_overflows``/``sync_pulls``/
    ``postings_widens``/``append_s``/``drain_s``/``sync_every``).

    ``checkpoint_dir``/``checkpoint_every``/``resume`` follow the
    streaming engines' crash-resume contract (``dsi_tpu/ckpt``): the
    cursor is the CONFIRMED-wave ordinal (``plan_waves`` is
    deterministic in doc lengths), snapshots carry the postings-table
    residue, the device buffer's drain-free image, and the sticky rung,
    tagged with the word-window rung they belong to; resumed output is
    bit-identical to an uninterrupted walk.
    """
    return TfidfStep(
        docs, mesh=mesh, n_reduce=n_reduce, max_word_len=max_word_len,
        u_cap=u_cap, partitions=partitions, packed=packed,
        device_accumulate=device_accumulate, sync_every=sync_every,
        mesh_shards=mesh_shards, wave_stats=wave_stats, depth=depth,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        checkpoint_async=checkpoint_async,
        checkpoint_delta=checkpoint_delta, resume=resume).close()


def _tfidf_setup(step, docs, mesh, n_reduce, max_word_len, u_cap,
                 partitions, packed, device_accumulate, sync_every,
                 mesh_shards, wave_stats, depth, checkpoint_dir,
                 checkpoint_every, checkpoint_async, checkpoint_delta,
                 resume, input_range=None):
    """The engine body behind :class:`TfidfStep`: corpus-wide setup,
    then ``begin_rung`` (the former per-rung ``run``) arms the pipeline
    and attaches the lifecycle hooks to ``step``.

    ``input_range`` is the shard scheduler's cursor range in DOC
    ordinals (mr/shards.py): drive ``docs[start:end]`` and tag the
    chain identity with the range so attempts over different ranges
    can never cross-restore."""
    if input_range is not None:
        docs = docs[int(input_range[0]):int(input_range[1])]
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    depth = pipeline_depth(depth)
    from dsi_tpu.device.policy import mesh_shards_default

    mesh_shards = mesh_shards_default(mesh_shards)
    if mesh_shards:
        device_accumulate = True
    doc_lens = getattr(docs, "lengths", None)
    if doc_lens is None:
        doc_lens = [len(d) for d in docs]
    waves = plan_waves(doc_lens, n_dev)
    longest = max(doc_lens, default=1)
    size_max = 1 << max(8, int(longest).bit_length())  # capacity hard ref
    n_real = len(docs)
    # Internal registry scope (dsi_tpu/obs); copied out to the caller's
    # ``wave_stats`` dict when the walk ends — wave_phases is a view
    # over the one documented schema, not its own dialect.
    stats = metrics_scope("tfidf")
    stats.update({"waves": len(waves), "step_pulls": 0, "depth": depth,
                  "replays": 0, "device_accumulate": device_accumulate,
                  "upload_s": 0.0, "kernel_s": 0.0, "pull_s": 0.0,
                  "merge_s": 0.0, "replay_s": 0.0})
    sh_chunk = NamedSharding(mesh, P(AXIS, None))
    sh_ids = NamedSharding(mesh, P(AXIS))

    # ── checkpoint/restore (dsi_tpu/ckpt): wave-cursor variant ──
    ck_store: Optional[CheckpointStore] = None
    resume_meta = None
    resume_arrays = None
    resume_deltas: list = []
    ck_async = checkpoint_async_default(checkpoint_async)
    ck_delta = checkpoint_delta_default(checkpoint_delta)
    if checkpoint_dir:
        import zlib

        # The wave plan — and with it the cursor's meaning — is a
        # function of the full per-doc length vector, so the vector's
        # CRC is part of the job identity: same count + same total with
        # shuffled lengths must refuse, not silently misalign waves.
        lens_crc = zlib.crc32(np.asarray(doc_lens, np.int64).tobytes())
        ident = {"n_dev": n_dev, "n_reduce": n_reduce, "u_cap": u_cap,
                 "n_docs": n_real, "doc_lens_crc32": lens_crc,
                 "partitions": (sorted(int(p) for p in partitions)
                                if partitions is not None else None),
                 "device_accumulate": bool(device_accumulate)}
        if input_range is not None:
            ident["input_range"] = [int(input_range[0]),
                                    int(input_range[1])]
        ck_store = CheckpointStore(checkpoint_dir, "tfidf", ident)
        if resume:
            loaded = ck_store.load_latest_chain()
            if loaded is not None:
                resume_meta, resume_arrays, resume_deltas = loaded
        else:
            ck_store.reset()

    def begin_rung(mwl: int):
        """One word-window rung: arm the pipelined wave walk at packed
        width ``mwl`` and attach its hooks to ``step``.  Capacity
        overflow never discards the rung — the overflowing wave alone
        replays wider and the widened capacity sticks; non-ASCII and
        word-window overflow raise ``_AbortRung`` through the
        lifecycle, which restarts wider or routes to the host path."""
        kk = mwl // 4
        # Buffer each wave's surviving rows AS THE WAVES CONFIRM — raw
        # uint32 tables copied out of the wave's transfer buffer (no
        # device-shaped block stays alive), grouped/decoded once at
        # payload time by the vectorized PostingsTable (parallel/
        # merge.py).  Host state is O(postings in this slice).  A
        # discarded rung (word-window widen) drops the whole table, so
        # partial rungs can't leak into the result.
        table = PostingsTable()
        part_arr = (None if partitions is None
                    else np.fromiter(partitions, dtype=np.uint32))
        # Sticky dispatch rung, exactly the streaming engine's: only
        # ever moves toward more headroom, so a corpus that widens once
        # doesn't replay every later wave.
        state = {"cap": rung0_cap(size_max, u_cap), "frac": 4}
        outcome = {"high": False, "widen": False}

        def buffer_rows(r: np.ndarray) -> None:
            """One device's pulled rows into the host table, filtered
            FIRST: the short last wave's padding documents and — for a
            partition slice — other slices' rows must cut the per-slice
            host cost, not just the final table (same rule on both the
            per-wave and the drain path)."""
            r = r[r[:, kk + 2] < n_real]
            if part_arr is not None:
                r = r[np.isin(r[:, kk + 3], part_arr)]
            if len(r):
                table.add(r, kk)

        # Device-resident accumulation (fresh per rung — a rung restart
        # discards partial device state exactly like the host table):
        # confirmed waves append on-device with lagged flags, the host
        # pulls per K-wave window; overflow drains early (or widens for
        # a lone outsized wave) — never a loss, and wave order survives
        # recovery (device/postings.py sticky-overflow protocol).
        buf_dev = None
        policy = None
        if device_accumulate:
            import os

            from dsi_tpu.device import DevicePostings, SyncPolicy

            # One worst-case wave by default (so drain-and-retry always
            # fits); DSI_DEVICE_POSTINGS_CAP trims it for HBM-tight
            # meshes (overflow then just syncs earlier) and lets tests
            # force the early-drain path.
            try:
                pcap = int(os.environ.get("DSI_DEVICE_POSTINGS_CAP", "0"))
            except ValueError:
                pcap = 0
            buf_dev = DevicePostings(
                mesh, width=kk + 4,
                cap=pcap if pcap > 0 else n_dev * state["cap"],
                sink=buffer_rows, lag=max(0, depth - 1), stats=stats,
                mesh_shards=mesh_shards, kk=kk)
            policy = SyncPolicy(sync_every)
            stats["sync_every"] = policy.sync_every
            stats["mesh_shards"] = mesh_shards

        # A checkpoint belongs to ONE word-window rung (the widen
        # restart discards rung state): apply the loaded image only at
        # its own rung.
        ck_policy: Optional[CheckpointPolicy] = None
        ck_writer: Optional[CheckpointWriter] = None
        ck_wave = [0]
        host_delta = HostDeltaLog()  # non-dacc delta log: trimmed copies
        # of the pulled (rows, nrows) waves, bounded like device logs
        start_wave = 0
        if ck_store is not None:
            ck_policy = CheckpointPolicy(checkpoint_every)
            stats.setdefault("ckpt_saves", 0)
            stats.setdefault("ckpt_s", 0.0)
            stats.setdefault("ckpt_capture_s", 0.0)
            stats["ckpt_every"] = ck_policy.every
            stats["ckpt_async"] = ck_async
            stats["ckpt_delta"] = ck_delta
            # A fresh writer per rung: a rung restart discards rung
            # state, so its first save is a full base again.
            ck_writer = CheckpointWriter(ck_store, stats, async_=ck_async,
                                         delta=ck_delta)
            if ck_delta and buf_dev is not None:
                buf_dev.enable_delta()
            # Cursor/rung state is newest-wins: the final delta's meta
            # IS the restore point; the base meta names image shapes.
            eff = resume_deltas[-1][0] if resume_deltas else resume_meta
            if eff is not None and int(eff["mwl"]) == mwl:
                t_res = time.perf_counter()
                start_wave = int(eff["wave"])
                ck_wave[0] = start_wave
                state.update({"cap": int(eff["cap"]),
                              "frac": int(eff["frac"])})
                table.restore({k[3:]: v for k, v in resume_arrays.items()
                               if k.startswith("pt_")})
                if buf_dev is not None and resume_meta.get("pb_cap"):
                    pb_img = {"buf": resume_arrays["pb_buf"],
                              "nrows": resume_arrays["pb_nrows"],
                              "cap": resume_meta["pb_cap"]}
                    saved_shards = int(resume_meta.get("mesh_shards", 0))
                    if resume_deltas or saved_shards != mesh_shards:
                        # Chain restore (and the sharding-degree
                        # change) re-enters via the drain path — the
                        # buffered rows into the host table, buffer
                        # empty; resumed waves rebuild device state.
                        DevicePostings.drain_image(buffer_rows, pb_img)
                        if saved_shards != mesh_shards:
                            stats["resharded_resume"] = saved_shards
                    else:
                        buf_dev.restore_state(pb_img)
                        if ck_delta:
                            buf_dev.enable_delta()
                if policy is not None:
                    policy.restore(eff.get("sync_since", 0))
                for _, darr in resume_deltas:
                    # Each delta's retained wave payloads re-enter the
                    # host table through the sink in save order —
                    # per-word posting order preserved, the drain-path
                    # argument the cross-degree resume rests on.
                    drain_posting_steps(buffer_rows, darr, "pb_")
                stats["resume_gap_s"] = round(
                    time.perf_counter() - t_res, 4)
                stats["resume_wave"] = start_wave

        def save_ckpt() -> None:
            """Consistent snapshot at a confirmed-wave boundary —
            capture here, commit inline or in the background writer
            (``ckpt/writer.py``): the device buffer's capture FIRST
            (flushing its lag can drain into the host table), host
            residue second.  A delta save ships only the wave payloads
            retained since the previous save; every
            ``DSI_STREAM_CKPT_REBASE``-th save is a full re-base (an
            invalid delta window forces one)."""
            with _span("ckpt", stats=stats, key="ckpt_s",
                       wave=ck_wave[0]):
                meta = {"mwl": mwl, "wave": ck_wave[0],
                        "cap": state["cap"], "frac": state["frac"]}
                kind = "full"
                parts = None
                with _span("ckpt_capture", lane="ckpt", stats=stats,
                           key="ckpt_capture_s"):
                    if ck_writer.want_delta():
                        if buf_dev is not None:
                            entries = buf_dev.take_delta()
                        else:
                            entries = host_delta.take()
                        if entries is not None:
                            parts = [("pb_", DeltaSteps(entries))]
                            if policy is not None:
                                meta["sync_since"] = policy.snapshot()
                            kind = "delta"
                    if parts is None:
                        # Full image — the PR-5 arrays (device pull
                        # dispatched, not awaited); the delta logs
                        # reset here: payloads recorded before this
                        # base are inside the image.
                        parts = []
                        if buf_dev is not None:
                            parts.append(("pb_",
                                          buf_dev.checkpoint_capture()))
                            meta["pb_cap"] = buf_dev.cap
                            meta["mesh_shards"] = buf_dev.mesh_shards
                            meta["sync_since"] = policy.snapshot()
                            if ck_delta:
                                buf_dev.take_delta()
                        host_delta.reset()
                        parts.append(("pt_", table.snapshot()))
                fault_point("mid-capture")
                ck_writer.commit(parts, meta, kind=kind)

        def materialize():
            for idxs, size in waves[start_wave:]:
                chunk_np = _wave_chunk(docs, idxs, n_dev, size)
                # Pad rows of a short last wave carry doc id n_real,
                # which buffer_rows discards.
                ids_np = np.array(list(idxs) + [n_real] * (n_dev - len(idxs)),
                                  dtype=np.int32)
                yield (size, chunk_np, ids_np)

        def wave_call(chunk_np, ids_np, size, cap, frac):
            """Upload + async wave dispatch at one rung.  Each attempt
            re-uploads: the compiled program donates its chunk."""
            with _span("upload", stats=stats, key="upload_s"):
                chunk = jax.device_put(chunk_np, sh_chunk)
                ids = jax.device_put(ids_np, sh_ids)
            fn = _wave_fn((chunk, ids), n_dev=n_dev, n_reduce=n_reduce,
                          max_word_len=mwl, u_cap=cap, size=size,
                          mesh=mesh, t_cap_frac=frac)
            from dsi_tpu.device.table import _quiet_unusable_donation

            with _quiet_unusable_donation():
                outs = fn(chunk, ids)
            _enqueued(outs[1])  # (rows, scal)
            return outs

        def dispatch(item):
            size, chunk_np, ids_np = item
            rows, scal = wave_call(chunk_np, ids_np, size, state["cap"],
                                   state["frac"])
            fault_point("post-dispatch")
            return (size, chunk_np, ids_np, rows, scal, state["cap"])

        def replay_wave(size, chunk_np, ids_np):
            """The full exactness ladder for ONE wave — the replay path
            of a deferred-check failure.  The cleared rung sticks for
            every later dispatch."""
            stats["replays"] += 1
            cap = state["cap"]
            with _span("replay", stats=stats, key="replay_s"):
                while True:
                    for frac in (4, 2):
                        rows, scal = wave_call(chunk_np, ids_np, size,
                                               cap, frac)
                        scal_np = np.asarray(scal)
                        if not scal_np[:, 4].any():
                            break
                    if bool(scal_np[:, 3].any()):
                        outcome["high"] = True
                        raise _AbortRung
                    if int(scal_np[:, 2].max()) > mwl:
                        outcome["widen"] = True
                        raise _AbortRung
                    if int(scal_np[:, 1].max()) > cap:
                        cap *= 4  # uniques <= tokens <= size/2: terminates
                        continue
                    break
            state["cap"], state["frac"] = cap, frac
            return rows, scal, scal_np

        def commit(rows, scal, scal_np):
            m = int(scal_np[:, 0].max())
            if m == 0:
                return
            if buf_dev is not None:
                pulls_before = stats["sync_pulls"]
                buf_dev.append(rows, scal,
                               nvalid=scal_np[:, 0].astype(np.int64))
                policy.note_fold()
                if stats["sync_pulls"] != pulls_before:
                    policy.reset()  # an overflow recovery just drained:
                    # that WAS this window's pull — without the reset,
                    # due() would fire a second, nearly empty one
                elif policy.due():
                    fault_point("pre-sync")
                    buf_dev.sync()
                    policy.reset()
                return
            # Pull only the occupied prefix (max per-device received
            # rows, pow2-rounded to bound the slice-program count): the
            # D2H bill tracks this wave's postings, not capacity.
            with _span("pull", stats=stats, key="pull_s"):
                mp = occupied_prefix(m, rows.shape[1])
                # the slice's program and its copy, blocked on at once
                with _span("d2h", lane="pull", stats=stats, key="d2h_s"):
                    rows_np = np.asarray(rows[:, :mp])
                stats["step_pulls"] += 1
            with _span("merge", stats=stats, key="merge_s"):
                for d in range(n_dev):
                    nr = int(scal_np[d, 0])
                    if nr:
                        buffer_rows(rows_np[d, :nr])
                if ck_store is not None and ck_delta:
                    # Host-merge delta log: the wave's payload, window-
                    # bounded like the device logs.
                    host_delta.append(rows_np, scal_np[:, 0])

        def finish(rec):
            """Retire the oldest in-flight wave: deferred scalar check,
            then commit (clean) or replay-at-wider-shape (overflow)."""
            size, chunk_np, ids_np, rows, scal, cap = rec
            with _span("kernel", stats=stats, key="kernel_s"):
                scal_np = np.asarray(scal)  # blocks until the kernel lands
            if bool(scal_np[:, 3].any()):
                outcome["high"] = True
                raise _AbortRung
            if int(scal_np[:, 2].max()) > mwl:
                outcome["widen"] = True
                raise _AbortRung
            if scal_np[:, 4].any() or int(scal_np[:, 1].max()) > cap:
                # Late-detected overflow: replay just this wave.
                # Exactly-once by construction — the optimistic attempt's
                # rows are dropped uncommitted, the replay's commit here
                # and nowhere else.
                rows, scal, scal_np = replay_wave(size, chunk_np, ids_np)
            commit(rows, scal, scal_np)
            # Confirmed (empty waves included); fault before the cursor
            # moves — the torn-update instant.
            fault_point("mid-fold")
            if ck_policy is not None:
                ck_wave[0] += 1
                ck_policy.note_step()
                if ck_policy.due():
                    save_ckpt()
                    ck_policy.reset()

        pipe = StepPipeline(depth=depth, dispatch=dispatch, finish=finish,
                            stats=stats, produce_key="materialize_s",
                            wait_key="materialize_wait_s",
                            inflight_key="max_inflight_waves",
                            thread_name="dsi-wave-materializer",
                            engine="tfidf")
        step._pipe = pipe
        step._mwl = mwl
        step._outcome = outcome
        step._save = save_ckpt if ck_policy is not None else None
        step._writer = ck_writer
        pipe.begin(materialize)

        def end_ok():
            try:
                if buf_dev is not None:
                    fault_point("pre-sync")
                    buf_dev.close()  # end-of-walk sync
                if ck_writer is not None:
                    ck_writer.drain()  # surface async commit errors
                    # before the payload (and save counters) are read
            finally:
                if ck_writer is not None:
                    ck_writer.shutdown()
            step.result = (table.finalize_packed() if packed
                           else table.finalize())

        step._on_complete = end_ok

    # The word-window ladder (exactness_retry's outer rung, hand-rolled
    # because capacity now widens per wave INSIDE a rung): a word wider
    # than the packed window re-keys every row, so that one overflow
    # class still restarts the walk.
    rungs = ((max_word_len, 64) if max_word_len < 64 else (max_word_len,))
    if resume_meta is not None:
        # Start at the checkpoint's rung: an earlier rung had provably
        # aborted before the checkpointed rung began its walk.
        rungs = tuple(m for m in rungs
                      if m >= int(resume_meta["mwl"])) or rungs
    step._rungs = tuple(rungs)
    step._begin_rung = begin_rung

    released = []

    def release():
        if released:
            return
        released.append(True)
        w = step._writer  # the CURRENT rung's writer (re-set per rung)
        if w is not None:
            w.shutdown()
        fold_source_stats(stats, docs)  # a doc source may pool-read too
        if wave_stats is not None:
            wave_stats.update(stats)

    step._release = release
    begin_rung(rungs[0])


class FileDocs:
    """Lazy document sequence for :func:`tfidf_sharded`: documents load
    from disk per access (one wave's working set at a time) instead of
    holding the whole corpus resident — at the 1 GB soak that was 1.07 GB
    of the peak RSS.  Its sibling ``utils/ioread.ReadAheadDocs`` makes
    the other trade for a job that held its documents anyway
    (``planrun --chain indexer``): every document read once, ahead of
    the walk, by a pool of threads, and kept."""

    def __init__(self, paths: Sequence[str]):
        import os

        self.paths = list(paths)
        self.lengths = [os.path.getsize(p) for p in self.paths]

    def __len__(self) -> int:
        return len(self.paths)

    def __getitem__(self, i: int) -> bytes:
        with open(self.paths[i], "rb") as f:
            return f.read()


def write_tfidf_output(result: Dict[str, Tuple[int, List[Tuple[int, int]]]],
                       doc_names: Sequence[str], n_reduce: int,
                       workdir: str = ".") -> List[str]:
    """Materialise mr-out-<r> files byte-identical to the host tfidf app's
    reduce output: scores via the shared ``format_value``, files via the
    shared partitioned writer (``shuffle.write_partitioned_output``)."""
    from dsi_tpu.apps.tfidf import format_value
    from dsi_tpu.parallel.shuffle import write_partitioned_output

    n_docs = len(doc_names)
    formatted = {
        w: (format_value([(doc_names[d], tf) for d, tf in pairs], n_docs), r)
        for w, (r, pairs) in result.items()}
    return write_partitioned_output(formatted, n_reduce, workdir)
