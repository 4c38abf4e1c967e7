"""SPMD MapReduce step: map + shuffle + reduce as ONE compiled program.

This is the multi-device redesign of the reference's whole data path:

* map phase  = per-device tokenize/group (``tokenize_group_core``), replacing
  the worker's mapf + bucketing hot loops (``mr/worker.go:69-92``),
* shuffle    = ``jax.lax.all_to_all`` over the device mesh, replacing the
  NxM ``mr-<m>-<r>`` intermediate files on a shared filesystem
  (``mr/worker.go:81-92, 102-121``) — the exchange rides ICI, not disk,
* reduce     = per-device sort + per-run sum of the received records,
  replacing the reduce task's decode/sort/group/count
  (``mr/worker.go:110-146``).

Partitioning semantics are bit-identical to the reference: a word belongs to
reduce partition ``r = fnv1a32(word) & 0x7fffffff % NReduce``
(``mr/worker.go:33-37,76``); partitions are mapped to devices round-robin
(``r % n_dev``), so every device ends up owning exactly the reduce partitions
``{r : r % n_dev == device}`` and the map-barrier-then-reduce structure of the
reference (``mr/coordinator.go:47,79``) is preserved *inside* the program: the
all_to_all is the barrier.

Everything is static-shaped for XLA: the send buffer gives each destination a
fixed ``u_cap``-row block (a device has at most ``u_cap`` unique words total,
so a per-destination block of the same size can never overflow); pad rows
carry key ``0xFFFFFFFF`` which sorts after every real ASCII word.  Exactness
escapes (non-ASCII bytes, words longer than ``max_word_len``, more uniques
than ``u_cap``) are returned as per-device flags; the host wrapper retries
with wider shapes or falls back to the host path, so results are always
exact (same discipline as ``ops/wordcount.py``).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dsi_tpu.ops.wordcount import (
    _PAD_KEY,
    exactness_retry,
    group_sorted,
    lex_sort,
    tokenize_group_core,
)

from dsi_tpu.utils.jaxcompat import x64_scoped, shard_map as _shard_map

AXIS = "workers"


def default_mesh(n_devices: int | None = None) -> Mesh:
    """1-D device mesh over the first n (default: all) local devices."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


@jax.named_scope("shuffle")
def shuffle_rows(rows: jax.Array, dest: jax.Array, *, n_dev: int,
                 u_cap: int, k: int) -> jax.Array:
    """Route per-word rows to their destination devices over ICI.

    The shared shuffle primitive of every SPMD job step (word count here,
    TF-IDF in ``parallel/tfidf.py``): scatter ``rows`` [u_cap, k+p] (k word
    key lanes + p payload lanes) into one fixed ``u_cap``-row block per
    destination — a device has at most ``u_cap`` rows total, so a
    per-destination block of the same size can never overflow — then one
    ``lax.all_to_all``.  ``dest`` must be ``n_dev`` for invalid rows (they
    are parked on the scatter's overflow row and dropped).  Pad rows carry
    key ``0xFFFFFFFF``, which sorts after every real ASCII word.  The
    placement is the one scatter: where a destination's block starts in
    the sorted rows is a count of the rows bound for a lower destination
    (``n_dev + 1`` compare-and-sum reductions in int32), where a
    ``jnp.bincount`` was a 64-bit ``scatter-add`` of one update a row
    under the x64 scope (PERF.md, PR 39).
    """
    p = rows.shape[1] - k
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    srows = rows[order]
    bins = jnp.arange(n_dev + 1, dtype=dest.dtype)
    starts = jnp.sum(dest[None, :] < bins[:, None], axis=1, dtype=jnp.int32)
    pos_in = jnp.arange(u_cap, dtype=jnp.int32) - starts[sdest]
    flat = jnp.where(sdest < n_dev, sdest * u_cap + pos_in, n_dev * u_cap)
    pad_row = jnp.concatenate(
        [jnp.full((k,), _PAD_KEY, jnp.uint32), jnp.zeros((p,), jnp.uint32)])
    sendbuf = jnp.broadcast_to(pad_row, (n_dev * u_cap + 1, k + p))
    sendbuf = sendbuf.at[flat].set(srows)[:n_dev * u_cap]
    return lax.all_to_all(sendbuf, AXIS, split_axis=0, concat_axis=0,
                          tiled=True)


@jax.named_scope("map")
def map_prologue(chunk: jax.Array, *, n_dev: int, n_reduce: int,
                 max_word_len: int, u_cap: int, t_cap_frac: int,
                 doc_sep: Optional[int] = None, map=None):
    """Shared per-device map phase: tokenize + combine + partition.

    The one place the reference-parity partition rule lives on device:
    ``part = fnv1a32(word) & 0x7fffffff % n_reduce`` (mr/worker.go:33-37,76)
    with destination device ``part % n_dev`` (invalid rows parked on
    ``n_dev`` for :func:`shuffle_rows`).  Used by the word-count step here
    and the TF-IDF step (``parallel/tfidf.py``) so the two SPMD jobs cannot
    drift apart.

    Returns (packed_u, len_u, cnt_u, part, dest, scalars) where scalars =
    (n_unique, max_len, has_high, token_overflow); over a packed chunk
    (``doc_sep``: ``tokenize_group_core``) a row is a (word, document)
    pair and a seventh result, ``doc_u``, is its document's place in the
    chunk.

    ``map`` (``ops/fieldsum.FieldSum``) puts another map in the
    tokenizer's place: rows of delimited fields, a row's key field the
    word and its value what is summed.  ``cnt_u`` is then
    ``uint32[u_cap, 2]``, a sum's low and high halves, ``has_high`` says a
    row could not be read, ``token_overflow`` that the chunk holds more
    rows than the buffer, and two more results follow: the chunk's rows
    and the first unreadable one's place.
    """
    core = map.group_core if map is not None else functools.partial(
        tokenize_group_core, doc_sep=doc_sep)
    (packed_u, len_u, cnt_u, fnv_u, n_unique, max_len, has_high,
     token_overflow, *more) = core(
        chunk, max_word_len=max_word_len, u_cap=u_cap, t_cap_frac=t_cap_frac)
    uvalid = jnp.arange(u_cap, dtype=jnp.int32) < n_unique
    part = (fnv_u & jnp.uint32(0x7FFFFFFF)) % jnp.uint32(n_reduce)
    dest = jnp.where(uvalid, (part % n_dev).astype(jnp.int32), n_dev)
    return (packed_u, len_u, cnt_u, part, dest,
            (n_unique, max_len, has_high, token_overflow), *more)


def _sort_wide(recv: jax.Array, k: int) -> tuple:
    """The reduce's sort over rows whose sums are two lanes: the sorted
    key columns, the lengths, the sums as a (low, high) pair, the
    partitions."""
    *scols, mlen, lo, hi, mpart = lex_sort(
        tuple(recv[:, j] for j in range(k)),
        tuple(recv[:, j] for j in range(k, k + 4)))
    return (*scols, mlen, (lo, hi), mpart)


def _device_step(chunk: jax.Array, *, n_dev: int, n_reduce: int,
                 max_word_len: int, u_cap: int, t_cap_frac: int, map=None):
    """Per-device body (runs under shard_map): map, all_to_all, reduce.
    A value that is not a count (``map``: :func:`map_prologue`) rides the
    shuffle as two lanes where a count is one, and is summed as them."""
    k = max_word_len // 4
    chunk = chunk.reshape(-1)  # [1, L] block -> [L]

    # ── map: tokenize + local combine (one record per unique word) ──
    packed_u, len_u, cnt_u, part, dest, (
        n_unique, max_len, has_high, token_overflow), *extra = map_prologue(
        chunk, n_dev=n_dev, n_reduce=n_reduce, max_word_len=max_word_len,
        u_cap=u_cap, t_cap_frac=t_cap_frac, map=map)

    # ── shuffle: the mr-X-Y files become one ICI collective ──
    with jax.named_scope("shuffle"):
        rows = jnp.concatenate(
            [packed_u, len_u[:, None].astype(jnp.uint32),
             cnt_u[:, None].astype(jnp.uint32) if map is None else cnt_u,
             part[:, None]], axis=1)
    recv = shuffle_rows(rows, dest, n_dev=n_dev, u_cap=u_cap, k=k)

    # ── reduce: sort received records by word, sum counts per run
    #    (shared grouping idiom, ops/wordcount.py lex_sort +
    #    group_sorted) ──
    out_cap = n_dev * u_cap
    with jax.named_scope("reduce"):
        *scols, mlen, mcnt, mpart = lex_sort(
            tuple(recv[:, j] for j in range(k)),
            (recv[:, k], recv[:, k + 1], recv[:, k + 2])) if map is None \
            else _sort_wide(recv, k)
        mkeys, tot, upos, ovalid, m_unique = group_sorted(
            tuple(scols), mcnt.astype(jnp.int32) if map is None else mcnt,
            out_cap)
        with jax.named_scope("group"):
            mlen = mlen.astype(jnp.int32)
            out_keys = jnp.where(ovalid[:, None], mkeys[upos],
                                 jnp.uint32(0))
            out_len = jnp.where(ovalid, mlen[upos], 0)
            out_part = jnp.where(ovalid, mpart[upos], 0)

    scalars = jnp.stack([m_unique, n_unique, max_len,
                         has_high.astype(jnp.int32),
                         token_overflow.astype(jnp.int32), *extra])
    return (out_keys[None], out_len[None], tot[None], out_part[None],
            scalars[None])


def _mapreduce_step_impl(chunks: jax.Array, *, n_dev: int, n_reduce: int,
                         max_word_len: int, u_cap: int, mesh: Mesh,
                         t_cap_frac: int = 4, map=None):
    """The full SPMD job step body — jitted twice below (with and without
    input-buffer donation) so the streaming engine's per-step uploads can
    be consumed by the kernel while ``wordcount_sharded`` keeps reusing
    one uploaded corpus across its retry attempts."""
    body = functools.partial(_device_step, n_dev=n_dev, n_reduce=n_reduce,
                             max_word_len=max_word_len, u_cap=u_cap,
                             t_cap_frac=t_cap_frac, map=map)
    totals = P(AXIS, None) if map is None else P(AXIS, None, None)
    return _shard_map(
        body, mesh=mesh,
        in_specs=P(AXIS, None),
        out_specs=(P(AXIS, None, None), P(AXIS, None), totals,
                   P(AXIS, None), P(AXIS, None)))(chunks)


_STEP_STATICS = ("n_dev", "n_reduce", "max_word_len", "u_cap", "t_cap_frac",
                 "mesh", "map")

#: The full SPMD job step, jitted over the mesh.
#:
#: ``chunks``: [n_dev, L] uint8, one zero-padded text shard per device.
#: Returns per-device arrays stacked on axis 0: packed word keys
#: [D, D*u_cap, K], byte lengths, summed counts, reduce-partition ids, and a
#: [D, 5] scalar block (m_unique, n_unique, max_len, has_high,
#: token_overflow).  With ``map`` (``ops/fieldsum.FieldSum``) the sums are
#: [D, D*u_cap, 2] uint32 (low, high) and the block is [D, 7]: the chunk's
#: rows and the first unreadable row's place behind the five.
mapreduce_step = x64_scoped(
    jax.jit(_mapreduce_step_impl, static_argnames=_STEP_STATICS))

#: Same program with the chunk buffer DONATED: the caller hands its upload
#: to the kernel, so an in-flight pipeline window holds at most one chunk
#: buffer per step in HBM (parallel/streaming.py).  A donated array cannot
#: be reused — streaming re-uploads per attempt; ``wordcount_sharded``
#: stays on the non-donated entry because it reuses one upload across its
#: whole retry ladder.
mapreduce_step_donate = x64_scoped(
    jax.jit(_mapreduce_step_impl, static_argnames=_STEP_STATICS,
            donate_argnums=(0,)))


def occupied_prefix(m: int, cap_rows: int) -> int:
    """Pow2-rounded occupied prefix of a ``cap_rows``-row result table with
    ``m`` valid rows (m >= 1): the one shape-bounding rule shared by every
    sliced D2H pull (here, streaming, TF-IDF), so the slice-program count
    stays at log2(cap) distinct shapes per path."""
    return min(cap_rows, 1 << max(6, (m - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("mp",))
def _slice_pack(keys, lens, cnts, parts, *, mp: int):
    """Device-side prefix slice + pack of a step's four result tables into
    ONE uint32 tensor [D, mp, K+3], so the host pays a single D2H
    round-trip per step instead of four (per-pull cost: not measured on
    the chip).
    ``mp`` is the pow2-rounded occupied prefix, so the bytes pulled track
    vocabulary, not capacity.  Lens/counts/partitions are uint32
    reinterpretations — all are small non-negative ints.  Sums that come
    as two lanes ([D, rows, 2]: a step with a ``map``) go down as two,
    [D, mp, K+4]."""
    with jax.named_scope("pack"):
        return jnp.concatenate(
            [keys[:, :mp],
             lens[:, :mp, None].astype(jnp.uint32),
             cnts[:, :mp, None].astype(jnp.uint32) if cnts.ndim == 2
             else cnts[:, :mp],
             parts[:, :mp, None].astype(jnp.uint32)], axis=2)


def shard_text(data: bytes, n_shards: int) -> Tuple[np.ndarray, int]:
    """Split text into n equal-ish device shards, cutting only at non-letter
    boundaries so no token straddles a shard (SURVEY.md §7 hard part 2), and
    zero-pad all shards to one power-of-two length.

    Returns ([n_shards, L] uint8, L).
    """
    n = len(data)
    cuts = [0]
    for i in range(1, n_shards):
        c = min(i * n // n_shards, n)
        # Advance past any letter run so data[c-1], data[c] are never both
        # letters (a cut inside a run would split a token).
        while 0 < c < n and _is_letter_byte(data[c - 1]) and \
                _is_letter_byte(data[c]):
            c += 1
        cuts.append(min(c, n))
    cuts.append(n)
    cuts = sorted(cuts)
    longest = max(cuts[i + 1] - cuts[i] for i in range(n_shards))
    size = 1 << max(8, longest.bit_length())
    out = np.zeros((n_shards, size), dtype=np.uint8)
    for i in range(n_shards):
        piece = data[cuts[i]:cuts[i + 1]]
        out[i, :len(piece)] = np.frombuffer(piece, dtype=np.uint8)
    return out, size


def _is_letter_byte(b: int) -> bool:
    return (65 <= b <= 90) or (97 <= b <= 122)


def wordcount_sharded(
        data: bytes, mesh: Mesh | None = None, n_reduce: int = 10,
        max_word_len: int = 16,
        u_cap: int = 1 << 15) -> Optional[Dict[str, Tuple[int, int]]]:
    """Count words over the whole corpus with one SPMD program per attempt.

    Returns ``{word: (count, reduce_partition)}`` — exact, or None when the
    input needs the host path (non-ASCII bytes or words longer than 64).
    Retries with wider static shapes on capacity overflow, mirroring
    ``ops.wordcount.count_words_host_result``.
    """
    if mesh is None:
        mesh = default_mesh()
    n_dev = mesh.devices.size
    chunks_np, shard_len = shard_text(data, n_dev)
    chunks = jnp.asarray(chunks_np)

    def run(mwl: int, cap: int):
        for frac in (4, 2):  # exact token bound is n//2+1
            keys, lens, cnts, parts, scal = mapreduce_step(
                chunks, n_dev=n_dev, n_reduce=n_reduce, max_word_len=mwl,
                u_cap=cap, mesh=mesh, t_cap_frac=frac)
            scal = np.asarray(scal)
            if not scal[:, 4].any():
                break

        def payload():
            # One sliced single-pull per attempt (see _slice_pack), merged
            # host-side by the vectorized table (parallel/merge.py) — the
            # devices' tables are disjoint (each owns distinct reduce
            # partitions), so the merge is a pure concatenate+decode.
            from dsi_tpu.parallel.merge import PackedCounts

            m = int(scal[:, 0].max())
            if m == 0:
                return {}
            mp = occupied_prefix(m, keys.shape[1])
            kk = keys.shape[2]
            packed = np.asarray(_slice_pack(keys, lens, cnts, parts, mp=mp))
            acc = PackedCounts()
            for d in range(n_dev):
                nu = int(scal[d, 0])
                r = packed[d, :nu]
                acc.add(r[:, :kk], r[:, kk], r[:, kk + 1], r[:, kk + 2])
            return acc.finalize()

        return (bool(scal[:, 3].any()), int(scal[:, 1].max()),
                int(scal[:, 2].max()), payload)

    payload = exactness_retry(run, shard_len, max_word_len, u_cap)
    return None if payload is None else payload()


def write_partitioned_output(result: Mapping[str, Tuple[int, int]],
                             n_reduce: int, workdir: str = ".",
                             stats: Optional[dict] = None) -> List[str]:
    """Materialise mr-out-<r> files from a sharded result — same file layout,
    line format ("%v %v\\n", mr/worker.go:144) and within-file key order the
    reference's reduce tasks produce (worker.go:124-146).

    One algorithm over two representations, chosen by what it is handed:
    a merged table (``merge.PackedWordCounts``, what a stream's
    accumulator returns; ``merge.PackedPostings`` with its documents
    named, an inverted index: a row is ``"word n doc,doc,..."``) renders
    each partition's bytes from its arrays (``write_rows_packed`` of
    ``stats`` counts the rows), with no Python object a word or a
    posting; a dict (the host fallback's) is bucketed, sorted and
    formatted a line at a time (``write_rows_dict``).  The bytes and the
    commits are the same.

    The CPU work and the durable commits are timed apart: ``format`` spans
    (``write_format_s`` of ``stats``: a partition's rendering; for a dict
    first the bucketing, then each partition's sort and line formatting)
    and ``commit`` spans (``write_commit_s``: a partition's write, flush,
    fsync and rename)."""
    import os

    from dsi_tpu.obs import span as _span
    from dsi_tpu.parallel.merge import PackedPostings, PackedWordCounts
    from dsi_tpu.utils.atomicio import atomic_write

    packed = isinstance(result, (PackedWordCounts, PackedPostings))
    if packed:
        # the dict's bucketing raises on such a row; a mask would skip it
        outside = np.count_nonzero((result.parts < 0)
                                   | (result.parts >= n_reduce))
        if outside:
            raise ValueError(f"{outside} of {len(result)} words lie "
                             f"outside the {n_reduce} partitions")
    else:
        with _span("format", lane="host", stats=stats,
                   key="write_format_s", keys=len(result)):
            by_part: List[List[Tuple[str, int]]] = [
                [] for _ in range(n_reduce)]
            for w, (c, r) in result.items():
                by_part[r].append((w, c))
    paths = []
    for r in range(n_reduce):
        path = os.path.join(workdir, f"mr-out-{r}")
        with _span("format", lane="host", stats=stats,
                   key="write_format_s", part=r) as sp:
            if packed:
                data = result.render_partition(r)
                keys = data.count(b"\n")
            else:
                keys = len(by_part[r])
                data = "".join(f"{w} {c}\n" for w, c
                               in sorted(by_part[r])).encode("utf-8")
                by_part[r] = None  # the bucket's teardown belongs to it too
            sp.set(keys=keys)
        with _span("commit", lane="host", stats=stats,
                   key="write_commit_s", part=r, bytes=len(data)):
            with atomic_write(path, "wb") as f:
                f.write(data)
        paths.append(path)
    if stats is not None:
        for key in ("write_rows_packed", "write_rows_dict"):
            stats.setdefault(key, 0)
        stats["write_rows_packed" if packed
              else "write_rows_dict"] += len(result)
    return paths
