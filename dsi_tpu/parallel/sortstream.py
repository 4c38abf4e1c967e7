"""The sort engine: fixed-width records ordered by key on one device.

OSDI'04 section 5.3's program, one worker's share of it: the input is
files of whole 100-byte records (``gensort``'s), the key bytes 0-9
compared as unsigned bytes, the record opaque otherwise; the answer is
every record, ordered by key, ties in input order (file order, then
offset), cut into ``n_reduce`` partitions by TeraSort's sampled range
partitioner and committed as ``mr-out-<r>``: partition ``r`` holds only
keys that are less than or equal to every key of partition ``r + 1``.

Three parts, which ``plan/driver.py`` runs as two stages and
``cli/planrun.py`` commits:

* :func:`sample_splits` (stage ``sample``, host side): ``n_sample`` keys
  (fewer where the input holds fewer) read at evenly spaced record
  ordinals ``j * n // m`` over all files, sorted; split point ``r`` is
  the sample's key at position ``r * m // n_reduce``.  A record's
  partition is the number of split points less than or equal to its key.
* :func:`range_sort` (stage ``range_sort``): the records go up in chunks
  of ``chunk_bytes`` (whole records, cut across file boundaries, the rest
  of the chunk padding) through the shared ``StepPipeline``; each step
  (``ops/sortk.py`` ``sort_ingest_step``) appends its records and their
  key lanes to a store that stays on the device for the whole job and
  returns the step's count of records a partition; after the last step
  has retired, ``sort_order`` orders the whole store in one pass: 5.4 M
  rows sort in 0.05 s and gather in 0.13 s on a v5e (PERF.md section 6,
  PR 45), so ten orderings of a partition each, with a pull beside each,
  would hide at most the ordering's 0.18 s behind half a second of pull
  and commit and pay ten programs' compiles.  The result is an
  :class:`OrderedStore`: the ordered rows, still on the device.
* :func:`write_sorted_output` pulls the ordered store in fixed blocks
  (``sort_pull_block``, the next blocks' copies started while this one is
  written) and cuts the stream of records into the partitions by their
  counts, each committed through ``atomic_write`` when its last record
  has been written.

Record bytes never visit the host between the step that took them up and
the pull of the ordered block.  There is no host fallback: a layout this
engine cannot run on the device is the caller's error (``plan/driver``
raises ``PlanHostPath``).
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from dsi_tpu.device.table import (_copy_to_host_async,
                                  _quiet_unusable_donation)
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.ops.sortk import (KEY_BYTES, ORDER_PASSES, PAST_END,
                               PULL_LANES, RECORD_BYTES, RECORD_WORDS,
                               chunk_words, ingest_fn, pull_block_fn,
                               sort_order)
from dsi_tpu.parallel.pipeline import (BufferPool, StepPipeline,
                                       pipeline_depth)
from dsi_tpu.utils.atomicio import atomic_write

#: TeraSort's ``mapreduce.terasort.partitions.sample``.
DEFAULT_SAMPLE = 100_000
#: Rows of one pulled block (13.1 MB) and blocks whose copies run ahead.
PULL_BLOCK_ROWS = 1 << 17
PULL_AHEAD = 2
#: Threads that flush, fsync and rename written partitions.
COMMIT_THREADS = 4


def record_counts(paths: Sequence[str]) -> List[int]:
    """Records a file; a file that is not whole records is an error."""
    counts = []
    for path in paths:
        size = os.path.getsize(path)
        if size % RECORD_BYTES:
            raise ValueError(f"{path}: {size} bytes is not a whole number "
                             f"of {RECORD_BYTES}-byte records")
        counts.append(size // RECORD_BYTES)
    return counts


def host_lanes(keys: np.ndarray) -> np.ndarray:
    """``uint8[m, 10]`` keys as the device's three big-endian lanes,
    ``uint32[m, 3]`` (``ops/sortk.key_lanes``)."""
    padded = np.zeros((len(keys), 12), np.uint8)
    padded[:, :KEY_BYTES] = keys
    return padded.view(">u4").astype(np.uint32)


def sample_splits(paths: Sequence[str], n_reduce: int,
                  n_sample: int = DEFAULT_SAMPLE,
                  stats: Optional[dict] = None) -> np.ndarray:
    """The ``n_reduce - 1`` split points, ``uint32[n_reduce - 1, 3]``, from
    a sample of the input's keys (module docstring): a function of the
    input alone."""
    with _span("sample", lane="host", stats=stats, key="sample_s",
               files=len(paths)) as sp:
        counts = record_counts(paths)
        n = sum(counts)
        m = min(int(n_sample), n)
        ordinals = (np.arange(m, dtype=np.int64) * n) // max(m, 1)
        keys = np.empty((m, KEY_BYTES), np.uint8)
        first = 0
        for path, count in zip(paths, counts):
            lo, hi = np.searchsorted(ordinals, [first, first + count])
            if hi > lo:
                records = np.memmap(path, np.uint8, "r").reshape(
                    count, RECORD_BYTES)
                keys[lo:hi] = records[ordinals[lo:hi] - first, :KEY_BYTES]
            first += count
        lanes = host_lanes(keys)
        order = np.lexsort((lanes[:, 2], lanes[:, 1], lanes[:, 0]))
        picks = (np.arange(1, n_reduce, dtype=np.int64) * m) // n_reduce
        splits = (lanes[order][picks] if m
                  else np.zeros((n_reduce - 1, 3), np.uint32))
        sp.set(keys=m)
    if stats is not None:
        stats["sort_sample_keys"] = m
        stats["sample_s"] = round(stats["sample_s"], 4)
    return np.ascontiguousarray(splits, np.uint32)


def record_chunks(paths: Sequence[str], chunk_records: int,
                  pool: BufferPool) -> Iterator[Tuple[np.ndarray, int]]:
    """``(buffer, records)`` of every chunk of the input: ``chunk_records``
    whole records, cut across file boundaries, the last chunk short."""
    want = chunk_records * RECORD_BYTES
    buf = pool.take()
    view = memoryview(buf).cast("B")
    fill = 0
    for path in paths:
        with open(path, "rb", buffering=0) as f:
            while True:
                got = f.readinto(view[fill:want])
                if not got:
                    break
                fill += got
                if fill == want:
                    yield buf, chunk_records
                    buf = pool.take()
                    view = memoryview(buf).cast("B")
                    fill = 0
    if fill % RECORD_BYTES:
        raise OSError(f"the input ended {fill % RECORD_BYTES} bytes into a "
                      "record: a file changed after its length was taken")
    if fill:
        yield buf, fill // RECORD_BYTES


class OrderedStore(NamedTuple):
    """A job's records in key order, on the device: ``ordered``
    (``uint32[capacity, 25]``, the first ``records`` rows the answer;
    None where there is no record) and ``counts``, the records a
    partition."""

    ordered: Optional[jax.Array]
    counts: np.ndarray
    records: int


def range_sort(paths: Sequence[str], splits: np.ndarray, *, mesh: Mesh,
               chunk_bytes: int = 1 << 20, depth: Optional[int] = None,
               stats: Optional[dict] = None) -> OrderedStore:
    """Order the records of ``paths`` on ``mesh``'s one device (module
    docstring).  ``stats`` receives the engine's scope when it ends."""
    if mesh.devices.size != 1:
        raise ValueError(f"the sort engine runs on one device, the mesh "
                         f"has {mesh.devices.size}")
    chunk_records = int(chunk_bytes) // RECORD_BYTES
    if chunk_records < 1:
        raise ValueError(f"chunk_bytes {chunk_bytes} holds no "
                         f"{RECORD_BYTES}-byte record")
    device = mesh.devices.flat[0]
    depth = pipeline_depth(depth)
    n_reduce = len(splits) + 1
    records = sum(record_counts(paths))
    steps = -(-records // chunk_records)
    # whole steps, rounded up to the pull's rows of 128 words; the rows
    # no step writes keep the lanes they start with and sort last
    capacity = -(-steps * chunk_records // PULL_LANES) * PULL_LANES
    sc = metrics_scope("sort")
    sc.update({"depth": depth, "steps": 0, "upload_s": 0.0,
               "kernel_s": 0.0, "enqueue_s": 0.0, "order_s": 0.0,
               "sort_records": records,
               "sort_order_passes": 0, "sort_resident_bytes": 0,
               "sort_partition_rows": [0] * n_reduce})
    counts = np.zeros(n_reduce, np.int64)
    ordered = None
    if records:
        pool = BufferPool((chunk_words(int(chunk_bytes)),),
                          retain=2 * depth + 3, dtype=np.uint32)
        head = chunk_records * RECORD_WORDS
        step_fn = ingest_fn(chunk_records)
        resident = [
            jnp.zeros((capacity, RECORD_WORDS), jnp.uint32, device=device),
            jnp.full((3, capacity), PAST_END, jnp.uint32, device=device)]
        splits_dev = jax.device_put(splits, device)
        sc["sort_resident_bytes"] = sum(int(a.nbytes) for a in resident)

        def dispatch(item):
            buf, n_valid = item
            step = sc["steps"]
            # where the chunk goes and how much of it is real: behind
            # its records, in the same put (ops/sortk.ingest_fn)
            buf[head:head + 2] = (step * chunk_records, n_valid)
            with _span("upload", stats=sc, key="upload_s", step=step):
                chunk = jax.device_put(buf, device)
            with _span("enqueue", lane="dispatch", stats=sc, step=step,
                       program="sort_ingest_step"):
                with _quiet_unusable_donation():
                    resident[0], resident[1], hist = step_fn(
                        resident[0], resident[1], chunk, splits_dev)
                _enqueued(hist)
                _copy_to_host_async(hist)
            sc["steps"] += 1
            return buf, n_valid, hist

        def finish(record) -> None:
            buf, n_valid, hist = record
            with _span("kernel", stats=sc, key="kernel_s"):
                hist_np = np.asarray(hist)  # blocks until the step ran
            if int(hist_np.sum()) != n_valid:
                raise RuntimeError(
                    f"host/device record-count disagreement: {n_valid} "
                    f"records went up, {hist_np.tolist()} were placed")
            counts[:] += hist_np
            pool.give(buf)

        pipe = StepPipeline(depth=depth, dispatch=dispatch, finish=finish,
                            stats=sc, produce_key="batch_s",
                            wait_key="batch_wait_s",
                            inflight_key="max_inflight_chunks",
                            thread_name="dsi-sort-reader", engine="sort",
                            # a 0.9 ms step behind a 28.6 us program: a
                            # look a step costs more than the program
                            # (PERF.md §6, PR 51)
                            count_ready=False)
        pipe.run(lambda: record_chunks(paths, chunk_records, pool))
        with _span("order", lane="kernel", stats=sc, key="order_s",
                   rows=capacity, passes=ORDER_PASSES):
            ordered = sort_order(*resident)
            _enqueued(ordered)
            del resident[:]
            ordered.block_until_ready()
        sc["sort_order_passes"] = ORDER_PASSES
        sc["batch_allocs"] = pool.allocs
    sc["sort_partition_rows"] = counts.tolist()
    sc["device_rows"] = [records]
    for key in ("batch_s", "batch_wait_s", "upload_s", "kernel_s",
                "dispatch_s", "retire_s", "enqueue_s", "order_s"):
        if key in sc:
            sc[key] = round(sc[key], 4)
    if stats is not None:
        stats.update(sc)
    return OrderedStore(ordered, counts, records)


def _blocks(store: OrderedStore, stats: Optional[dict]
            ) -> Iterator[memoryview]:
    """The answer's bytes in order, a pulled block at a time."""
    capacity = int(store.ordered.shape[0])
    rows = min(PULL_BLOCK_ROWS, capacity)
    cut = pull_block_fn(rows)
    # a start past the last whole block is clamped to it by the program:
    # the host then skips the rows it has had
    starts = [min(s, capacity - rows)
              for s in range(0, store.records, rows)]
    flying: list = []

    def fly(i: int) -> None:
        if i < len(starts):
            block = cut(store.ordered, np.int32(starts[i]))
            _enqueued(block)
            _copy_to_host_async(block)
            flying.append(block)

    for i in range(PULL_AHEAD):
        fly(i)
    done = 0
    for i, start in enumerate(starts):
        fly(i + PULL_AHEAD)
        block = flying.pop(0)
        with _span("pull", stats=stats, key="pull_s", block=i):
            with _span("d2h", lane="pull", stats=stats,
                       bytes=int(block.nbytes)):
                host = np.ascontiguousarray(block)
        if stats is not None:
            stats["pull_bytes"] = stats.get("pull_bytes", 0) + host.nbytes
        end = min(start + rows, store.records)
        yield memoryview(host).cast("B")[
            (done - start) * RECORD_BYTES:(end - start) * RECORD_BYTES]
        done = end


def write_sorted_output(store: OrderedStore, workdir: str = ".",
                        stats: Optional[dict] = None) -> List[str]:
    """Commit ``mr-out-<r>`` for every partition of ``store``: the ordered
    records pulled in blocks (``pull`` spans, ``pull_s`` and ``d2h_s`` of
    ``stats``) and cut by the partitions' counts.  A partition's writes
    are ``commit`` spans on this thread; its flush, fsync and rename are
    one more on a pool of ``COMMIT_THREADS`` threads, beside the next
    partitions' writes (ten commits of 54 MB one after the other took
    0.40-0.42 s on the chip's host, on four threads 0.18-0.23:
    ``scripts/sort_micro.py commit``).  ``write_commit_s`` is what the
    commits hold this thread: the writes, and the wait for the pool's
    last fsync.  Every partition is durable when this returns."""
    paths = [os.path.join(workdir, f"mr-out-{r}")
             for r in range(len(store.counts))]
    blocks = _blocks(store, stats) if store.records else iter(())
    view = memoryview(b"")

    def close(commit: contextlib.ExitStack, r: int) -> None:
        with _span("commit", lane="host", part=r, sync=True):
            commit.close()  # flush, fsync, rename

    closing: list = []
    with ThreadPoolExecutor(max_workers=COMMIT_THREADS,
                            thread_name_prefix="dsi-sort-commit") as pool:
        for r, path in enumerate(paths):
            left = int(store.counts[r]) * RECORD_BYTES
            with contextlib.ExitStack() as commit:
                f = commit.enter_context(atomic_write(path, "wb"))
                while left:
                    if not len(view):
                        view = next(blocks)
                    took = min(left, len(view))
                    with _span("commit", lane="host", stats=stats,
                               key="write_commit_s", part=r, bytes=took):
                        f.write(view[:took])
                    view, left = view[took:], left - took
                # written whole: the rest of its commit is the pool's
                closing.append(pool.submit(close, commit.pop_all(), r))
        with _span("commit", lane="host", stats=stats,
                   key="write_commit_s", parts=len(closing)):
            for job in closing:
                job.result()
    if stats is not None:
        for key in ("pull_s", "d2h_s", "write_commit_s"):
            stats[key] = round(stats.get(key, 0.0), 4)
    return paths
