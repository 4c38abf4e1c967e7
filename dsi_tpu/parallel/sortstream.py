"""The sort engine: fixed-width records ordered by key on the device, or
across the devices of a mesh, each the owner of one key range.

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
  For a mesh of ``n_dev`` devices the same sample gives ``n_dev - 1``
  device split points beside them, at positions ``d * m // n_dev``:
  device ``d`` owns the keys that count ``d`` device split points at or
  below them.  The two sets are independent: the answer is device 0's
  ordered records, then device 1's, and so on, one stream, cut by the
  partitions' counts, so a partition may lie across two devices and a
  device hold several partitions, and every device is loaded with its
  share of the sample whatever ``n_reduce`` is.
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
  On a mesh a step is one chunk a device (step ``t`` gives device ``s``
  chunk ``t * n_dev + s`` of the input's sequence) and one program over
  all of them (``sort_exchange_step``): every record goes to the device
  that owns its key through the mesh's ``all_to_all`` and is appended
  there, in (step, source device, row) order, which is input order.  A
  device's store is of a fixed shape: its share of the records by the
  sample, a sixteenth more (``STORE_SLACK``) and one step's landing
  block.  A key range that outgrows it fails the job
  (:class:`StoreOverfull`): no record is dropped, cut or sent to the
  host.  Then every device orders its own store.
* :func:`write_sorted_output` pulls the ordered stores in fixed blocks,
  device by device (``sort_pull_block``, the next blocks' copies started
  while this one is written) and cuts the stream of records into the
  partitions by their counts, each committed through ``atomic_write``
  when its last record has been written.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsi_tpu.device.table import (_copy_to_host_async,
                                  _quiet_unusable_donation)
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.ops.sortk import (KEY_BYTES, ORDER_PASSES, PAST_END,
                               PULL_LANES, RECORD_BYTES, RECORD_WORDS,
                               chunk_words, exchange_fn, ingest_fn,
                               mesh_order_fn, pull_block_fn, sort_order)
from dsi_tpu.parallel.pipeline import (BufferPool, StepPipeline,
                                       pipeline_depth)
from dsi_tpu.parallel.shuffle import AXIS
from dsi_tpu.utils.atomicio import atomic_write

#: TeraSort's ``mapreduce.terasort.partitions.sample``.
DEFAULT_SAMPLE = 100_000
#: Rows of one pulled block (13.1 MB) and blocks whose copies run ahead.
PULL_BLOCK_ROWS = 1 << 17
PULL_AHEAD = 2
#: Threads that flush, fsync and rename written partitions.
COMMIT_THREADS = 4
#: What a device's store on a mesh holds over its share of the records by
#: the sample: ten standard deviations of a quarter's error at 100,000
#: sampled keys.
STORE_SLACK = 1 / 16


class StoreOverfull(RuntimeError):
    """A device's key range holds more records than its store: the sample
    did not say so.  The job fails and commits nothing."""


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


class SplitPoints(NamedTuple):
    """What the sample gives: the ``n_reduce - 1`` partition split points
    (``uint32[n_reduce - 1, 3]``, a function of the input alone), the
    ``n_dev - 1`` device split points (``uint32[n_dev - 1, 3]``) and how
    many of the sample's keys each device owns by them."""

    partitions: np.ndarray
    devices: np.ndarray
    shares: Tuple[int, ...]


def sample_splits(paths: Sequence[str], n_reduce: int,
                  n_sample: int = DEFAULT_SAMPLE,
                  stats: Optional[dict] = None,
                  n_dev: int = 1) -> SplitPoints:
    """The split points of ``n_reduce`` partitions and of ``n_dev``
    devices from one sample of the input's keys (module docstring)."""
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
        lanes = lanes[np.lexsort((lanes[:, 2], lanes[:, 1], lanes[:, 0]))]

        def cut(n: int) -> Tuple[np.ndarray, np.ndarray]:
            picks = (np.arange(1, n, dtype=np.int64) * m) // n
            return picks, np.ascontiguousarray(
                lanes[picks] if m else np.zeros((n - 1, 3)), np.uint32)

        _, splits = cut(n_reduce)
        picks, device_splits = cut(n_dev)
        # a device owns a split point's key from the first of its equals
        runs = np.flatnonzero(np.concatenate(
            [[True], (lanes[1:] != lanes[:-1]).any(axis=1)])) if m else picks
        firsts = runs[np.searchsorted(runs, picks, side="right") - 1]
        shares = np.diff(np.concatenate([[0], firsts, [m]]))
        sp.set(keys=m)
    if stats is not None:
        stats["sort_sample_keys"] = m
        stats["sample_s"] = round(stats["sample_s"], 4)
    return SplitPoints(splits, device_splits, tuple(int(x) for x in shares))


def device_capacity(records: int, shares: Sequence[int],
                    chunk_records: int) -> int:
    """Rows of a device's store on a mesh: the fullest device's share of
    ``records`` by the sample, ``STORE_SLACK`` of it more, one step's
    landing block of ``chunk_records`` rows, rounded up to the pull's
    rows of 128 words."""
    share = -(-records * max(shares) // max(sum(shares), 1))
    rows = share + int(share * STORE_SLACK) + chunk_records
    return -(-rows // PULL_LANES) * PULL_LANES


def record_chunks(paths: Sequence[str], chunk_records: int,
                  pool: BufferPool, n_dev: int = 1
                  ) -> Iterator[Tuple[np.ndarray, List[int]]]:
    """``(buffer, records a row)`` of every step of the input: a row of
    the buffer a device (a flat buffer is one row), ``chunk_records``
    whole records a row, cut across file boundaries and filled in the
    input's order; the last step's last row is short, the rows behind it
    hold nothing."""
    want = chunk_records * RECORD_BYTES
    buf, views, taken, fill = None, [], [], 0
    for path in paths:
        with open(path, "rb", buffering=0) as f:
            while True:
                if buf is None:
                    buf = pool.take()
                    views = [memoryview(row).cast("B")
                             for row in buf.reshape(n_dev, -1)]
                got = f.readinto(views[len(taken)][fill:want])
                if not got:
                    break
                fill += got
                if fill == want:
                    taken.append(chunk_records)
                    fill = 0
                    if len(taken) == n_dev:
                        yield buf, taken
                        buf, taken = None, []
    if fill % RECORD_BYTES:
        raise OSError(f"the input ended {fill % RECORD_BYTES} bytes into a "
                      "record: a file changed after its length was taken")
    if fill:
        taken.append(fill // RECORD_BYTES)
    if taken:
        yield buf, taken + [0] * (n_dev - len(taken))


class OrderedStore(NamedTuple):
    """A job's records in key order, on the device: ``stores``, a device's
    ordered rows each (``uint32[capacity, 25]``, in device order; empty
    where there is no record), ``rows``, how many of a store's first rows
    are records (the answer is device 0's, then device 1's, ...), and
    ``counts``, the records a partition."""

    stores: Tuple[jax.Array, ...]
    rows: Tuple[int, ...]
    counts: np.ndarray
    records: int


def range_sort(paths: Sequence[str], points: SplitPoints, *, mesh: Mesh,
               chunk_bytes: int = 1 << 20, depth: Optional[int] = None,
               stats: Optional[dict] = None) -> OrderedStore:
    """Order the records of ``paths`` on ``mesh``'s devices (module
    docstring); ``points`` are the sample's for that many devices.
    ``stats`` receives the engine's scope when it ends."""
    n_dev = int(mesh.devices.size)
    if len(points.devices) != n_dev - 1:
        raise ValueError(f"{len(points.devices)} device split points for "
                         f"a mesh of {n_dev} devices")
    chunk_records = int(chunk_bytes) // RECORD_BYTES
    if chunk_records < 1:
        raise ValueError(f"chunk_bytes {chunk_bytes} holds no "
                         f"{RECORD_BYTES}-byte record")
    exchange = n_dev > 1
    depth = pipeline_depth(depth)
    n_reduce = len(points.partitions) + 1
    records = sum(record_counts(paths))
    steps = -(-records // (chunk_records * n_dev))
    # one device: whole steps, rounded up to the pull's rows of 128
    # words; the rows no step writes keep the lanes they start with and
    # sort last
    capacity = (device_capacity(records, points.shares, chunk_records)
                if exchange
                else -(-steps * chunk_records // PULL_LANES) * PULL_LANES)
    holds = capacity - chunk_records  # under the landing block
    sc = metrics_scope("sort")
    sc.update({"depth": depth, "steps": 0, "upload_s": 0.0,
               "kernel_s": 0.0, "enqueue_s": 0.0, "order_s": 0.0,
               "sort_records": records,
               "sort_order_passes": 0, "sort_resident_bytes": 0,
               "sort_partition_rows": [0] * n_reduce})
    if exchange:
        sc.update({"sort_devices": n_dev, "sort_device_capacity": capacity,
                   "sort_exchange_rows": 0, "sort_exchange_bytes": 0})
    counts = np.zeros(n_reduce, np.int64)
    held = np.zeros(n_dev, np.int64)
    stores: Tuple[jax.Array, ...] = ()
    if records:
        width = chunk_words(int(chunk_bytes))
        pool = BufferPool((n_dev, width) if exchange else (width,),
                          retain=2 * depth + 3, dtype=np.uint32)
        head = chunk_records * RECORD_WORDS
        if exchange:
            program = "sort_exchange_step"
            step_fn = exchange_fn(chunk_records, mesh)
            chunk_at, lanes_at, fill_at, whole = (
                NamedSharding(mesh, spec) for spec in (
                    P(AXIS, None), P(None, AXIS), P(AXIS), P()))
            splits_dev = (jax.device_put(points.partitions, whole),
                          jax.device_put(points.devices, whole))
        else:
            program = "sort_ingest_step"
            step_fn = ingest_fn(chunk_records)
            chunk_at = lanes_at = mesh.devices.flat[0]
            splits_dev = (jax.device_put(points.partitions, chunk_at),)
        # a device's store and its lanes, side by side over the mesh
        resident = [
            jnp.zeros((n_dev * capacity, RECORD_WORDS), jnp.uint32,
                      device=chunk_at),
            jnp.full((3, n_dev * capacity), PAST_END, jnp.uint32,
                     device=lanes_at)]
        sc["sort_resident_bytes"] = sum(int(a.nbytes) for a in resident)
        if exchange:  # and the records each holds, which the step keeps
            resident.append(jnp.zeros((n_dev,), jnp.int32, device=fill_at))

        def dispatch(item):
            buf, taken = item
            step = sc["steps"]
            # how much of a chunk is real (and, on one device, where it
            # goes): behind its records, in the same put (ops/sortk)
            if exchange:
                buf[:, head] = taken
            else:
                buf[head:head + 2] = (step * chunk_records, taken[0])
            with _span("upload", stats=sc, key="upload_s", step=step):
                chunk = jax.device_put(buf, chunk_at)
            with _span("enqueue", lane="dispatch", stats=sc, step=step,
                       program=program):
                with _quiet_unusable_donation():
                    *resident[:], tally = step_fn(*resident, chunk,
                                                  *splits_dev)
                _enqueued(tally)
                _copy_to_host_async(tally)
            sc["steps"] += 1
            return buf, step, sum(taken), tally

        def finish(record) -> None:
            buf, step, n_valid, tally = record
            with _span("kernel", stats=sc, key="kernel_s"):
                tally_np = np.asarray(tally)  # blocks until the step ran
            tally_np = tally_np.reshape(n_dev, -1)
            hist_np = tally_np[:, :n_reduce].sum(axis=0)
            if exchange:
                held[:] = tally_np[:, n_reduce + 1]
                full = int(held.argmax())
                if held[full] > holds:
                    raise StoreOverfull(
                        f"device {full}'s key range outgrew its store at "
                        f"step {step}: {int(held[full])} records, "
                        f"{int(held[full]) - holds} more than the {holds} "
                        f"it holds (its share of {records} records by "
                        f"the sample's {sum(points.shares)} keys and a "
                        f"sixteenth more); nothing is committed")
                sc["sort_exchange_rows"] += int(tally_np[:, n_reduce].sum())
            else:
                held[0] += n_valid
            counts[:] += hist_np
            if int(hist_np.sum()) != n_valid \
                    or int(held.sum()) != int(counts.sum()):
                raise RuntimeError(
                    f"host/device record-count disagreement: {n_valid} "
                    f"records went up, {hist_np.tolist()} were counted, "
                    f"the devices hold {held.tolist()} of "
                    f"{int(counts.sum())}")
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
        pipe.run(lambda: record_chunks(paths, chunk_records, pool, n_dev))
        with _span("order", lane="kernel", stats=sc, key="order_s",
                   rows=capacity, passes=ORDER_PASSES):
            order_fn = mesh_order_fn(mesh) if exchange else sort_order
            ordered = order_fn(*resident[:2])
            _enqueued(ordered)
            del resident[:]
            ordered.block_until_ready()
        shards = {s.device: s.data for s in ordered.addressable_shards}
        stores = tuple(shards[d] for d in mesh.devices.flat)
        sc["sort_order_passes"] = ORDER_PASSES
        sc["batch_allocs"] = pool.allocs
    sc["sort_partition_rows"] = counts.tolist()
    sc["device_rows"] = held.tolist()
    if exchange:
        sc["sort_exchange_bytes"] = sc["sort_exchange_rows"] * RECORD_BYTES
    for key in ("batch_s", "batch_wait_s", "upload_s", "kernel_s",
                "dispatch_s", "retire_s", "enqueue_s", "order_s"):
        if key in sc:
            sc[key] = round(sc[key], 4)
    if stats is not None:
        stats.update(sc)
    return OrderedStore(stores, tuple(held.tolist()), counts, records)


def _blocks(store: OrderedStore, stats: Optional[dict]
            ) -> Iterator[memoryview]:
    """The answer's bytes in order, a pulled block at a time: a device's
    records, then the next device's."""
    capacity = int(store.stores[0].shape[0])
    rows = min(PULL_BLOCK_ROWS, capacity)
    cut = pull_block_fn(rows)
    # (device, where the block starts, its rows that are the answer's
    # next).  A start past the last whole block is clamped to it by the
    # program: the host then skips the rows it has had
    plan = [(d, min(s, capacity - rows), s, min(s + rows, held))
            for d, held in enumerate(store.rows)
            for s in range(0, held, rows)]
    flying: list = []

    def fly(i: int) -> None:
        if i < len(plan):
            d, start = plan[i][:2]
            block = cut(store.stores[d], np.int32(start))
            _enqueued(block)
            _copy_to_host_async(block)
            flying.append(block)

    for i in range(PULL_AHEAD):
        fly(i)
    for i, (_, start, lo, hi) in enumerate(plan):
        fly(i + PULL_AHEAD)
        block = flying.pop(0)
        with _span("pull", stats=stats, key="pull_s", block=i):
            with _span("d2h", lane="pull", stats=stats,
                       bytes=int(block.nbytes)):
                host = np.ascontiguousarray(block)
        if stats is not None:
            stats["pull_bytes"] = stats.get("pull_bytes", 0) + host.nbytes
        yield memoryview(host).cast("B")[
            (lo - start) * RECORD_BYTES:(hi - start) * RECORD_BYTES]


def write_sorted_output(store: OrderedStore, workdir: str = ".",
                        stats: Optional[dict] = None) -> List[str]:
    """Commit ``mr-out-<r>`` for every partition of ``store``: the ordered
    records pulled in blocks, device by device (``pull`` spans, ``pull_s`` and ``d2h_s`` of
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
