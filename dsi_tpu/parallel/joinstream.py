"""The join engine: a keyed table built on the device, a second table
streamed past it, and the matched rows' sums by a group key.

Pavlo et al., SIGMOD'09, the Join Task, one worker's share of it after the
partitioning by URL: ``SELECT sourceIP, AVG(pageRank), SUM(adRevenue) FROM
Rankings, UserVisits WHERE pageURL = destURL AND visitDate BETWEEN <first>
AND <last> GROUP BY sourceIP``.  Both tables are files of
newline-terminated rows of ``|``-delimited fields: a build row is
``pageURL|pageRank|...``, a probe row
``sourceIP|destURL|visitDate|adRevenue|...``.  ``plan/driver.py`` runs
:func:`table_join` as the one stage of the ``join`` chain and
``cli/planrun.py`` commits what it returns.

Two phases, each through the shared ``StepPipeline`` in chunks of
``chunk_bytes`` cut behind a newline (``streaming._row_batches``), the
programs ``ops/joink.py``'s:

* **build** (span ``join_build``): every build row through
  ``join_build_step`` into a table that stays on the device (donated,
  appended in place, never pulled), then ``join_build_order``: the table
  ordered by a hash of its keys and every pair of neighbours looked at.
  Two rows of one key fail the job (:class:`DuplicateKey`: ``pageURL`` is
  a primary key), with both rows' files and lines.  Two keys of one hash
  send the table through the ordering again under the next salt, so that
  a collision can never change the answer; after :data:`SALTS` orderings
  the job fails (:class:`HashCollision`) and commits nothing.
* **probe** (span ``join_probe``): every probe row through
  ``join_probe_step``; a step's table of groups (``sourceIP``, three
  64-bit sums as six ``uint32`` lanes: revenue in 10^-6 units, rank,
  rows) is packed behind its step (``shuffle._slice_pack``, the
  occupied prefix predicted as the stream engine predicts it), pulled and
  merged by ``merge.PackedCounts``.  Nothing that passes the window
  visits the host before it is a group's sums.

The row buffers are rungs, as the stream engine's (``ops/joink``'s
``BUILD_FRACS``, ``PROBE_FRACS``, ``WINDOW_FRACS``): a chunk that holds
more rows than its buffer, or more rows inside the window than the
window's, restarts its phase at the next rung, where it stays.  A row of
either table that cannot be read fails the job
(``ops/fieldsum.BadRow``, its file and line in the message).  There is no
host fallback and one device only: a join across a mesh needs the
exchange by key first (``plan/driver`` raises ``PlanHostPath`` for what
this engine cannot run).

The result is a ``merge.PackedWordCounts`` whose counts are ``[n, 3]``
(revenue units, rank sum, rows a key), rendered ``<key> <revenue with six
decimals> <rank sum / rows, six decimals, truncated>``.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from dsi_tpu.device.table import (_copy_to_host_async,
                                  _quiet_unusable_donation)
from dsi_tpu.obs import enqueued as _enqueued, metrics_scope, span as _span
from dsi_tpu.ops.fieldsum import DECIMALS, BadRow, file_rows, row_place
from dsi_tpu.ops.joink import (BUILD_FRACS, GROUP_LANES, PROBE_FRACS,
                               TABLE_COLS, VALUE_LANES, WINDOW_FRACS,
                               build_fn, join_build_order, probe_fn)
from dsi_tpu.parallel.merge import PackedCounts, PackedWordCounts
from dsi_tpu.parallel.pipeline import (BufferPool, StepPipeline,
                                       pipeline_depth)
from dsi_tpu.parallel.shuffle import _slice_pack, occupied_prefix
from dsi_tpu.parallel.streaming import _row_batches, stream_rows

#: Orderings of the table, each under a salt of its own, before two keys
#: of one hash fail the job.
SALTS = 4


class DuplicateKey(ValueError):
    """Two build rows hold one key: the job fails and commits nothing."""


class HashCollision(RuntimeError):
    """Two build keys share their hash under every salt: the table cannot
    be searched by it, and the job fails rather than answer wrongly."""


class _NextRung(Exception):
    """A chunk outgrew a buffer: the phase starts again at ``rung``."""

    def __init__(self, rung: tuple):
        super().__init__(rung)
        self.rung = rung


def _bad_row(what: str, row: int, paths: List[str]) -> BadRow:
    return BadRow(f"bad row: {what}", row).at(paths)


def _next_rung(fracs: tuple, frac: int, row: int, paths: List[str]) -> int:
    """The rung behind ``frac``; at the last, the rows are what cannot be
    read."""
    at = fracs.index(frac) + 1
    if at == len(fracs):
        raise _bad_row(f"rows of under {frac} bytes cannot be read", row,
                       paths)
    return fracs[at]


def table_join(build_paths: Sequence[str], probe_paths: Sequence[str],
               dates: Tuple[bytes, bytes], *, mesh: Mesh, n_reduce: int = 10,
               chunk_bytes: int = 1 << 20, depth: Optional[int] = None,
               stats: Optional[dict] = None) -> PackedWordCounts:
    """The join of the module docstring over ``mesh``'s one device;
    ``dates`` are the window's two ends, ten bytes each
    (``plan/graph.parse_dates``).  ``stats``
    receives the engine's scope when it ends, however it ends."""
    if int(mesh.devices.size) != 1:
        raise ValueError("the join runs on one device")
    chunk_bytes = int(chunk_bytes)
    depth = pipeline_depth(depth)
    device = mesh.devices.flat[0]
    build_paths, probe_paths = list(build_paths), list(probe_paths)
    sc = metrics_scope("join")
    sc.update({"depth": depth, "steps": 0, "step_pulls": 0,
               "pulls_early": 0, "pulls_late": 0, "upload_s": 0.0,
               "kernel_s": 0.0, "pull_s": 0.0, "merge_s": 0.0,
               "enqueue_s": 0.0, "order_s": 0.0, "read_s": 0.0,
               "finalize_s": 0.0, "join_build_s": 0.0, "join_probe_s": 0.0,
               "join_build_rows": 0, "join_build_bytes": 0,
               "join_build_steps": 0, "join_table_bytes": 0,
               "join_probe_rows": 0, "join_window_rows": 0,
               "join_matched_rows": 0, "join_groups": 0,
               "join_value_lanes": VALUE_LANES})
    pool = BufferPool((1, chunk_bytes), retain=2 * depth + 3)

    def pipeline(dispatch, finish, paths: List[str]) -> None:
        StepPipeline(depth=depth, dispatch=dispatch, finish=finish, stats=sc,
                     produce_key="batch_s", wait_key="batch_wait_s",
                     inflight_key="max_inflight_chunks",
                     thread_name="dsi-join-reader", engine="join").run(
            lambda: _row_batches(stream_rows(paths), 1, chunk_bytes,
                                 pool=pool))

    def upload(buf: np.ndarray, step: int):
        with _span("upload", stats=sc, key="upload_s", step=step):
            return jax.device_put(buf, device)

    def settled(scal) -> np.ndarray:
        with _span("kernel", stats=sc, key="kernel_s"):
            return np.asarray(scal)  # blocks until the step ran

    # ── build ──

    def build(frac: int):
        """The ordered table, its hashes and ``(rows, salt, n_reduce)``
        on the device, from a build at rung ``frac``."""
        t_cap = chunk_bytes // frac + 1
        # every row, the last step's landing block, whole tiles of rows
        capacity = -(-(build_rows + t_cap) // 4096) * 4096
        step_fn = build_fn(frac)
        resident = [jnp.zeros((capacity, TABLE_COLS), jnp.uint32,
                              device=device),
                    jnp.zeros((2,), jnp.int32, device=device)]
        sc.update({"join_build_rows": 0, "join_build_steps": 0,
                   "join_table_bytes": 0})
        step_rows: List[int] = []  # rows a retired step, for a row's place

        def dispatch(buf):
            step = sc["join_build_steps"]
            chunk = upload(buf, step)
            with _span("enqueue", lane="dispatch", stats=sc, step=step,
                       program="join_build_step"):
                with _quiet_unusable_donation():
                    *resident[:], scal = step_fn(*resident, chunk)
                _enqueued(scal)
                _copy_to_host_async(scal)
            sc["join_build_steps"] += 1
            return buf, scal

        def finish(record) -> None:
            buf, scal = record
            n_rows, first_bad, overflow, _fill = settled(scal).tolist()
            if first_bad < t_cap:
                raise _bad_row(
                    "fewer than 2 fields, a key of 0 or over 100 bytes or "
                    "not printable ASCII, or a rank that is not [0-9]{1,9}",
                    sc["join_build_rows"] + first_bad, build_paths)
            if overflow:
                raise _NextRung(_next_rung(
                    BUILD_FRACS, frac, sc["join_build_rows"] + t_cap,
                    build_paths))
            step_rows.append(n_rows)
            sc["join_build_rows"] += n_rows
            pool.give(buf)

        pipeline(dispatch, finish, build_paths)
        table, state = resident
        del resident[:]
        for salt in range(SALTS):
            with _span("order", lane="kernel", stats=sc, key="order_s",
                       rows=capacity, salt=salt):
                ordered, hashes, scal = join_build_order(
                    table, state, jnp.int32(salt))
                _enqueued(scal)
                twice, collisions, *pair = np.asarray(scal).tolist()
            if twice:
                first = np.concatenate([[0], np.cumsum(step_rows)])
                places = [row_place(build_paths, int(first[step]) + row)
                          for step, row in (pair[:2], pair[2:])]
                raise DuplicateKey(
                    f"{places[0]} and {places[1]} hold one key (and "
                    f"{twice - 1} more pairs of rows do): the build side's "
                    "key is a primary key; nothing is committed")
            if not collisions:
                break
            table = ordered
        else:
            raise HashCollision(
                f"{collisions} pairs of the build side's keys share their "
                f"hash under each of {SALTS} salts: the table cannot be "
                "searched; nothing is committed")
        sc["join_table_bytes"] = int(ordered.nbytes) + int(hashes.nbytes)
        meta = jnp.stack([state[0], jnp.int32(salt), jnp.int32(n_reduce)])
        return ordered, hashes, meta

    # ── probe ──

    def probe(table, hashes, meta, frac: int, window_frac: int):
        """The merged table of groups from a probe at the two rungs."""
        t_cap = chunk_bytes // frac + 1
        w_cap = min(t_cap, t_cap // window_frac + 1)
        step_fn = probe_fn(frac, window_frac)
        window = jax.device_put(np.frombuffer(
            b"".join(d.ljust(12, b"\0") for d in dates), ">u4").astype(
                np.uint32), device)
        sc.update({"steps": 0, "join_probe_rows": 0, "join_window_rows": 0,
                   "join_matched_rows": 0})
        acc = PackedCounts(stats=sc, decimals=DECIMALS)
        # the occupied prefix packed behind a step before anyone knows its
        # count, as the stream engine predicts it: it only ever rises
        state = {"mp": occupied_prefix(1, w_cap)}

        def dispatch(buf):
            step = sc["steps"]
            chunk = upload(buf, step)
            with _span("enqueue", lane="dispatch", stats=sc, step=step,
                       program="join_probe_step"):
                *tables, scal = step_fn(table, hashes, meta, window, chunk)
                packed = _slice_pack(*tables, mp=state["mp"])
                _enqueued(packed)
                _copy_to_host_async(scal)
                _copy_to_host_async(packed)
            sc["steps"] += 1
            return buf, scal, packed, tables

        def finish(record) -> None:
            buf, scal, packed, tables = record
            (groups, n_rows, first_bad, n_window, n_matched, overflow,
             window_overflow) = settled(scal)[0].tolist()
            if first_bad < t_cap:
                raise _bad_row(
                    "fewer than 4 fields, a sourceIP of 0 or over 16 bytes, "
                    "a destURL of 0 or over 100, either not printable "
                    "ASCII, a date that is not YYYY-MM-DD, or a value that "
                    "is not [0-9]{1,3}(.[0-9]{1,6})?",
                    sc["join_probe_rows"] + first_bad, probe_paths)
            if overflow:
                raise _NextRung((_next_rung(
                    PROBE_FRACS, frac, sc["join_probe_rows"] + t_cap,
                    probe_paths), window_frac))
            if window_overflow:  # its last rung holds every row
                raise _NextRung((frac, WINDOW_FRACS[
                    WINDOW_FRACS.index(window_frac) + 1]))
            sc["join_probe_rows"] += n_rows
            sc["join_window_rows"] += n_window
            sc["join_matched_rows"] += n_matched
            if groups:
                with _span("pull", stats=sc, key="pull_s") as sp:
                    early = groups <= packed.shape[1]
                    if not early:  # outgrew the predicted prefix: pack now
                        state["mp"] = occupied_prefix(groups, w_cap)
                        packed = _slice_pack(*tables, mp=state["mp"])
                    rows = np.asarray(packed)
                    sc["pulls_early" if early else "pulls_late"] += 1
                    sc["step_pulls"] += 1
                    sc["pull_bytes"] = sc.get("pull_bytes", 0) + rows.nbytes
                    sp.set(early=early)
                with _span("merge", stats=sc, key="merge_s"):
                    acc.add_packed_step(rows, [groups], GROUP_LANES)
            pool.give(buf)

        try:
            pipeline(dispatch, finish, probe_paths)
            with _span("finalize", lane="host", stats=sc) as sp:
                result = acc.finalize()
                sp.set(keys=len(result))
        finally:
            acc.close()
        return result

    try:
        with _span("join_build", lane="plan", stats=sc, key="join_build_s"):
            with _span("read", lane="host", stats=sc, key="read_s",
                       files=len(build_paths)):
                build_rows = sum(file_rows(path) for path in build_paths)
                sc["join_build_bytes"] = sum(
                    os.path.getsize(path) for path in build_paths)
            frac = BUILD_FRACS[0]
            while True:
                try:
                    table, hashes, meta = build(frac)
                    break
                except _NextRung as e:
                    frac = e.rung
        with _span("join_probe", lane="plan", stats=sc, key="join_probe_s"):
            rung = (PROBE_FRACS[0], WINDOW_FRACS[0])
            while True:
                try:
                    result = probe(table, hashes, meta, *rung)
                    break
                except _NextRung as e:
                    rung = e.rung
        sc["join_groups"] = len(result)
        return result
    finally:
        sc["batch_allocs"] = pool.allocs
        for key in ("batch_s", "batch_wait_s", "upload_s", "kernel_s",
                    "pull_s", "merge_s", "dispatch_s", "retire_s",
                    "enqueue_s", "order_s", "read_s", "finalize_s",
                    "join_build_s", "join_probe_s", "compact_s",
                    "compact_caller_s", "finalize_decode_s"):
            if key in sc:
                sc[key] = round(sc[key], 4)
        if stats is not None:
            stats.update(sc)
