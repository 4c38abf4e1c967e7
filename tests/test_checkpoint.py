"""Crash-resume parity for the checkpoint/restore subsystem (dsi_tpu/ckpt).

The contract under test is the strongest the engines can make: kill a
streaming engine at a named fault point (``DSI_FAULT_POINT``), resume
from the last durable checkpoint, and the FINAL output — word-count
table, grep histogram/top-k, indexer postings including per-word order
and df top-k — is bit-identical to an uninterrupted run.  The grid runs
in-process (``DSI_FAULT_MODE=raise``: the fault raises instead of
``os._exit`` so one interpreter can afford engine x fault-point x mode
cells inside the tier-1 budget); the CLI tests at the bottom use the
real thing — ``os._exit`` mid-engine in a subprocess, resume in a fresh
process — so the durable-write path is exercised by actual process
death, not a stand-in.
"""

import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

import numpy as np

from dsi_tpu.ckpt import (
    FAULT_EXIT,
    FAULT_POINTS,
    CheckpointMismatch,
    CheckpointPolicy,
    CheckpointStore,
    FaultInjected,
    checkpoint_every_default,
    reset_faults,
    skip_stream,
)
from dsi_tpu.parallel.grepstream import (
    grep_host_oracle,
    grep_streaming,
    indexer_streaming,
)
from dsi_tpu.parallel.shuffle import default_mesh
from dsi_tpu.parallel.streaming import wordcount_streaming
from dsi_tpu.parallel.tfidf import tfidf_sharded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    return default_mesh(4)


def _letters(i: int) -> str:
    return "".join(chr(97 + (i // 26 ** j) % 26) for j in range(3))


WC_WORDS = [_letters(i) for i in range(120)]
WC_TEXT = ((" ".join(WC_WORDS) + "\n") * 80).encode()  # ~38 KB, ~10 steps
WC_CHUNK = 1 << 10

_GREP_LINES = []
for _i in range(3000):
    _GREP_LINES.append(b"ab " * (_i % 5) + b"line" + str(_i).encode())
GREP_TEXT = b"\n".join(_GREP_LINES) + b"\n"  # ~45 KB, ~6 steps
GREP_CHUNK = 1 << 11

IDX_DOCS = [(" ".join(WC_WORDS[(3 * i) % 90:(3 * i) % 90 + 14])
             + " common words").encode() for i in range(20)]  # 5 waves

#: point -> which occurrence to kill at, tuned so a checkpoint exists
#: BEFORE the crash for every point (every=2): resume must restore real
#: state, not just start over.
_FAULT_AT = {"post-dispatch": 4, "mid-fold": 4, "pre-sync": 2,
             "post-ckpt": 2, "mid-capture": 2, "mid-commit": 2}

_BASE = {}


def _fault_env(monkeypatch, point, step):
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", point)
    monkeypatch.setenv("DSI_FAULT_STEP", str(step))


def _clear_fault(monkeypatch):
    for k in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
        monkeypatch.delenv(k, raising=False)


def _run_wc(ckpt=None, resume=False, dacc=False, depth=2, stats=None,
            async_=None, delta=None):
    reset_faults()
    return wordcount_streaming(
        [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
        u_cap=256, depth=depth, device_accumulate=dacc, sync_every=2,
        checkpoint_dir=ckpt, checkpoint_every=2, checkpoint_async=async_,
        checkpoint_delta=delta, resume=resume, pipeline_stats=stats)


def _run_grep(ckpt=None, resume=False, dacc=False, depth=2, stats=None,
              async_=None, delta=None):
    reset_faults()
    return grep_streaming(
        [GREP_TEXT], "ab", mesh=_mesh(), chunk_bytes=GREP_CHUNK,
        depth=depth, device_accumulate=dacc, sync_every=2, topk=8,
        checkpoint_dir=ckpt, checkpoint_every=2, checkpoint_async=async_,
        checkpoint_delta=delta, resume=resume, pipeline_stats=stats)


def _run_idx(ckpt=None, resume=False, dacc=False, depth=2, stats=None,
             async_=None, delta=None):
    reset_faults()
    return indexer_streaming(
        IDX_DOCS, mesh=_mesh(), n_reduce=10, u_cap=1 << 9, depth=depth,
        device_accumulate=dacc, sync_every=2, topk=8,
        checkpoint_dir=ckpt, checkpoint_every=2, checkpoint_async=async_,
        checkpoint_delta=delta, resume=resume, stats=stats)


_RUNNERS = {"wc": _run_wc, "grep": _run_grep, "idx": _run_idx}


def _baseline(engine, dacc):
    key = (engine, dacc)
    if key not in _BASE:
        _BASE[key] = _RUNNERS[engine](dacc=dacc)
        assert _BASE[key] is not None
    return _BASE[key]


def _crash_resume(engine, monkeypatch, tmp_path, point, dacc, depth=2,
                  async_=None, delta=None):
    """Run with a fault armed (expect it to fire), then resume and
    return the resumed result."""
    run = _RUNNERS[engine]
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, point, _FAULT_AT[point])
    with pytest.raises(FaultInjected):
        run(ckpt=ck, dacc=dacc, depth=depth, async_=async_, delta=delta)
    _clear_fault(monkeypatch)
    stats = {}
    res = run(ckpt=ck, resume=True, dacc=dacc, depth=depth, stats=stats,
              async_=async_, delta=delta)
    return res, stats


# ── the crash-resume parity grid ───────────────────────────────────────


@pytest.mark.parametrize("dacc", [False, True])
@pytest.mark.parametrize("point", FAULT_POINTS)
def test_wc_crash_resume_parity(monkeypatch, tmp_path, point, dacc):
    if point == "pre-sync" and not dacc:
        pytest.skip("pre-sync exists only on the device-accumulate path")
    res, stats = _crash_resume("wc", monkeypatch, tmp_path, point, dacc)
    assert res == _baseline("wc", dacc)
    if point in ("post-ckpt", "mid-fold"):
        # A checkpoint provably existed before the crash: the resume
        # must have restored it (sought past the cursor), not replayed
        # the stream from byte 0.
        assert stats["resume_cursor"] > 0


@pytest.mark.parametrize("dacc", [False, True])
@pytest.mark.parametrize("point", FAULT_POINTS)
def test_grep_crash_resume_parity(monkeypatch, tmp_path, point, dacc):
    if point == "pre-sync" and not dacc:
        pytest.skip("pre-sync exists only on the device-accumulate path")
    res, stats = _crash_resume("grep", monkeypatch, tmp_path, point, dacc)
    assert res == _baseline("grep", dacc)
    assert res == grep_host_oracle([GREP_TEXT], "ab", topk=8)


@pytest.mark.parametrize("dacc", [False, True])
@pytest.mark.parametrize("point", FAULT_POINTS)
def test_indexer_crash_resume_parity(monkeypatch, tmp_path, point, dacc):
    if point == "pre-sync" and not dacc:
        pytest.skip("pre-sync exists only on the device-accumulate path")
    res, stats = _crash_resume("idx", monkeypatch, tmp_path, point, dacc)
    base = _baseline("idx", dacc)
    # Postings equality includes per-word doc order; topk includes df
    # count ties broken by word.
    assert res == base


# ── async + incremental (ISSUE 8): the capture/commit split under fire ──


@pytest.mark.parametrize("engine", ["wc", "grep", "idx"])
@pytest.mark.parametrize("point", ("mid-capture", "mid-commit",
                                   "mid-fold"))
def test_async_delta_crash_resume_parity(monkeypatch, tmp_path, engine,
                                         point):
    """The async overlapped + incremental mode under the same bar as
    PR 5's sync path: kill during a capture, during a background
    commit, or at the torn-update instant, resume from whatever chain
    survived, and the final output is bit-identical.  A death
    mid-commit means the in-flight delta/image never produced a
    manifest — the previous complete chain must win."""
    res, stats = _crash_resume(engine, monkeypatch, tmp_path, point,
                               dacc=True, async_=True, delta=True)
    assert res == _baseline(engine, True)


@pytest.mark.parametrize("dacc", [False, True])
def test_wc_async_delta_host_and_device_paths(monkeypatch, tmp_path,
                                              dacc):
    res, stats = _crash_resume("wc", monkeypatch, tmp_path, "post-ckpt",
                               dacc=dacc, async_=True, delta=True)
    assert res == _baseline("wc", dacc)
    assert stats["resume_cursor"] > 0


@pytest.mark.parametrize("engine", ["grep", "idx"])
def test_async_delta_host_path_crash_resume(monkeypatch, tmp_path,
                                            engine):
    """The non-dacc delta spellings (grep's cand_mark watermark +
    newest-wins hist/totals, the indexer's HostDeltaLog wave rows)
    under a crash mid-chain — the device-path grid above never touches
    them."""
    res, stats = _crash_resume(engine, monkeypatch, tmp_path,
                               "mid-fold", dacc=False, async_=True,
                               delta=True)
    assert res == _baseline(engine, False)


def test_tfidf_async_delta_crash_resume_parity(monkeypatch, tmp_path):
    """The TF-IDF wave walk's async+delta chain (DevicePostings
    take_delta in dacc mode) across a mid-fold crash."""
    docs = IDX_DOCS
    base = tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9)
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 4)
    reset_faults()
    with pytest.raises(FaultInjected):
        tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9,
                      device_accumulate=True, sync_every=2,
                      checkpoint_dir=ck, checkpoint_every=1,
                      checkpoint_async=True, checkpoint_delta=True)
    _clear_fault(monkeypatch)
    reset_faults()
    stats = {}
    res = tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9,
                        device_accumulate=True, sync_every=2,
                        checkpoint_dir=ck, checkpoint_every=1,
                        checkpoint_async=True, checkpoint_delta=True,
                        resume=True, wave_stats=stats)
    assert res == base


def test_wc_delta_rebase_cadence_and_counters(tmp_path, monkeypatch):
    """Cadence-1 deltas with the default re-base window: the save
    counters decompose exactly (first save full, a full re-base every
    DSI_STREAM_CKPT_REBASE deltas), payload byte totals land in the
    stats, and the chain restores bit-identically."""
    monkeypatch.setenv("DSI_STREAM_CKPT_REBASE", "4")
    ck = str(tmp_path / "ck")
    stats = {}
    res = wordcount_streaming(
        [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
        u_cap=256, depth=2, device_accumulate=True, sync_every=2,
        checkpoint_dir=ck, checkpoint_every=1, checkpoint_async=True,
        checkpoint_delta=True, pipeline_stats=stats)
    assert res == _baseline("wc", True)
    saves, deltas = stats["ckpt_saves"], stats["ckpt_deltas"]
    assert saves >= 5 and 0 < deltas < saves
    # First save full, then <=4 deltas per full (the rebase window).
    fulls = saves - deltas
    assert fulls >= (saves + 4) // 5
    assert stats["ckpt_full_bytes"] > 0 and stats["ckpt_delta_bytes"] > 0
    # Append-heavy dacc stream: a delta is strictly smaller per save
    # than a full image.
    assert (stats["ckpt_delta_bytes"] / deltas
            < stats["ckpt_full_bytes"] / fulls)
    res2 = wordcount_streaming(
        [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
        u_cap=256, depth=2, device_accumulate=True, sync_every=2,
        checkpoint_dir=ck, checkpoint_every=1, checkpoint_async=True,
        checkpoint_delta=True, resume=True)
    assert res2 == _baseline("wc", True)


def test_rebase_one_means_every_save_full(tmp_path, monkeypatch):
    """The documented knob edge: ``DSI_STREAM_CKPT_REBASE=1`` really is
    every-save-full — zero deltas, flat restores — even with
    ``--ckpt-delta`` on."""
    monkeypatch.setenv("DSI_STREAM_CKPT_REBASE", "1")
    ck = str(tmp_path / "ck")
    stats = {}
    res = wordcount_streaming(
        [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
        u_cap=256, depth=2, device_accumulate=True, sync_every=2,
        checkpoint_dir=ck, checkpoint_every=1, checkpoint_delta=True,
        pipeline_stats=stats)
    assert res == _baseline("wc", True)
    assert stats["ckpt_saves"] >= 5 and stats["ckpt_deltas"] == 0
    assert not any(n.startswith("delta-") for n in os.listdir(ck))


def test_commit_worker_single_in_flight_barrier():
    """The writer's documented barrier: with ``max_pending=1`` a second
    submit must BLOCK while the first thunk is still RUNNING (a bounded
    queue alone would admit one running + one queued)."""
    import threading
    import time as _time

    from dsi_tpu.parallel.pipeline import CommitWorker

    w = CommitWorker(name="t-cw")
    release = threading.Event()
    running = threading.Event()

    def slow():
        running.set()
        release.wait(5.0)

    assert w.submit(slow) == 0.0
    running.wait(5.0)
    t0 = _time.perf_counter()
    done2 = []

    def second():
        done2.append(_time.perf_counter())

    def unblock():
        _time.sleep(0.15)
        release.set()

    threading.Thread(target=unblock, daemon=True).start()
    waited = w.submit(second)  # must block until slow() finishes
    assert waited >= 0.1, waited
    assert w.drain() >= 0.0
    assert done2
    w.shutdown()


def test_wc_delta_resume_across_forced_widen(monkeypatch, tmp_path):
    """A device-table widen straddling a delta chain: the forced tiny
    rung widens mid-stream (drain into the host accumulator + realloc),
    delta saves land around it, the crash loses the tail, and the chain
    restore (base drained + deltas re-applied) must still reproduce the
    uninterrupted output bit-identically."""
    monkeypatch.setenv("DSI_DEVICE_TABLE_CAP", "16")
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 6)
    stats = {}
    with pytest.raises(FaultInjected):
        _run_wc(ckpt=ck, dacc=True, stats=stats, async_=True, delta=True)
    assert stats.get("widens", 0) >= 1
    _clear_fault(monkeypatch)
    res = _run_wc(ckpt=ck, resume=True, dacc=True, async_=True,
                  delta=True)
    assert res == _baseline("wc", True)


def test_wc_delta_chain_resume_across_mesh_degrees(monkeypatch,
                                                   tmp_path):
    """A ``--mesh-shards`` degree change straddling a delta chain: the
    chain was saved by a mesh-sharded run, the resume runs host-merge
    (degree 0).  The chain restore already re-enters through the drain
    path, so the degree change rides the same machinery — output stays
    bit-identical."""
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 6)
    with pytest.raises(FaultInjected):
        reset_faults()
        wordcount_streaming(
            [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
            u_cap=256, depth=2, device_accumulate=True, sync_every=2,
            mesh_shards=2, checkpoint_dir=ck, checkpoint_every=1,
            checkpoint_async=True, checkpoint_delta=True)
    _clear_fault(monkeypatch)
    reset_faults()
    stats = {}
    res = wordcount_streaming(
        [WC_TEXT], mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK,
        u_cap=256, depth=2, device_accumulate=True, sync_every=2,
        mesh_shards=0, checkpoint_dir=ck, checkpoint_every=1,
        checkpoint_async=True, checkpoint_delta=True, resume=True,
        pipeline_stats=stats)
    assert res == _baseline("wc", True)
    assert "resharded_resume" in stats and stats["resharded_resume"] == 2


@pytest.mark.parametrize("point", ["mid-fold", "post-ckpt"])
def test_wc_crash_resume_parity_with_reader_pool(monkeypatch, tmp_path,
                                                 point):
    """Cursor exactness under the parallel ingest pool (ISSUE 13): a
    crash with readahead in flight — the pool has read blocks the
    batcher never consumed — must resume byte-identically from the
    durable cursor, even when the resume run uses a DIFFERENT reader
    count (batching is a pure function of the byte stream; the pool
    only changes scheduling)."""
    from dsi_tpu.utils.ioread import ParallelBlocks, serial_blocks

    half = len(WC_TEXT) // 2
    paths = []
    for i, piece in enumerate((WC_TEXT[:half], WC_TEXT[half:])):
        p = tmp_path / f"c{i}.txt"
        p.write_bytes(piece)
        paths.append(str(p))

    def pool_run(readers, **kw):
        reset_faults()
        # Small blocks so readahead is GENUINELY in flight at the crash
        # (several blocks resident in slots beyond the consumed cursor).
        return wordcount_streaming(
            ParallelBlocks(paths, block_bytes=2048, readers=readers),
            mesh=_mesh(), n_reduce=10, chunk_bytes=WC_CHUNK, u_cap=256,
            sync_every=2, checkpoint_every=2, **kw)

    # Baseline over the SAME byte stream (the pool inserts the
    # stream_files file separator, so WC_TEXT alone is not it).
    reset_faults()
    baseline = wordcount_streaming(
        [b"".join(serial_blocks(paths))], mesh=_mesh(), n_reduce=10,
        chunk_bytes=WC_CHUNK, u_cap=256)
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, point, _FAULT_AT[point])
    with pytest.raises(FaultInjected):
        pool_run(3, checkpoint_dir=ck)
    _clear_fault(monkeypatch)
    stats: dict = {}
    res = pool_run(2, checkpoint_dir=ck, resume=True,
                   pipeline_stats=stats)
    assert res == baseline
    assert stats["resume_cursor"] > 0  # restored, not replayed from 0
    assert stats["ingest_readers"] == 2


@pytest.mark.parametrize("depth", [1, 3])
def test_wc_crash_resume_parity_across_depths(monkeypatch, tmp_path,
                                              depth):
    res, _ = _crash_resume("wc", monkeypatch, tmp_path, "mid-fold",
                           dacc=True, depth=depth)
    assert res == _baseline("wc", True)


def test_wc_resume_across_forced_widen(monkeypatch, tmp_path):
    """A device-table widen straddling a checkpoint: the tiny forced
    rung widens mid-stream (drain into the host accumulator + realloc),
    a checkpoint lands between widens, the crash loses the tail, and
    resume must reconstruct the widened table image exactly."""
    monkeypatch.setenv("DSI_DEVICE_TABLE_CAP", "16")
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 6)
    stats = {}
    with pytest.raises(FaultInjected):
        _run_wc(ckpt=ck, dacc=True, stats=stats)
    assert stats.get("widens", 0) >= 1  # the forced rung actually widened
    _clear_fault(monkeypatch)
    res = _run_wc(ckpt=ck, resume=True, dacc=True)
    assert res == _baseline("wc", True)


def test_grep_resume_across_forced_topk_widen(monkeypatch, tmp_path):
    monkeypatch.setenv("DSI_DEVICE_TOPK_CAP", "8")
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 6)
    stats = {}
    with pytest.raises(FaultInjected):
        _run_grep(ckpt=ck, dacc=True, stats=stats)
    assert stats.get("widens", 0) >= 1
    _clear_fault(monkeypatch)
    res = _run_grep(ckpt=ck, resume=True, dacc=True)
    assert res == _baseline("grep", True)


def _retired_meta(monkeypatch, **retired):
    """Every checkpoint commit writes ``retired`` into its meta too, as
    the engines did before PR 28 (grep's ``l_cap``, a lane's ``rung``).
    Returns the list the committed kinds are appended to."""
    from dsi_tpu.ckpt import CheckpointWriter

    commit = CheckpointWriter.commit
    kinds = []

    def commit_with_retired(self, parts, meta, kind="full"):
        kinds.append(kind)
        return commit(self, parts, {**meta, **retired}, kind=kind)

    monkeypatch.setattr(CheckpointWriter, "commit", commit_with_retired)
    return kinds


@pytest.mark.parametrize("dacc", [False, True])
def test_grep_resume_reads_past_the_retired_l_cap(monkeypatch, tmp_path,
                                                  dacc):
    """A chain (base + deltas) whose metas carry the ``l_cap`` the
    engine wrote before PR 28, here the hard-bound rung a short-line
    stream had stuck at: the resume restores it and the output is
    bit-identical to an uninterrupted run."""
    kinds = _retired_meta(monkeypatch, l_cap=GREP_CHUNK + 1)
    run = _RUNNERS["grep"]
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 6)
    with pytest.raises(FaultInjected):
        run(ckpt=ck, dacc=dacc, delta=True)
    assert kinds[:2] == ["full", "delta"]  # a chain, not one image
    _clear_fault(monkeypatch)
    stats = {}
    res = run(ckpt=ck, resume=True, dacc=dacc, delta=True, stats=stats)
    assert stats["resume_cursor"] > 0 and "l_cap" not in stats
    assert res == _baseline("grep", dacc)


def test_grep_lane_resume_reads_past_the_retired_rung(monkeypatch,
                                                      tmp_path):
    """The packed serving lane's twin: a lane evicted with the ``rung``
    it checkpointed before PR 28 resumes from that chain, and its
    result equals the lane that ran through (and the oracle)."""
    from dsi_tpu.serve.pack import GrepLane, PackedGrepScheduler

    path = tmp_path / "in.txt"
    path.write_bytes(GREP_TEXT)
    job = {"tenant": "t", "pattern": "ab", "files": [str(path)]}
    sched = PackedGrepScheduler(mesh=default_mesh(1), chunk_bytes=GREP_CHUNK)

    def lane(name):
        return GrepLane(job, GREP_CHUNK, str(tmp_path / name),
                        checkpoint_every=2)

    def run(ln):
        while ln.runnable:
            sched.step([ln])
        return ln.finalize()

    through = run(lane("through"))
    assert through == grep_host_oracle([GREP_TEXT], "ab")
    kinds = _retired_meta(monkeypatch, rung=1)
    first = lane("evicted")
    for _ in range(5):
        sched.step([first])
    first.suspend()
    assert kinds and first.runnable  # saved mid-stream, input left
    resumed = lane("evicted")
    assert 0 < resumed.start_offset == first.cursor < len(GREP_TEXT)
    assert run(resumed) == through


def test_tfidf_crash_resume_parity(monkeypatch, tmp_path):
    """The wave-cursor checkpoint on the TF-IDF walk (the indexer grid
    above exercises the same machinery more heavily)."""
    docs = IDX_DOCS
    base = tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9)
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "mid-fold", 4)
    reset_faults()
    with pytest.raises(FaultInjected):
        tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9,
                      device_accumulate=True, sync_every=2,
                      checkpoint_dir=ck, checkpoint_every=2)
    _clear_fault(monkeypatch)
    reset_faults()
    res = tfidf_sharded(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9,
                        device_accumulate=True, sync_every=2,
                        checkpoint_dir=ck, checkpoint_every=2, resume=True)
    assert res == base


def _run_tfidf(ckpt=None, resume=False, dacc=False, depth=2, stats=None,
               async_=None, delta=None):
    reset_faults()
    return tfidf_sharded(
        IDX_DOCS, mesh=_mesh(), n_reduce=10, u_cap=1 << 9, depth=depth,
        device_accumulate=dacc, sync_every=2, checkpoint_dir=ckpt,
        checkpoint_every=2, checkpoint_async=async_,
        checkpoint_delta=delta, resume=resume, wave_stats=stats)


_RUNNERS["tfidf"] = _run_tfidf


def _stamp_grouper(ck: str) -> int:
    """Every manifest of ``ck`` as PR 43's engines wrote it on the CPU:
    the sticky rung's ``"grouper": "hash"`` beside its ``frac``."""
    import json

    from dsi_tpu.utils.atomicio import (read_bytes_verified,
                                        write_bytes_durable)

    stamped = 0
    for name in sorted(os.listdir(ck)):
        if name.startswith("manifest-") and name.endswith(".json"):
            path = os.path.join(ck, name)
            manifest = json.loads(read_bytes_verified(path))
            assert "frac" in manifest["meta"]
            assert "grouper" not in manifest["meta"]
            manifest["meta"]["grouper"] = "hash"
            write_bytes_durable(
                path, json.dumps(manifest, sort_keys=True).encode("utf-8"))
            stamped += 1
    return stamped


@pytest.mark.parametrize("engine", ["wc", "idx", "tfidf"])
def test_checkpoint_with_a_grouper_key_resumes(monkeypatch, tmp_path,
                                               engine):
    """A checkpoint whose meta still names a grouper (one written before
    the hash grouper went) restores and finishes to the uninterrupted
    run's result: the key is not read, and no other key moved."""
    run = _RUNNERS[engine]
    ck = str(tmp_path / "ck")
    _fault_env(monkeypatch, "post-ckpt", _FAULT_AT["post-ckpt"])
    with pytest.raises(FaultInjected):
        run(ckpt=ck)
    _clear_fault(monkeypatch)
    assert _stamp_grouper(ck) >= 1
    stats = {}
    assert run(ckpt=ck, resume=True, stats=stats) == _baseline(engine, False)
    assert stats["resume_cursor" if engine == "wc" else "resume_wave"] > 0


def test_resume_skips_confirmed_work(monkeypatch, tmp_path):
    """Resume is a restore + tail replay, not a rerun: the resumed run
    processes strictly fewer steps than the whole stream holds."""
    full_stats = {}
    _run_wc(stats=full_stats)
    res, stats = _crash_resume("wc", monkeypatch, tmp_path, "post-ckpt",
                               dacc=False)
    assert res == _baseline("wc", False)
    assert stats["resume_cursor"] > 0
    assert stats["steps"] < full_stats["steps"]


# ── store / policy / plumbing units ────────────────────────────────────


def test_checkpoint_policy_cadence_and_env(monkeypatch):
    p = CheckpointPolicy(3)
    for _ in range(2):
        p.note_step()
        assert not p.due()
    p.note_step()
    assert p.due()
    p.reset()
    assert not p.due()
    monkeypatch.setenv("DSI_STREAM_CKPT_EVERY", "7")
    assert checkpoint_every_default() == 7
    assert checkpoint_every_default(2) == 2
    monkeypatch.setenv("DSI_STREAM_CKPT_EVERY", "junk")
    assert checkpoint_every_default() == 32


def test_checkpoint_policy_time_trigger(monkeypatch):
    p = CheckpointPolicy(1000, secs=0.01)
    p.note_step()
    import time

    time.sleep(0.02)
    assert p.due()
    p.reset()
    assert not p.due()  # no step since reset: time alone never fires


def test_store_roundtrip_gc_and_fallback(tmp_path):
    st = CheckpointStore(str(tmp_path), "wc", {"n_dev": 4})
    for i in range(3):
        st.save({"a": np.arange(i + 1)}, {"cursor": 10 * i})
    # Last-two retention: seqs 1 is gone, 2 and 3 remain.
    names = sorted(os.listdir(str(tmp_path)))
    assert "manifest-000001.json" not in names
    assert "manifest-000002.json" in names and "manifest-000003.json" in names
    meta, arrays = st.load_latest()
    assert meta["cursor"] == 20 and np.array_equal(arrays["a"],
                                                   np.arange(3))
    # Corrupt the newest payload: the loader must fall back to seq 2.
    p3 = str(tmp_path / "state-000003.npz")
    with open(p3, "r+b") as f:
        f.seek(10)
        b = f.read(1)
        f.seek(10)
        f.write(bytes([b[0] ^ 0xFF]))
    meta, arrays = st.load_latest()
    assert meta["cursor"] == 10 and np.array_equal(arrays["a"],
                                                   np.arange(2))


def test_store_chain_gc_protects_live_base(tmp_path):
    """Chain-aware GC (ISSUE 8): last-two retention must never reap a
    base ``state-<seq>.npz`` that a live delta chain still references —
    with three deltas chained on one base, both retained restore points
    are deltas, and naive last-two would have deleted the base they
    both need."""
    st = CheckpointStore(str(tmp_path), "wc", {})
    st.save({"a": np.arange(3)}, {"cursor": 0})                   # seq 1
    for i in range(3):                                            # 2..4
        st.save_delta({"d": np.arange(i + 1)}, {"cursor": 10 * (i + 1)})
    names = sorted(os.listdir(str(tmp_path)))
    assert "state-000001.npz" in names          # the live chain's base
    assert "manifest-000001.json" in names
    meta, arrays, deltas = st.load_latest_chain()
    assert meta["cursor"] == 0 and len(deltas) == 3
    assert [m["cursor"] for m, _ in deltas] == [10, 20, 30]
    # A NEW full save starts a fresh chain; once two newer restore
    # points exist without references into the old chain, it goes.
    st.save({"a": np.arange(9)}, {"cursor": 99})                  # seq 5
    st.save({"a": np.arange(9)}, {"cursor": 100})                 # seq 6
    names = sorted(os.listdir(str(tmp_path)))
    assert "state-000001.npz" not in names
    assert not any(n.startswith("delta-") for n in names)


def test_store_torn_chain_falls_back_to_complete_chain(tmp_path):
    """A torn middle delta invalidates every seq above it: the walk
    falls back to the last COMPLETE chain (ultimately the bare base),
    never restores around a hole."""
    st = CheckpointStore(str(tmp_path), "wc", {})
    st.save({"a": np.arange(2)}, {"cursor": 0})                   # seq 1
    st.save_delta({"d": np.arange(1)}, {"cursor": 10})            # seq 2
    st.save_delta({"d": np.arange(2)}, {"cursor": 20})            # seq 3
    st.save_delta({"d": np.arange(3)}, {"cursor": 30})            # seq 4
    # Corrupt the MIDDLE delta's payload: seqs 3 and 4 now both sit on
    # a hole; the loader must fall back to base+delta2.
    p = str(tmp_path / "delta-000003.npz")
    with open(p, "r+b") as f:
        f.seek(5)
        b = f.read(1)
        f.seek(5)
        f.write(bytes([b[0] ^ 0xFF]))
    meta, arrays, deltas = st.load_latest_chain()
    assert len(deltas) == 1 and deltas[0][0]["cursor"] == 10
    # Remove that delta entirely (missing middle): same fallback.
    os.remove(p)
    meta, arrays, deltas = st.load_latest_chain()
    assert len(deltas) == 1 and deltas[0][0]["cursor"] == 10
    # Now tear delta 2 as well: only the bare base survives.
    os.remove(str(tmp_path / "delta-000002.npz"))
    meta, arrays, deltas = st.load_latest_chain()
    assert deltas == [] and meta["cursor"] == 0
    # load_latest (full-only view) agrees with the chain walk's base.
    m2, _ = st.load_latest()
    assert m2["cursor"] == 0


def test_store_gc_retains_fallback_below_unreadable_link(tmp_path):
    """GC must err toward retention when a chain walk cannot reach its
    base: with a mid-chain manifest gone, later saves keep chaining
    above the hole — everything at or below it must survive GC, because
    the loader's fallback is exactly the complete chain down there."""
    st = CheckpointStore(str(tmp_path), "wc", {})
    st.save({"a": np.arange(2)}, {"cursor": 0})           # seq 1
    st.save_delta({"d": np.arange(1)}, {"cursor": 10})    # seq 2
    st.save_delta({"d": np.arange(2)}, {"cursor": 20})    # seq 3
    st.save_delta({"d": np.arange(3)}, {"cursor": 30})    # seq 4
    os.remove(str(tmp_path / "manifest-000003.json"))     # the hole
    st.save_delta({"d": np.arange(4)}, {"cursor": 40})    # seq 5
    st.save_delta({"d": np.arange(5)}, {"cursor": 50})    # seq 6
    names = os.listdir(str(tmp_path))
    assert "state-000001.npz" in names
    assert "delta-000002.npz" in names
    meta, arrays, deltas = st.load_latest_chain()
    assert meta["cursor"] == 0
    assert len(deltas) == 1 and deltas[0][0]["cursor"] == 10


def test_host_delta_log_trims_and_bounds_like_device_logs():
    """The host-merge delta log mirrors the device rule: entries are
    trimmed to the occupied prefix AND copied (an AOT-shaped pull is
    full capacity; a view would pin it), and a window past
    ``max_steps`` invalidates THIS window only — ``take()`` returns
    None (the full-save fallback) and the next window is clean."""
    from dsi_tpu.ckpt import HostDeltaLog

    log = HostDeltaLog(max_steps=2)
    big = np.arange(2 * 100 * 5, dtype=np.uint32).reshape(2, 100, 5)
    log.append(big, np.array([3, 7]))
    entries = log.take()
    assert len(entries) == 1
    rows, nus = entries[0]
    assert rows.shape == (2, 7, 5)  # trimmed to max(nus), not capacity
    assert rows.base is None        # a copy, not a view pinning `big`
    assert np.array_equal(rows, big[:, :7])
    assert log.take() == []         # re-armed, empty window
    for _ in range(3):              # overflow the 2-step window
        log.append(big, np.array([1, 1]))
    assert log.take() is None       # invalid -> full-save fallback
    log.append(big, np.array([2, 2]))
    assert len(log.take()) == 1     # next window valid again
    log.append(big, np.array([1, 1]))
    log.reset()                     # a full save landed
    assert log.take() == []


def test_store_delta_refuses_empty_lineage(tmp_path):
    st = CheckpointStore(str(tmp_path), "wc", {})
    with pytest.raises(RuntimeError):
        st.save_delta({"d": np.arange(1)}, {"cursor": 1})


def test_store_refuses_other_job_and_resets(tmp_path):
    st = CheckpointStore(str(tmp_path), "wc", {"chunk": 1024})
    st.save({"a": np.zeros(1)}, {"cursor": 1})
    other = CheckpointStore(str(tmp_path), "wc", {"chunk": 2048})
    with pytest.raises(CheckpointMismatch):
        other.load_latest()
    other.reset()
    assert st.load_latest() is None  # lineage gone
    assert not [n for n in os.listdir(str(tmp_path))
                if n.startswith(("manifest-", "state-"))]


def test_store_torn_manifest_is_invisible(tmp_path):
    st = CheckpointStore(str(tmp_path), "wc", {})
    st.save({"a": np.ones(2)}, {"cursor": 5})
    # A manifest whose sidecar disagrees (torn write) must not load.
    st.save({"a": np.ones(3)}, {"cursor": 9})
    with open(str(tmp_path / "manifest-000002.json"), "ab") as f:
        f.write(b" ")
    meta, _ = st.load_latest()
    assert meta["cursor"] == 5


def test_skip_stream_seeks_exactly():
    blocks = [b"abc", b"", b"defg", b"hi"]
    assert b"".join(skip_stream(blocks, 0)) == b"abcdefghi"
    assert b"".join(skip_stream(blocks, 4)) == b"efghi"
    assert b"".join(skip_stream(blocks, 9)) == b""
    assert b"".join(skip_stream(blocks, 50)) == b""


def test_atomicio_durable_write_verify_and_reap(tmp_path):
    from dsi_tpu.utils.atomicio import (read_bytes_verified,
                                        reap_tmp_files,
                                        write_bytes_durable)

    p = str(tmp_path / "blob")
    crc = write_bytes_durable(p, b"hello world")
    assert os.path.exists(p + ".crc32")
    assert read_bytes_verified(p) == b"hello world"
    import zlib

    assert crc == zlib.crc32(b"hello world")
    with open(p, "ab") as f:  # tamper: sidecar now disagrees
        f.write(b"!")
    assert read_bytes_verified(p) is None
    assert read_bytes_verified(str(tmp_path / "absent")) is None
    open(str(tmp_path / ".tmp-orphan.x"), "w").close()
    assert reap_tmp_files(str(tmp_path)) == 1
    assert not os.path.exists(str(tmp_path / ".tmp-orphan.x"))


def test_fault_point_counts_per_point(monkeypatch):
    from dsi_tpu.ckpt import fault_point

    reset_faults()
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", "mid-fold")
    monkeypatch.setenv("DSI_FAULT_STEP", "2")
    fault_point("post-dispatch")  # other points never consume the budget
    fault_point("mid-fold")
    fault_point("post-dispatch")
    with pytest.raises(FaultInjected):
        fault_point("mid-fold")
    reset_faults()


def test_device_snapshot_roundtrip_byte_equal_drain(tmp_path):
    """Seeded-random snapshot round trip (the hypothesis twin lives in
    tests/test_property_fuzz.py and runs where hypothesis is
    installed): arbitrary service states, imaged by checkpoint_state,
    pushed through the real durable store, restored into a fresh
    service, must drain byte-equal."""
    from dsi_tpu.device import DeviceHistogram, DevicePostings, DeviceTable

    rng = np.random.default_rng(7)
    mesh = default_mesh(8)
    n_dev, cap, kk = 8, 8, 2

    class Capture:
        def __init__(self):
            self.rows = []

        def add(self, keys, lens, cnts, parts):
            self.rows.append((np.array(keys), np.array(lens),
                              np.array(cnts), np.array(parts)))

    for trial in range(4):
        nrows = rng.integers(0, cap + 1, n_dev)
        img = {"keys": rng.integers(0, 2 ** 32, (n_dev, cap, kk),
                                    dtype=np.uint32),
               "lens": rng.integers(0, 9, (n_dev, cap), dtype=np.int32),
               "cnts": rng.integers(0, 2 ** 63, (n_dev, cap)).astype(
                   np.uint64),
               "parts": rng.integers(0, 10, (n_dev, cap), dtype=np.int32),
               "tn": nrows.astype(np.int32), "nrows": nrows}
        store = CheckpointStore(str(tmp_path / f"t{trial}"), "fuzz", {})
        a1, a2 = Capture(), Capture()
        t1 = DeviceTable(mesh, kk=kk, cap=cap, acc=a1)
        t1.restore_state(img)
        store.save(t1.checkpoint_state(), {})
        _, arrays = store.load_latest()
        t2 = DeviceTable(mesh, kk=kk, cap=cap, acc=a2)
        t2.restore_state(arrays)
        t1.close()
        t2.close()
        assert len(a1.rows) == len(a2.rows)
        for ra, rb in zip(a1.rows, a2.rows):
            for x, y in zip(ra, rb):
                assert np.array_equal(x, y)

    # Postings buffer: random committed prefix, order must survive.
    width = kk + 4
    m = 5
    img = {"buf": rng.integers(0, 2 ** 32, (n_dev, m, width),
                               dtype=np.uint32),
           "nrows": rng.integers(0, m + 1, n_dev),
           "cap": np.array(cap, dtype=np.int64)}
    sink1, sink2 = [], []
    p1 = DevicePostings(mesh, width=width, cap=cap,
                        sink=lambda r: sink1.append(np.array(r)))
    p1.restore_state(img)
    st = p1.checkpoint_state()
    store = CheckpointStore(str(tmp_path / "pb"), "fuzz", {})
    store.save({"buf": st["buf"], "nrows": st["nrows"]},
               {"cap": int(st["cap"])})
    meta, arrays = store.load_latest()
    p2 = DevicePostings(mesh, width=width, cap=cap,
                        sink=lambda r: sink2.append(np.array(r)))
    p2.restore_state({"buf": arrays["buf"], "nrows": arrays["nrows"],
                      "cap": meta["cap"]})
    p1.close()
    p2.close()
    assert len(sink1) == len(sink2)
    assert all(np.array_equal(a, b) for a, b in zip(sink1, sink2))

    # Histogram vector.
    hstate = rng.integers(0, 2 ** 63, (n_dev, 6)).astype(np.uint64)
    h1 = DeviceHistogram(mesh, slots=6)
    h1.restore_state({"hist": hstate})
    store = CheckpointStore(str(tmp_path / "h"), "fuzz", {})
    store.save(h1.checkpoint_state(), {})
    _, arrays = store.load_latest()
    h2 = DeviceHistogram(mesh, slots=6)
    h2.restore_state(arrays)
    assert np.array_equal(h1.close(), h2.close())


# ── the real thing: process death + fresh-process resume ───────────────


def _cli_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def test_cli_wcstream_real_crash_resume(tmp_path):
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(WC_TEXT * 3)  # ~115 KB: ~7 steps at 16 KB/step
    env = _cli_env(tmp_path)
    ck = str(tmp_path / "ck")
    wd = str(tmp_path / "wd")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.wcstream", "--devices", "2",
           "--chunk-bytes", "8192", "--checkpoint-dir", ck,
           "--checkpoint-every", "1", "--workdir", wd, str(corpus)]
    env_crash = dict(env)
    env_crash.update({"DSI_FAULT_POINT": "mid-fold", "DSI_FAULT_STEP": "3"})
    p = subprocess.run(cmd, env=env_crash, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == FAULT_EXIT, p.stderr[-2000:]
    assert any(n.startswith("manifest-") for n in os.listdir(ck))
    p = subprocess.run(cmd + ["--resume", "--check"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr


def test_cli_wcstream_async_delta_real_crash_resume(tmp_path):
    """REAL ``os._exit`` during an in-flight ASYNC snapshot
    (``mid-commit`` fires on the background writer thread after the
    capture materialized, before the store write): the half-captured
    save must be invisible — no torn manifest — and the fresh-process
    resume walks the surviving delta chain to bit-identical output."""
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(WC_TEXT * 3)
    env = _cli_env(tmp_path)
    ck = str(tmp_path / "ck")
    wd = str(tmp_path / "wd")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.wcstream", "--devices", "2",
           "--chunk-bytes", "8192", "--device-accumulate",
           "--sync-every", "2", "--checkpoint-dir", ck,
           "--checkpoint-every", "1", "--ckpt-async", "--ckpt-delta",
           "--workdir", wd, str(corpus)]
    env_crash = dict(env)
    env_crash.update({"DSI_FAULT_POINT": "mid-commit",
                      "DSI_FAULT_STEP": "3"})
    p = subprocess.run(cmd, env=env_crash, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == FAULT_EXIT, p.stderr[-2000:]
    names = os.listdir(ck)
    # Two commits landed before the third died mid-write: a base and a
    # delta chained on it survive, and nothing half-written is visible.
    assert any(n.startswith("state-") for n in names), names
    assert any(n.startswith("delta-") for n in names), names
    p = subprocess.run(cmd + ["--resume", "--check"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr


@pytest.mark.slow
def test_cli_grepstream_real_crash_resume(tmp_path):
    corpus = tmp_path / "g.txt"
    corpus.write_bytes(GREP_TEXT * 4)
    env = _cli_env(tmp_path)
    ck = str(tmp_path / "ck")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.grepstream", "--devices",
           "2", "--pattern", "ab", "--chunk-bytes", "16384",
           "--device-accumulate", "--sync-every", "2",
           "--checkpoint-dir", ck, "--checkpoint-every", "1",
           str(corpus)]
    env_crash = dict(env)
    env_crash.update({"DSI_FAULT_POINT": "mid-fold", "DSI_FAULT_STEP": "3"})
    p = subprocess.run(cmd, env=env_crash, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == FAULT_EXIT, p.stderr[-2000:]
    p = subprocess.run(cmd + ["--resume", "--check"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "parity OK" in p.stderr
