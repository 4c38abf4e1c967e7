"""``planrun --chain indexer`` reads its documents ahead of the walk.

``utils/ioread.ReadAheadDocs`` stands where the list of whole documents
stood: the lengths before the first stage, the bytes from a pool of
reader threads in the order the waves will ask.  Here: the sequence
against the resident list (a mixed collection, asked out of order and
twice), a file that is removed, grown or cut after its length was taken
(the job fails, nothing is committed, packed and unpacked), no reader
thread alive after ``planrun.main`` returns, the counters that say how
the read-ahead engaged, the plan's signature over the lazy sequence (a
manifest written over a list resumes), and a run without
``--checkpoint-dir`` that asks for no document before the walk does.
"""

import ast
import contextlib
import glob
import io
import os
import re
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.ckpt import FaultInjected, reset_faults
from dsi_tpu.cli import planrun as cli
from dsi_tpu.obs import registry
from dsi_tpu.plan import indexer_join_plan, run_plan
from dsi_tpu.plan.stagehost import build_plan
from dsi_tpu.utils import ioread
from dsi_tpu.utils.ioread import ReadAheadDocs

CHUNK = 4096
PACK = ("--pack-docs", "--chunk-bytes", str(CHUNK))
READ_KEYS = ("read_s", "read_wait_s", "read_ahead_hits", "read_docs",
             "read_threads")


def _collection(seed=5, n=70):
    """An empty file, one over ``--chunk-bytes``, many small ones."""
    rng = np.random.default_rng(seed)
    vocab = ["".join(chr(97 + int(c)) for c in rng.integers(0, 26, size))
             for size in rng.integers(2, 9, 300)]
    docs = [" ".join(vocab[int(p) % 300] for p in rng.zipf(1.3, int(k)))
            .encode() + b"\n" for k in rng.integers(1, 200, n)]
    docs[2] = b""
    docs[9] = b" ".join(vocab[int(p) % 300].encode()
                        for p in rng.zipf(1.3, 1400))
    assert len(docs[9]) > CHUNK
    return docs


@pytest.fixture(scope="module")
def collection(tmp_path_factory):
    directory = tmp_path_factory.mktemp("docs")
    docs = _collection()
    paths = [str(directory / f"d{i:05d}.txt") for i in range(len(docs))]
    for path, data in zip(paths, docs):
        with open(path, "wb") as f:
            f.write(data)
    return paths, docs


def _readers():
    return [t for t in threading.enumerate()
            if t.name.startswith("dsi-doc-reader")]


def _count_reads(monkeypatch):
    """The ordinals ``ReadAheadDocs`` reads from their files from here
    on, one entry a document a read."""
    reads = []
    real_read = ReadAheadDocs._read

    def counting_read(self, run):
        reads.extend(run)                           # extend is atomic
        return real_read(self, run)

    monkeypatch.setattr(ReadAheadDocs, "_read", counting_read)
    return reads


@pytest.fixture(params=["native", "python"])
def reader(request, monkeypatch):
    """Both ways a run of documents is read and measured:
    ``native.read_files`` / ``native.file_lengths``, and the ``os``
    calls that stand in where there is no library."""
    if request.param == "python":
        monkeypatch.setattr(ioread.native, "read_files",
                            lambda *a: None)
        monkeypatch.setattr(ioread.native, "file_lengths",
                            lambda *a: None)
    elif not ioread.native.available():
        pytest.skip("no native library here")
    return request.param


# ── the sequence ───────────────────────────────────────────────────────


def test_bytes_and_lengths_are_the_resident_lists(collection, reader):
    paths, docs = collection
    lazy = ReadAheadDocs(paths)
    try:
        assert len(lazy) == len(docs)
        assert lazy.lengths == [len(d) for d in docs]
        assert not _readers()            # lengths alone start no thread
        assert list(lazy) == docs        # iteration ends, as a list's
        assert lazy[-1] == docs[-1]
        with pytest.raises(IndexError):
            lazy[len(docs)]
        assert lazy.stats["read_docs"] == len(docs)
        assert lazy.stats["read_threads"] == ioread.DOC_READ_THREADS
    finally:
        lazy.close()
    assert not _readers()
    assert lazy[9] == docs[9]            # what is held stays readable


def test_out_of_order_and_twice_is_one_read_a_document(collection, reader,
                                                       monkeypatch):
    paths, docs = collection
    reads = _count_reads(monkeypatch)
    lazy = ReadAheadDocs(paths)
    try:
        order = list(np.random.default_rng(3).permutation(len(docs)))
        lazy.read_ahead(order[::-1])     # the pool walks the other way
        for i in order + order:
            assert lazy[int(i)] == docs[int(i)]
    finally:
        lazy.close()
    assert sorted(reads) == list(range(len(docs)))
    st = lazy.stats
    assert st["read_ahead_hits"] <= st["read_docs"] == len(docs)
    assert st["read_wait_s"] >= 0.0


def test_many_callers_race_the_pool_and_every_document_is_read_once(
        collection, reader, monkeypatch):
    """More threads than cores, a short switch interval: a lost claim
    would read a document twice or leave a caller waiting for ever."""
    paths, docs = collection
    reads = _count_reads(monkeypatch)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    lazy = ReadAheadDocs(paths)
    wrong = []

    def caller(seed):
        for i in np.random.default_rng(seed).permutation(len(docs)):
            if lazy[int(i)] != docs[int(i)]:
                wrong.append(int(i))

    try:
        callers = [threading.Thread(target=caller, args=(s,))
                   for s in range(2 * (os.cpu_count() or 4))]
        for t in callers:
            t.start()
        for t in callers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in callers)
    finally:
        sys.setswitchinterval(old)
        lazy.close()
    assert not wrong and sorted(reads) == list(range(len(docs)))
    assert lazy.stats["read_docs"] == len(docs)


@pytest.mark.parametrize("change", ["removed", "grown", "cut"])
def test_a_file_that_changed_after_its_length_was_taken(tmp_path, reader,
                                                        change):
    paths = []
    for i in range(12):
        paths.append(str(tmp_path / f"d{i}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(b"some words here " * (i + 1))
    lazy = ReadAheadDocs(paths)
    if change == "removed":
        os.remove(paths[7])
    else:
        with open(paths[7], "r+b") as f:
            f.truncate(5) if change == "cut" else f.seek(0, 2)
            f.write(b"more")
    try:
        with pytest.raises(OSError) as e:
            for i in range(12):
                lazy[i]
        assert "d7.txt" in str(e.value)
    finally:
        lazy.close()
    assert not _readers()


@pytest.mark.parametrize("max_dirs", [64, 1, 0])
def test_files_are_named_from_their_directories(tmp_path, monkeypatch,
                                                reader, max_dirs):
    """Three directories, one of them the working directory named by
    nothing; with room for fewer directories the rest go by path; no
    descriptor is left open."""
    monkeypatch.setattr(ioread, "_MAX_DIR_FDS", max_dirs)
    monkeypatch.chdir(tmp_path)
    paths, docs = [], []
    for i in range(9):
        sub = ("", "a", os.path.join(str(tmp_path), "b"))[i % 3]
        os.makedirs(sub or ".", exist_ok=True)
        paths.append(os.path.join(sub, f"d{i}.txt"))
        docs.append(b"word " * i)
        with open(paths[-1], "wb") as f:
            f.write(docs[-1])
    before = set(os.listdir("/proc/self/fd"))
    lazy = ReadAheadDocs(paths)
    try:
        assert len(lazy._dirs) == min(max_dirs, 3)
        assert lazy.lengths == list(map(len, docs))
        assert list(lazy) == docs
    finally:
        lazy.close()
    assert set(os.listdir("/proc/self/fd")) == before
    with pytest.raises(OSError):
        ReadAheadDocs(paths + [os.path.join("nowhere", "d.txt")])
    assert set(os.listdir("/proc/self/fd")) == before


@pytest.mark.parametrize("change,errno_", [
    ("none", None), ("removed", 2), ("grown", 0), ("cut", 0), ("empty", 0)])
def test_the_native_reader_names_the_first_file_that_is_not_its_length(
        tmp_path, change, errno_):
    """``native.read_files``: the bytes one file behind the other, or
    the first file that could not be opened (its errno) or was another
    length than it was given (0); by name from a directory and by path."""
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library here")
    docs = [b"one two three\n", b"", b"four " * 3000, b"five"]
    for i, data in enumerate(docs):
        with open(tmp_path / f"f{i}", "wb") as f:
            f.write(data)
    if change == "removed":
        os.remove(tmp_path / "f2")
    elif change != "none":
        with open(tmp_path / "f2", "r+b") as f:
            f.truncate({"cut": 7, "empty": 0}.get(change, len(docs[2])))
            f.seek(0, 2)
            f.write(b"!" if change == "grown" else b"")
    dfd = os.open(tmp_path, os.O_RDONLY)
    try:
        names = [b"f0", b"f1", os.fsencode(str(tmp_path / "f2")), b"f3"]
        data, bad, err = native.read_files(
            names, [dfd, dfd, -1, dfd], [len(d) for d in docs])
    finally:
        os.close(dfd)
    if errno_ is None:
        assert (bad, err) == (-1, 0)
        assert bytes(data[:-1]) == b"".join(docs)
    else:
        assert (bad, err) == (2, errno_)


def test_native_lengths_name_the_first_file_that_has_none(tmp_path):
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library here")
    for i in range(4):
        with open(tmp_path / f"f{i}", "wb") as f:
            f.write(b"x" * (i * 5000))
    dfd = os.open(tmp_path, os.O_RDONLY)
    try:
        names = [b"f0", os.fsencode(str(tmp_path / "f1")), b"f2", b"f3"]
        assert native.file_lengths(names, [dfd, -1, dfd, dfd]) == (
            [0, 5000, 10000, 15000], -1, 0)
        os.remove(tmp_path / "f2")
        _, bad, err = native.file_lengths(names, [dfd, -1, dfd, dfd])
        assert (bad, err) == (2, 2)
    finally:
        os.close(dfd)


def test_a_file_missing_at_the_start_has_no_length(tmp_path, reader):
    present = tmp_path / "here.txt"
    present.write_bytes(b"words")
    with pytest.raises(OSError) as e:
        ReadAheadDocs([str(present), str(tmp_path / "nothing.txt")])
    assert "nothing.txt" in str(e.value)


# ── the job ────────────────────────────────────────────────────────────


def _run(paths, workdir, *flags):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", "1", "--nreduce",
                       "10", "--u-cap", "1024", "--stats", "--workdir",
                       workdir, *flags, *paths])
    text = err.getvalue()
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", text, re.M)
    return rc, text, ast.literal_eval(m.group(1)) if m else None


def _committed(workdir):
    out = []
    for r in range(10):
        with open(os.path.join(workdir, f"mr-out-{r}"), "rb") as f:
            out.append(f.read())
    with open(os.path.join(workdir, "plan-join.json"), "rb") as f:
        return out + [f.read()]


@pytest.fixture(scope="module")
def resident(collection, tmp_path_factory):
    """What the job commits over the list of whole documents, read
    before the first stage as the parent read them."""
    paths, docs = collection
    plan = indexer_join_plan(docs, n_reduce=10, u_cap=1024)
    return _rendered(run_plan(plan), paths)


def _rendered(res, paths):
    """The ten partitions' bytes of a plan's index, as ``planrun``
    commits them, and the join (a word's documents sorted: their order
    is the walk's)."""
    index = res.index.named([os.path.basename(p) for p in paths])
    return ([index.render_partition(r) for r in range(10)],
            {w: (df, part, sorted(held))
             for w, (df, part, held) in res.final.items()})


@pytest.mark.parametrize("flags", [(), PACK], ids=["unpacked", "packed"])
def test_the_job_reports_how_the_read_ahead_engaged(collection, resident,
                                                    tmp_path, flags):
    paths, docs = collection
    workdir = str(tmp_path / "wd")
    rc, err, ps = _run(paths, workdir, *flags)
    assert rc == 0, err[-2000:]
    assert not _readers()
    for key in READ_KEYS:
        assert key in ps and key in registry.SCHEMA_KEYS, key
    assert ps["read_ahead_hits"] <= ps["read_docs"] == len(docs)
    assert ps["read_threads"] == ioread.DOC_READ_THREADS
    assert ps["read_wait_s"] >= 0.0 and ps["read_s"] >= 0.0
    walk = ps["stages"]["indexer"]
    assert walk["docs"] == walk["wave_docs"] == len(docs)
    assert walk["bytes_in"] == sum(map(len, docs))
    # byte for byte what the resident list commits
    assert _committed(workdir)[:10] == resident[0]


@pytest.mark.parametrize("flags", [(), PACK], ids=["unpacked", "packed"])
@pytest.mark.parametrize("change", ["removed", "grown", "cut"])
def test_a_changed_file_fails_the_job_and_commits_nothing(
        collection, tmp_path, monkeypatch, change, flags):
    """The file changes between the lengths and the walk: after
    ``build_plan`` has returned, as a writer racing the job would."""
    paths, docs = collection
    mine = []
    for i, data in enumerate(docs[:30]):
        mine.append(str(tmp_path / f"d{i:05d}.txt"))
        with open(mine[-1], "wb") as f:
            f.write(data)
    victim = mine[-2]                    # in a late wave of either walk

    def build_then_change(spec):
        plan = build_plan(spec)
        if change == "removed":
            os.remove(victim)
        else:
            with open(victim, "r+b") as f:
                f.truncate(3) if change == "cut" else f.seek(0, 2)
                f.write(b" more words")
        return plan

    monkeypatch.setattr("dsi_tpu.plan.stagehost.build_plan",
                        build_then_change)
    workdir = str(tmp_path / "wd")
    rc, err, ps = _run(mine, workdir, *flags)
    assert rc == 1 and ps is None
    assert re.search(r"^planrun: .*d00028\.txt", err, re.M), err[-2000:]
    assert not glob.glob(os.path.join(workdir, "mr-out-*"))
    assert not os.path.exists(os.path.join(workdir, "plan-join.json"))
    assert not _readers()
    assert not [t for t in threading.enumerate()
                if t.name.startswith("dsi-idx-materializer")]


# ── the plan layer ─────────────────────────────────────────────────────


def test_signature_over_the_lazy_sequence_is_the_resident_lists(collection):
    paths, docs = collection
    kw = dict(n_reduce=10, u_cap=1024, chunk_bytes=CHUNK)
    for pack in (False, True):
        lazy = ReadAheadDocs(paths)
        try:
            a = indexer_join_plan(lazy, pack_docs=pack, **kw)
            b = indexer_join_plan(docs, pack_docs=pack, **kw)
            assert a["indexer"].params["docs"] is lazy    # not copied
            assert lazy.stats["read_docs"] == 0           # nor read
            assert a["indexer"].identity() == b["indexer"].identity()
            assert a.signature() == b.signature()
            assert lazy.stats["read_docs"] == len(docs)
        finally:
            lazy.close()


class _Watched(ReadAheadDocs):
    """Notes the thread of every ask."""

    def __init__(self, paths):
        super().__init__(paths)
        self.askers = set()

    def __getitem__(self, i):
        self.askers.add(threading.current_thread().name)
        return super().__getitem__(i)


@pytest.mark.parametrize("pack", [False, True], ids=["unpacked", "packed"])
def test_without_a_checkpoint_dir_only_the_walks_producer_asks(
        collection, resident, monkeypatch, pack):
    paths, docs = collection
    monkeypatch.setattr("dsi_tpu.plan.graph.Plan.signature", lambda self:
                        pytest.fail("run_plan asked for a signature"))
    lazy = _Watched(paths)
    try:
        plan = indexer_join_plan(lazy, pack_docs=pack, n_reduce=10,
                                 u_cap=1024, chunk_bytes=CHUNK)
        res = run_plan(plan)
    finally:
        lazy.close()
    assert lazy.askers == {"dsi-idx-materializer"}
    assert _rendered(res, paths) == resident


def test_a_job_killed_after_the_indexer_stage_resumes_to_the_same_bytes(
        collection, tmp_path, monkeypatch):
    """The manifest is written over the lazy sequence and verified over
    it again; the resumed job asks the walk for nothing."""
    paths, docs = collection
    rc, err, _ = _run(paths, str(tmp_path / "whole"), *PACK, "--staged")
    assert rc == 0, err[-2000:]
    ck = str(tmp_path / "ck")
    reset_faults()
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", "post-stage-commit")
    monkeypatch.setenv("DSI_FAULT_STEP", "1")
    with pytest.raises(FaultInjected):
        _run(paths, str(tmp_path / "killed"), *PACK, "--staged",
             "--checkpoint-dir", ck)
    assert not _readers()
    assert not glob.glob(str(tmp_path / "killed" / "mr-out-*"))
    for key in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
        monkeypatch.delenv(key)
    reset_faults()
    rc, err, ps = _run(paths, str(tmp_path / "resumed"), *PACK, "--staged",
                       "--checkpoint-dir", ck, "--resume")
    assert rc == 0, err[-2000:]
    assert ps["plan"]["plan_resumed_stages"] == 1
    assert "indexer" not in ps["plan"]["plan_stage_walls"]
    assert ps["read_docs"] == len(docs)      # the signature's CRC
    assert _committed(str(tmp_path / "resumed")) == \
        _committed(str(tmp_path / "whole"))


def test_a_manifest_written_over_a_resident_list_resumes(collection,
                                                         tmp_path):
    """The parent's manifests carry the signature of a list of whole
    documents; the lazy sequence's is the same, so they verify."""
    paths, docs = collection
    ck = str(tmp_path / "ck")
    kw = dict(n_reduce=10, u_cap=1024)
    first = run_plan(indexer_join_plan(docs, **kw), staged=True,
                     checkpoint_dir=ck)
    lazy = ReadAheadDocs(paths)
    try:
        stats = {}
        again = run_plan(indexer_join_plan(lazy, **kw), staged=True,
                         checkpoint_dir=ck, resume=True, stats=stats)
    finally:
        lazy.close()
    assert stats["plan_resumed_stages"] == 3
    assert again.final == first.final
