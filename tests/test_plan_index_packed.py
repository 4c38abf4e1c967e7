"""``planrun --chain indexer --pack-docs``: whole documents packed into
the waves, grouped by (word, document) on the device.

The packed walk must commit, byte for byte, what the walk of one document
a wave commits and what ``mrsequential`` with ``apps/indexer`` writes over
the same names, over collections that hold the cases a packer can get
wrong: an empty document, a document that ends in a letter before one
that begins in a letter, a document longer than the chunk, a document
that holds the separator byte, one word in every document of a wave, a
wave whose pairs overflow the first capacity rung, four devices, a kill
and a resume at a wave boundary.  With the flag off the word-count map and
the wave program lower to the text they lowered to before the flag
existed.
"""

import ast
import contextlib
import hashlib
import io
import json
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.ckpt import CheckpointMismatch, FaultInjected, reset_faults
from dsi_tpu.cli import planrun as cli
from dsi_tpu.ops.wordcount import DOC_SEP, count_words_kernel, decode_packed
from dsi_tpu.parallel.grepstream import (
    indexer_streaming,
    pack_chunk,
    pack_docs_cap,
    plan_packed_waves,
)
from dsi_tpu.parallel.shuffle import default_mesh
from tests.harness import word_count_program

CHUNK = 4096
WORD = re.compile(rb"[A-Za-z]+")
SEP = bytes([DOC_SEP])


def _vocab(rng, n):
    return ["".join(chr(97 + int(c)) for c in rng.integers(0, 26, size))
            for size in rng.integers(2, 10, n)]


def _text(rng, vocab, n_words):
    picks = rng.zipf(1.3, n_words) % len(vocab)
    seps = [" ", "\n", ", ", " - "]
    return "".join(vocab[int(p)] + seps[int(p) % 4] for p in picks)


def _collection(seed):
    """Documents as ``bytes``, the packer's hard cases among them."""
    rng = np.random.default_rng(seed)
    vocab = _vocab(rng, 400)
    docs = [_text(rng, vocab, int(n)).encode()
            for n in rng.integers(1, 260, 40)]
    docs[3] = b""                                    # an empty document
    docs[4] = b""                                    # and its neighbour
    docs[11] = _text(rng, vocab, 1500).encode()      # longer than CHUNK
    assert len(docs[11]) > CHUNK
    docs[13] = b"left" + SEP + b"right " + docs[13]  # holds the separator
    docs = [d + b" everywhere" for d in docs[:-1]] + [docs[-1] + b"last"]
    docs[3] = docs[4] = b""
    docs[7] += b" tail"                              # ends in a letter,
    docs[8] = b"head " + docs[8]                     # the next begins in one
    long = _text(rng, vocab, 1500).encode()
    docs[17] = long[:CHUNK - 11].rstrip(b"abcdefghijklmnopqrstuvwxyz") \
        .ljust(CHUNK - 10) + b" everywhere"          # a byte over the chunk
    docs[19] = docs[17][1:]                          # fills a chunk alone
    assert (len(docs[17]), len(docs[19])) == (CHUNK + 1, CHUNK)
    return docs


def _write(directory, docs):
    os.makedirs(directory, exist_ok=True)
    names = [f"d{i:05d}.txt" for i in range(len(docs))]
    for name, data in zip(names, docs):
        with open(os.path.join(directory, name), "wb") as f:
            f.write(data)
    return names


@pytest.fixture(scope="module")
def collection(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("pages"))
    docs = _collection(41)
    return directory, _write(directory, docs), docs


def _run(directory, names, workdir, *flags, u_cap=256, devices=1):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", str(devices),
                       "--nreduce", "10", "--u-cap", str(u_cap), "--stats",
                       "--workdir", workdir, *flags,
                       *[os.path.join(directory, n) for n in names]])
    text = err.getvalue()
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", text, re.M)
    return rc, text, ast.literal_eval(m.group(1)) if m else None


def _committed(workdir):
    lines = []
    for r in range(10):
        with open(os.path.join(workdir, f"mr-out-{r}"),
                  encoding="ascii") as f:
            lines += [line.rstrip("\n") for line in f if line.strip()]
    return sorted(lines)


def _join(workdir, names):
    """``plan-join.json`` as the benchmark's driver renders it: a word's
    documents by name, sorted (their order in the file is the walk's)."""
    with open(os.path.join(workdir, "plan-join.json")) as f:
        found = json.load(f)
    return ([tuple(row) for row in found["topk"]],
            {w: (e["df"], e["part"], sorted(names[d] for d in e["docs"]))
             for w, e in found["join"].items()})


def _sequential(directory, names, out):
    from dsi_tpu.apps import indexer
    from dsi_tpu.mr.sequential import run_sequential

    here = os.getcwd()
    os.chdir(directory)
    try:
        run_sequential(indexer.Map, indexer.Reduce, list(names), out)
    finally:
        os.chdir(here)
    with open(out, encoding="ascii") as f:
        return sorted(line.rstrip("\n") for line in f if line.strip())


PACK = ("--pack-docs", "--chunk-bytes", str(CHUNK))
MODES = {"chained": (), "staged": ("--staged",),
         "device-accumulate": ("--device-accumulate",),
         "four-devices": (), "mesh-shards": ("--mesh-shards", "2")}


@pytest.fixture(scope="module")
def unpacked(collection, tmp_path_factory):
    """What the walk of one document a wave commits, and the sequential
    indexer's lines."""
    directory, names, _ = collection
    root = tmp_path_factory.mktemp("base")
    workdir = str(root / "wd")
    rc, err, ps = _run(directory, names, workdir)
    assert rc == 0, err[-2000:]
    assert ps["stages"]["indexer"]["pack_docs"] is False
    seq = _sequential(directory, names, str(root / "seq.txt"))
    assert _committed(workdir) == seq
    return _committed(workdir), _join(workdir, names), ps


@pytest.mark.parametrize("mode", sorted(MODES))
def test_packed_walk_commits_what_the_unpacked_walk_and_the_oracle_do(
        collection, unpacked, tmp_path, mode):
    directory, names, docs = collection
    want, want_join, _ = unpacked
    workdir = str(tmp_path / "wd")
    devices = 4 if mode in ("four-devices", "mesh-shards") else 1
    rc, err, ps = _run(directory, names, workdir, *PACK, *MODES[mode],
                       devices=devices)
    assert rc == 0, err[-2000:]
    assert _committed(workdir) == want
    assert _join(workdir, names) == want_join
    walk = ps["stages"]["indexer"]
    assert walk["pack_docs"] is True
    assert walk["docs"] == walk["wave_docs"] == len(docs)
    assert walk["wave_doc_bytes"] == sum(map(len, docs))
    # two documents go alone (longest first, a wave each device), the
    # others fill chunks of CHUNK bytes
    sizes = {int(size) for size in walk["waves_by_size"]}
    assert CHUNK in sizes and max(sizes) > CHUNK
    assert walk["waves"] < len(docs) / 2
    assert walk["docs_per_wave_max"] > devices
    assert walk["pack_s"] >= 0.0 and "read_s" in ps
    # "everywhere" is in every document but the empty ones and the last
    line = next(l for l in want if l.startswith("everywhere "))
    assert line.split(" ")[1] == str(len(docs) - 3)
    # no word joined two documents, none was cut at the separator byte
    words = {l.split(" ")[0] for l in want}
    assert {"tail", "head", "left", "right", "last"} <= words
    assert not {"tailhead", "leftright"} & words
    # the first rung (256 pairs) overflowed and the wave was replayed
    assert walk["replays"] >= 1


def test_a_chunk_that_never_overflows_replays_nothing(collection, unpacked,
                                                      tmp_path):
    directory, names, _ = collection
    workdir = str(tmp_path / "wd")
    rc, err, ps = _run(directory, names, workdir, *PACK, u_cap=1 << 14)
    assert rc == 0, err[-2000:]
    assert _committed(workdir) == unpacked[0]
    assert ps["stages"]["indexer"]["replays"] == 0


def test_one_chunk_for_everything_and_a_chunk_a_document(collection,
                                                         unpacked, tmp_path):
    """The two ends of ``--chunk-bytes``: every document in one wave, and
    a chunk so small that nearly every document goes alone."""
    directory, names, docs = collection
    for chunk, waves in ((1 << 18, 1), (64, None)):
        workdir = str(tmp_path / f"wd-{chunk}")
        rc, err, ps = _run(directory, names, workdir, "--pack-docs",
                           "--chunk-bytes", str(chunk))
        assert rc == 0, err[-2000:]
        assert _committed(workdir) == unpacked[0]
        if waves:
            assert ps["stages"]["indexer"]["waves"] == waves
            assert ps["stages"]["indexer"]["docs_per_wave_max"] == len(docs)


def test_non_ascii_takes_the_host_path_under_packing(collection, tmp_path):
    directory, names, _ = collection
    bad = tmp_path / "bad.txt"
    bad.write_bytes("café au lait\n".encode("utf-8"))
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", "1", *PACK,
                       "--workdir", str(tmp_path / "wd"),
                       os.path.join(directory, names[0]), str(bad)])
    assert rc == 1 and "needs the host path" in err.getvalue()


def test_a_word_wider_than_the_window_restarts_the_walk_wider(tmp_path):
    docs = [b"short words here", b"a " + b"w" * 40 + b" b", b"",
            b"more short words"]
    names = _write(str(tmp_path / "docs"), docs)
    workdir = str(tmp_path / "wd")
    rc, err, ps = _run(str(tmp_path / "docs"), names, workdir, *PACK)
    assert rc == 0, err[-2000:]
    assert _committed(workdir) == _sequential(
        str(tmp_path / "docs"), names, str(tmp_path / "seq.txt"))
    # the rung restarted: its waves were dispatched twice
    assert ps["stages"]["indexer"]["wave_docs"] == 2 * len(docs)


def test_pack_docs_is_the_indexer_chains_flag(tmp_path):
    src = tmp_path / "a.txt"
    src.write_bytes(b"the cat\n")
    with pytest.raises(SystemExit) as e, \
            contextlib.redirect_stderr(io.StringIO()):
        cli.main(["--chain", "grep-wc", "--pattern", "the", "--pack-docs",
                  "--workdir", str(tmp_path / "wd"), str(src)])
    assert e.value.code == 2


def test_stage_commits_resume_under_packing(collection, unpacked, tmp_path):
    """``--checkpoint-dir`` / ``--resume``: the packed plan's stage
    commits are its own (the flag is in the plan's signature)."""
    directory, names, _ = collection
    ck = str(tmp_path / "ck")
    rc, err, _ = _run(directory, names, str(tmp_path / "wd1"), *PACK,
                      "--staged", "--checkpoint-dir", ck)
    assert rc == 0, err[-2000:]
    rc, err, ps = _run(directory, names, str(tmp_path / "wd2"), *PACK,
                       "--staged", "--checkpoint-dir", ck, "--resume")
    assert rc == 0, err[-2000:]
    assert ps["plan"]["plan_resumed_stages"] == 3
    assert _committed(str(tmp_path / "wd2")) == unpacked[0]
    # the unpacked plan is another job: it refuses these commits
    rc, err, _ = _run(directory, names, str(tmp_path / "wd3"), "--staged",
                      "--checkpoint-dir", ck, "--resume")
    assert rc == 1 and "planrun:" in err


# ── the wave walk's own checkpoints: a kill at a wave boundary ─────────


def _walk(docs, ckpt=None, resume=False, dacc=False, stats=None, **kw):
    reset_faults()
    kw.setdefault("pack_docs", True)
    return indexer_streaming(
        docs, mesh=default_mesh(4), n_reduce=10, u_cap=1 << 9, depth=2,
        device_accumulate=dacc, sync_every=2, topk=8,
        checkpoint_dir=ckpt, checkpoint_every=2, resume=resume,
        stats=stats, chunk_bytes=512, **kw)


def _as_sets(result):
    postings, top = result
    return {w: (part, sorted(ds)) for w, (part, ds) in postings.items()}, top


@pytest.fixture(scope="module")
def walk_docs():
    return [d for d in _collection(43) if len(d) < 1500]


@pytest.mark.parametrize("dacc", [False, True])
@pytest.mark.parametrize("point", ["post-dispatch", "mid-fold", "post-ckpt"])
def test_packed_walk_resumes_at_the_confirmed_wave(monkeypatch, tmp_path,
                                                   walk_docs, point, dacc):
    base = _walk(walk_docs, dacc=dacc)
    unpacked_walk = _walk(walk_docs, dacc=dacc, pack_docs=False)
    assert _as_sets(base) == _as_sets(unpacked_walk)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", point)
    monkeypatch.setenv("DSI_FAULT_STEP", "2" if point == "post-ckpt"
                       else "4")
    with pytest.raises(FaultInjected):
        _walk(walk_docs, ckpt=ck, dacc=dacc)
    for key in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
        monkeypatch.delenv(key)
    stats = {}
    res = _walk(walk_docs, ckpt=ck, resume=True, dacc=dacc, stats=stats)
    assert res == base                   # the postings' order included
    assert stats["resume_wave"] > 0      # restored, not walked from 0
    assert stats["wave_docs"] < len(walk_docs)


def test_a_packed_checkpoint_is_not_an_unpacked_walks(tmp_path, walk_docs):
    ck = str(tmp_path / "ck")
    _walk(walk_docs, ckpt=ck)
    with pytest.raises(CheckpointMismatch):
        _walk(walk_docs, ckpt=ck, resume=True, pack_docs=False)
    with pytest.raises(CheckpointMismatch):
        indexer_streaming(walk_docs, mesh=default_mesh(4), n_reduce=10,
                          u_cap=1 << 9, topk=8, checkpoint_dir=ck,
                          resume=True, pack_docs=True, chunk_bytes=1024)


# ── the planner and the packer ─────────────────────────────────────────


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("n_dev", [1, 4])
def test_plan_holds_every_document_once_and_splits_none(seed, n_dev):
    rng = np.random.default_rng(seed)
    lens = [int(n) for n in rng.integers(0, 3 * CHUNK // 2, 300)]
    lens[5], lens[6] = CHUNK, CHUNK + 1
    waves = plan_packed_waves(lens, n_dev, CHUNK)
    assert waves == plan_packed_waves(list(lens), n_dev, CHUNK)
    seen = [i for slots, _ in waves for slot in slots for i in slot]
    assert sorted(seen) == list(range(len(lens)))
    packed_order = []
    for slots, size in waves:
        assert 1 <= len(slots) <= n_dev
        for slot in slots:
            need = sum(lens[i] for i in slot) + len(slot) - 1
            assert need <= size and len(slot) <= pack_docs_cap(size)
            if size != CHUNK or lens[slot[0]] > CHUNK:
                assert len(slot) == 1 and lens[slot[0]] > CHUNK
                assert size == 1 << lens[slot[0]].bit_length() or \
                    size > lens[slot[0]]
            else:
                packed_order += slot
    assert packed_order == sorted(packed_order)      # document order
    alone = [slots[0][0] for slots, size in waves if lens[slots[0][0]]
             > CHUNK]
    assert [lens[i] for i in alone] == sorted(
        (lens[i] for i in alone), reverse=True)[:len(alone)]
    # a chunk closes only where the next document does not fit
    chunks = [slot for slots, size in waves for slot in slots
              if lens[slot[0]] <= CHUNK]
    for a, b in zip(chunks, chunks[1:]):
        used = sum(lens[i] for i in a) + len(a) - 1
        assert used + 1 + lens[b[0]] > CHUNK or \
            len(a) == pack_docs_cap(CHUNK)


def test_plan_closes_a_chunk_at_the_most_documents_it_names():
    waves = plan_packed_waves([0] * 200, 1, CHUNK)
    assert [len(slots[0]) for slots, _ in waves] == [64, 64, 64, 8]
    assert plan_packed_waves([], 4, CHUNK) == []


def test_packer_writes_separators_and_rewrites_a_documents_own():
    docs = [b"ab", b"", b"c" + SEP + b"d", b"ef"]
    chunk, ids = pack_chunk(docs, [[0, 1, 2], [3]], 4, 256, pad_id=4)
    assert chunk.shape == (4, 256) and ids.shape == (4, pack_docs_cap(256))
    assert bytes(chunk[0, :9]) == b"ab" + SEP + SEP + b"c d" + b"\0\0"
    assert bytes(chunk[1, :3]) == b"ef\0" and not chunk[2:].any()
    assert ids[0, :4].tolist() == [0, 1, 2, 4]
    assert ids[1, :2].tolist() == [3, 4] and (ids[2:] == 4).all()


def test_kernel_groups_by_word_and_document():
    """The map's new lane, alone: a row for every distinct (word,
    document) pair of a packed chunk, counted, the document as its place
    in the chunk."""
    rng = np.random.default_rng(7)
    vocab = _vocab(rng, 30)
    docs = [_text(rng, vocab, int(n)).encode()
            for n in rng.integers(0, 60, 12)]
    docs[2] = b""
    docs[5] = docs[5].rstrip(b" ,-\n") + b"zz"
    docs[6] = b"zz" + docs[6]
    chunk, _ = pack_chunk(docs, [list(range(len(docs)))], 1, 4096, 99)
    out = count_words_kernel(jax.numpy.asarray(chunk[0]), max_word_len=16,
                             u_cap=1024, t_cap_frac=4, doc_sep=DOC_SEP)
    packed_u, len_u, cnt_u, _, n_unique, max_len, high, overflow, doc_u = \
        [np.asarray(x) for x in out]
    n = int(n_unique)
    words = decode_packed(packed_u, len_u, n)
    got = {(w, int(d)): int(c)
           for w, d, c in zip(words, doc_u[:n], cnt_u[:n])}
    want = {}
    for d, data in enumerate(docs):
        for w in WORD.findall(data):
            key = (w.decode(), d)
            want[key] = want.get(key, 0) + 1
    assert got == want and len(got) == n
    assert not high and not overflow and int(max_len) <= 16


# ── with the flag off, the programs are what they were ─────────────────

#: sha256 of the lowered text, jax 0.9.0, CPU, taken with PR 46, whose
#: lane movement (``ops/wordcount.token_lanes``) changed every program the
#: word-count map is in: ``tokenize_group_core`` at 4,096 B, the index
#: wave program on 1 and 4 devices, the stream step and the TF-IDF wave
#: on 4 devices, ``corpus_kernel``, and the wave program at the rung the
#: served word-count lane starts from.  Before PR 46 the digests dated
#: from commits 9831bd2 (PR 39) and 6415a3d (PR 43).
LOWERED_BEFORE = {
    ("wc", 1):
        "c2193059527678187a46f8430f8647ec7af73340a3090c3f60c7550b5598aa80",
    ("idx", 1):
        "6d4e960fc7eb7656c4cfadc21d6b0721a7b460e96b030998c551ec9aec929150",
    ("idx", 4):
        "4f3cbb112ca9a0f0f5d4ebfb9d02a96077335383b87fe300a6235f4d8cf689cc",
    ("stream", 4):
        "226f75bb62f62323c25bbda0beae3389be1685ddcf85b7bbb20e34b3943606dd",
    ("tfidf", 4):
        "e58675b922e7bdf571918b87e9bb95f58dd2a79934aa696dfc366b5fe37534f0",
    ("corpus", 1):
        "f543ea89ebb40499903cf935a27cb596bf37dd39ba30d286b41358207d614d4f",
    ("serve", 1):
        "57cba2c49ec159fe46ba5f5b9e22c3351b48b236bf5ffee4866363d0f5e57603",
}

#: The compiled program's name: the key of its persisted executable.
LOWERED_NAMES = {
    ("idx", 1): "idx_wave_d1_r10_w16_u256_s4096_f4",
    ("idx", 4): "idx_wave_d4_r10_w16_u256_s4096_f4",
    ("stream", 4): "stream_step_d4_r10_w16_u256_f4",
    ("tfidf", 4): "tfidf_wave_d4_r10_w16_u256_s4096_f4",
    ("serve", 1): "tfidf_wave_d1_r10_w16_u4096_s4096_f4",
}


def _lowered(program, n_dev, **more):
    """(name, lowered text) of one of the programs the grouper is in."""
    from dsi_tpu.serve.pack import PackedWcScheduler
    from dsi_tpu.utils.jaxcompat import enable_x64

    u_cap = 256
    if program == "serve":  # the wave at the rung the lane starts from
        lane = PackedWcScheduler(mesh=default_mesh(n_dev), chunk_bytes=4096)
        assert (lane.n_reduce, lane.state["mwl"], lane.state["frac"],
                lane.chunk_bytes) == (10, 16, 4, 4096)
        program, u_cap = "tfidf", lane.state["cap"]
    name, fn, args, static = word_count_program(program, n_dev, size=4096,
                                                u_cap=u_cap, **more)
    with enable_x64(True):
        return name, jax.jit(fn, static_argnames=tuple(static)).lower(
            *args, **static).as_text()


@pytest.mark.parametrize("program, n_dev", sorted(LOWERED_BEFORE))
def test_flag_off_lowers_to_the_text_it_lowered_to_before(program, n_dev):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken with jax 0.9.0")
    name, text = _lowered(program, n_dev)
    assert name == LOWERED_NAMES.get((program, n_dev))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        LOWERED_BEFORE[program, n_dev]


def test_flag_on_is_another_program_under_the_same_module_name():
    off_name, off = _lowered("idx", 1)
    on_name, on = _lowered("idx", 1, pack_docs=True)
    assert on_name == off_name + "_pk" and on != off
    assert "idx_wave_step" in on.splitlines()[0]
    assert _lowered("wc", 1, doc_sep=DOC_SEP) != _lowered("wc", 1)


# ── spans and counters ─────────────────────────────────────────────────


def test_pack_spans_are_on_the_producers_lane(collection, tmp_path,
                                              monkeypatch):
    from dsi_tpu.obs import trace as obs_trace
    from dsi_tpu.obs.registry import COUNTER_KEYS, PHASE_KEYS

    monkeypatch.delenv("DSI_TRACE_DIR", raising=False)
    tracer = obs_trace.Tracer(enabled=False)
    monkeypatch.setattr(obs_trace, "_global", tracer)
    directory, names, docs = collection
    try:
        rc, err, ps = _run(directory, names, str(tmp_path / "wd"), *PACK,
                           "--trace-dir", str(tmp_path / "trace"))
    finally:
        tracer.enabled = False
    assert rc == 0, err[-2000:]
    walk = ps["stages"]["indexer"]
    assert {"pack_docs", "wave_docs", "docs_per_wave_max"} <= \
        set(walk) & set(COUNTER_KEYS)
    assert {"pack_s", "read_s"} <= set(PHASE_KEYS)
    events = []
    with open(tmp_path / "trace" / "trace.jsonl") as f:
        for line in f:
            e = json.loads(line)
            if e.get("ph") == "X":
                events.append(e)
    spans = {e["id"]: e for e in events}
    packs = [e for e in events if e["name"] == "pack"]
    assert len(packs) == walk["waves"]
    assert {e["lane"] for e in packs} == {"materialize"}
    assert {spans[e["parent"]]["name"] for e in packs} == {"materialize"}
    assert sum(e["docs"] for e in packs) == len(docs)
    assert walk["pack_s"] == pytest.approx(sum(e["dur"] for e in packs),
                                           abs=5e-4)
    (read,) = [e for e in events if e["name"] == "read"]
    assert read["files"] == len(docs)
    assert ps["read_s"] == pytest.approx(read["dur"], abs=5e-4)
    # tracecat names them lane/span
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "tracecat.py"),
         str(tmp_path / "trace")], capture_output=True, text=True)
    assert "materialize/pack" in out.stdout, out.stdout[-1500:]


# ── the commit: a word's documents already in name order ───────────────


def _index_table(words_docs, kk=4, n_parts=3):
    """A ``PackedPostings`` of ``{word: [doc, ...]}``, each word's rows
    in the order given."""
    from dsi_tpu.mr.worker import ihash
    from dsi_tpu.parallel.merge import PostingsTable

    table = PostingsTable()
    for word, held in words_docs.items():
        rows = np.zeros((len(held), kk + 4), np.uint32)
        raw = word.encode("ascii")
        rows[:, :kk] = np.frombuffer(raw.ljust(4 * kk, b"\x00"), ">u4")
        for i, d in enumerate(held):
            rows[i, kk:] = (len(raw), 1, d, ihash(word) % n_parts)
        table.add(rows, kk)
    return table.finalize_packed()


@pytest.mark.parametrize("case, words_docs, names, in_order", [
    ("document-order", {"a": [0, 2, 5], "b": [1], "c": [0, 1, 2, 3, 4, 5]},
     [f"d{i:05d}.txt" for i in range(6)], True),
    ("a-word-out-of-order", {"a": [0, 5, 2], "b": [1], "c": [3, 4]},
     [f"d{i:05d}.txt" for i in range(6)], False),
    ("names-that-sort-otherwise", {"a": [0, 1, 2], "b": [1, 2]},
     ["z.txt", "m.txt", "a.txt"], False),
    ("one-name-twice", {"a": [0, 1, 2], "b": [1, 2]},
     ["a.txt", "b.txt", "b.txt"], False),
    ("a-document-twice", {"a": [0, 1, 1], "b": [2]},
     ["a.txt", "b.txt", "c.txt"], False),
])
def test_rendering_skips_the_sort_only_where_nothing_would_move(
        case, words_docs, names, in_order):
    from dsi_tpu.apps import indexer
    from dsi_tpu.mr.worker import ihash

    packed = _index_table(words_docs).named(names)
    rank = {n: j for j, n in enumerate(sorted(set(names)))}
    rank_of = np.array([rank[n] for n in names], np.int64)
    fast = packed._ranks_in_order(rank_of)
    assert (fast[0] is not None) == in_order
    slow = packed._ranks_sorted(rank_of, len(rank))
    if in_order:
        assert fast[0].tolist() == slow[0].tolist()
        assert fast[1].tolist() == slow[1].tolist()
    for r in range(3):
        want = "".join(
            f"{word} {indexer.Reduce(word, [names[d] for d in held])}\n"
            for word, held in sorted(words_docs.items())
            if ihash(word) % 3 == r)
        assert packed.render_partition(r) == want.encode("ascii")
