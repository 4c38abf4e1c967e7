"""Streaming SPMD path: corpus size decoupled from device/host memory.

Oracle discipline as everywhere else: exact agreement with a host Counter
over the Go tokenizer semantics, and with the one-shot sharded path.
"""

import collections
import re

import pytest

jax = pytest.importorskip("jax")

import numpy as np

from dsi_tpu.mr.worker import ihash
from dsi_tpu.parallel.shuffle import default_mesh, wordcount_sharded
from dsi_tpu.parallel.streaming import (
    _MAX_BACKOFF,
    _TokenTooLong,
    _cut_at_boundary,
    batch_stream,
    stream_files,
    wordcount_streaming,
)

WORDS = re.compile(r"[A-Za-z]+")


def _mesh():
    return default_mesh(8)


def test_batches_never_split_tokens():
    text = ("alpha beta gamma delta epsilon " * 400).encode()
    # Tiny chunks force cuts everywhere; every cut must land on a boundary.
    rebuilt = []
    for batch in batch_stream([text], n_dev=4, chunk_bytes=64):
        for row in batch:
            rebuilt.append(bytes(row).rstrip(b"\x00"))
    got = collections.Counter()
    for piece in rebuilt:
        got.update(WORDS.findall(piece.decode()))
    assert got == collections.Counter(WORDS.findall(text.decode()))


def test_streaming_matches_counter_and_partitions():
    text = ("the quick brown fox jumps over the lazy dog " * 3000).encode()
    blocks = [text[i:i + 7919] for i in range(0, len(text), 7919)]
    res = wordcount_streaming(blocks, mesh=_mesh(), n_reduce=10,
                              chunk_bytes=1 << 12, u_cap=1 << 10)
    assert res is not None
    want = collections.Counter(WORDS.findall(text.decode()))
    assert {w: c for w, (c, _) in res.items()} == dict(want)
    for w, (_, p) in res.items():
        assert p == ihash(w) % 10


def test_streaming_matches_one_shot_sharded():
    rng = np.random.default_rng(7)
    words = ["tpu", "stream", "carry", "boundary", "chunk", "merge",
             "accumulate", "wave"]
    text = " ".join(words[i] for i in rng.integers(0, 8, 20_000)).encode()
    mesh = _mesh()
    stream = wordcount_streaming([text], mesh=mesh, n_reduce=10,
                                 chunk_bytes=1 << 12, u_cap=1 << 10)
    oneshot = wordcount_sharded(text, mesh=mesh, n_reduce=10, u_cap=1 << 10)
    assert stream is not None and oneshot is not None
    assert stream == oneshot


def _cut_reference(buf, size):
    """The pre-vectorization per-byte backoff loop, kept as the oracle."""
    def letter(b):
        return (65 <= b <= 90) or (97 <= b <= 122)

    if len(buf) <= size:
        return len(buf)
    c = size
    while c > 0 and letter(buf[c - 1]) and letter(buf[c]):
        c -= 1
        if size - c > _MAX_BACKOFF:
            raise _TokenTooLong
    return c


def test_cut_at_boundary_matches_scalar_reference():
    """The vectorized cut must agree with the per-byte reference loop on
    random byte soup, long letter runs at every offset around the cut,
    and the too-long-token escape."""
    rng = np.random.default_rng(11)
    for size in (8, 64, 97, 256):
        for _ in range(40):
            n = size + int(rng.integers(1, 2 * _MAX_BACKOFF + 8))
            buf = bytearray(rng.integers(0, 256, size=n, dtype=np.uint8)
                            .tobytes())
            # bias toward letters so long runs actually occur
            if rng.random() < 0.5:
                run = int(rng.integers(1, 2 * _MAX_BACKOFF))
                at = int(rng.integers(0, max(1, n - run)))
                buf[at:at + run] = b"q" * run
            try:
                want = _cut_reference(buf, size)
            except _TokenTooLong:
                with pytest.raises(_TokenTooLong):
                    _cut_at_boundary(buf, size)
                continue
            assert _cut_at_boundary(buf, size) == want
    # short-buffer fast path
    assert _cut_at_boundary(bytearray(b"abc"), 8) == 3


def test_pipeline_depth_parity_and_deferred_replay():
    """depth=1, depth=3, and a host Counter must agree bit-for-bit on a
    stream that forces a mid-stream capacity overflow — the deferred
    check replays the overflowing step exactly once (counts would be
    doubled by a merge-then-replay bug, halved by a dropped step)."""
    rng = np.random.default_rng(23)
    small = ["aa", "bb", "cc", "dd"]
    big = ["w%03d" % i for i in range(700)]  # > u_cap uniques per chunk
    blocks = []
    for i in range(12):
        vocab = small if i < 6 else big  # overflow arrives mid-stream
        picks = rng.integers(0, len(vocab), 400)
        blocks.append((" ".join(vocab[j] for j in picks) + "\n").encode())
    text = b"".join(blocks)
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    mesh = _mesh()
    results, stats = {}, {}
    for d in (1, 3):
        st: dict = {}
        res = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                                  chunk_bytes=1 << 11, u_cap=64, depth=d,
                                  pipeline_stats=st)
        assert res is not None
        results[d], stats[d] = res, st
    assert {w: c for w, (c, _) in results[3].items()} == want
    assert results[1] == results[3]  # bit-identical dicts, partitions too
    assert stats[3]["replays"] >= 1  # the deferred check actually fired
    assert stats[3]["steps"] == stats[1]["steps"]


def test_pipeline_keeps_tail_batch_and_step_count():
    """depth>1 must retire every step including the partial tail batch —
    a window-drain bug would drop the newest steps, a reorder would still
    show up as wrong counts for the tail-only marker word."""
    filler = ("lorem ipsum dolor sit amet " * 40).encode()
    blocks = [filler] * 7 + [b"zzzmarker zzzmarker zzzmarker"]
    text = b"".join(blocks)
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    st: dict = {}
    res = wordcount_streaming(list(blocks), mesh=_mesh(), n_reduce=10,
                              chunk_bytes=1 << 10, u_cap=1 << 8, depth=3,
                              pipeline_stats=st)
    assert res is not None
    assert {w: c for w, (c, _) in res.items()} == want
    assert res["zzzmarker"][0] == 3  # the tail-only word survived
    n_rows = sum(len(b) for b in blocks) // (1 << 10) + 1
    assert st["steps"] >= max(1, n_rows // 8)  # tail batch was a step


def test_pipeline_buffer_accounting_stays_bounded():
    """Host batch buffers are recycled (O(depth) allocations however long
    the stream) and the device in-flight window never exceeds depth —
    the HBM-residency bound the design promises."""
    line = ("alpha beta gamma delta " * 30).encode()
    blocks = [line] * 200
    for d in (1, 2, 3):
        st: dict = {}
        res = wordcount_streaming(list(blocks), mesh=_mesh(), n_reduce=10,
                                  chunk_bytes=1 << 10, u_cap=1 << 8,
                                  depth=d, pipeline_stats=st)
        assert res is not None
        assert st["steps"] > 2 * d  # long enough to prove recycling
        assert st["max_inflight_chunks"] <= d
        assert st["batch_allocs"] <= 2 * d + 3
        assert st["replays"] == 0


def test_pipeline_sticky_rung_bounds_replays():
    """A stream that token-overflows the optimistic frac on EVERY chunk
    (dense single-letter words: tokens ≈ n/2 > t_cap at frac 4) must
    replay at most the in-flight window, not every step: the cleared
    (frac) rung sticks for later dispatches just like a widened
    capacity."""
    text = b"a b c d e f g h " * 6000
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    st: dict = {}
    res = wordcount_streaming([text], mesh=_mesh(), n_reduce=10,
                              chunk_bytes=1 << 11, u_cap=1 << 8, depth=3,
                              pipeline_stats=st)
    assert res is not None
    assert {w: c for w, (c, _) in res.items()} == want
    assert st["steps"] > 3  # long enough that stickiness matters
    assert 1 <= st["replays"] <= 3  # bounded by the window, not the stream


def test_pipeline_depth_env_default(monkeypatch):
    """DSI_STREAM_PIPELINE_DEPTH is the default window for callers that
    pass no depth; an explicit depth always wins."""
    monkeypatch.setenv("DSI_STREAM_PIPELINE_DEPTH", "3")
    st: dict = {}
    res = wordcount_streaming([b"one two three " * 200], mesh=_mesh(),
                              chunk_bytes=1 << 10, u_cap=1 << 8,
                              pipeline_stats=st)
    assert res is not None and st["depth"] == 3
    st = {}
    res = wordcount_streaming([b"one two three " * 200], mesh=_mesh(),
                              chunk_bytes=1 << 10, u_cap=1 << 8, depth=1,
                              pipeline_stats=st)
    assert res is not None and st["depth"] == 1


def test_streaming_non_ascii_falls_back():
    blocks = [b"plain words ", "café".encode("utf-8"), b" more words"]
    assert wordcount_streaming(blocks, mesh=_mesh(),
                               chunk_bytes=1 << 10, u_cap=1 << 8) is None


def test_streaming_giant_token_falls_back():
    # A letter run far beyond the 64-byte device word limit, positioned to
    # span a chunk cut: the streaming path must hand the job to the host.
    blocks = [b"ok words here ", b"x" * 5000, b" tail"]
    assert wordcount_streaming(blocks, mesh=_mesh(),
                               chunk_bytes=1 << 10, u_cap=1 << 8) is None


def test_stream_files_separates_documents(tmp_path):
    a = tmp_path / "a.txt"
    b = tmp_path / "b.txt"
    a.write_bytes(b"ends with word")
    b.write_bytes(b"word starts here")
    data = b"".join(stream_files([str(a), str(b)]))
    got = collections.Counter(WORDS.findall(data.decode()))
    # "word" twice — NOT a merged "wordword" at the file seam.
    assert got["word"] == 2 and "wordword" not in got


def test_wcstream_cli_matches_sequential_oracle(tmp_path, monkeypatch):
    """The streaming path must be reachable without
    importing internals — the wcstream CLI end-to-end vs the oracle."""
    from dsi_tpu.cli import wcstream
    from tests.harness import merged_output, oracle_output

    from dsi_tpu.utils.corpus import ensure_corpus

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=3,
                          file_size=20_000)
    want = oracle_output("wc", files, str(tmp_path))
    wd = tmp_path / "out"
    wd.mkdir()
    rc = wcstream.main(["--nreduce", "10", "--chunk-bytes", "4096",
                        "--check", "--workdir", str(wd)] + files)
    assert rc == 0  # --check exits 2 on a parity failure
    assert merged_output(str(wd)) == want


def test_wcstream_cli_host_fallback(tmp_path):
    from dsi_tpu.cli import wcstream
    from tests.harness import merged_output, oracle_output

    f = tmp_path / "in.txt"
    f.write_text("café words café and more words", encoding="utf-8")
    want = oracle_output("wc", [str(f)], str(tmp_path))
    wd = tmp_path / "out"
    wd.mkdir()
    rc = wcstream.main(["--workdir", str(wd), str(f)])
    assert rc == 0
    assert merged_output(str(wd)) == want


@pytest.mark.slow
def test_streaming_100mb_bounded_memory():
    """>=100 MB through the 8-device virtual mesh with bounded footprint:
    the corpus is a generator (never materialised), the accumulator is
    vocabulary-bounded, and every step reuses one compiled program."""
    from dsi_tpu.utils.corpus import generate_file

    base_path = "/tmp/dsi-stream-base.bin"
    generate_file(base_path, (1 << 20) - 1, seed=99)
    with open(base_path, "rb") as f:
        base = f.read() + b"\n"  # newline: no cross-repeat token merge
    repeats = 100  # ~100 MB total

    def blocks():
        for _ in range(repeats):
            yield base

    res = wordcount_streaming(blocks(), mesh=_mesh(), n_reduce=10,
                              chunk_bytes=1 << 20, u_cap=1 << 16)
    assert res is not None
    base_counts = collections.Counter(WORDS.findall(base.decode()))
    want = {w: c * repeats for w, c in base_counts.items()}
    assert {w: c for w, (c, _) in res.items()} == want


def test_streaming_aot_path_matches_counter(tmp_path, monkeypatch):
    """The aot=True bench path (AOT-cached step + full-capacity pack) on a
    single-device mesh — the exact configuration bench.py's stream row
    runs on the chip — must agree with the Counter oracle, and the warm
    pass must cover every program the stream then executes (zero compiles
    after warming)."""
    from dsi_tpu.backends import aotcache
    from dsi_tpu.parallel.streaming import warm_stream_aot

    mesh = default_mesh(1)
    warm_stream_aot(mesh=mesh, chunk_bytes=1 << 14, caps=(1 << 10,))
    compiles_after_warm = aotcache.stats["compiles"]
    text = ("portable exact streaming " * 900).encode()
    res = wordcount_streaming([text], mesh=mesh, n_reduce=10,
                              chunk_bytes=1 << 14, u_cap=1 << 10, aot=True)
    assert res is not None
    want = collections.Counter(WORDS.findall(text.decode()))
    assert {w: c for w, (c, _) in res.items()} == dict(want)
    for w, (_, part) in res.items():
        assert part == ihash(w) % 10
    assert aotcache.stats["compiles"] == compiles_after_warm


@pytest.mark.parametrize("how", ["stream", "stream-accumulate", "step"])
def test_stream_result_is_a_mapping_equal_to_the_oracles_dict(how):
    """The merged table is the result: a read-only mapping that compares
    equal to the oracle's dict, whose ``len()`` reads the arrays and whose
    first keyed access decodes every spelling once."""
    from collections.abc import Mapping

    from dsi_tpu.parallel.merge import PackedWordCounts
    from dsi_tpu.parallel.streaming import WordcountStep

    text = ("Alpha alpha beta Beta gamma a ab abc the quick brown fox "
            * 700).encode()
    kw = dict(mesh=_mesh(), n_reduce=10, chunk_bytes=1 << 12,
              u_cap=1 << 10)
    ps: dict = {}
    if how == "step":
        step = WordcountStep([text], pipeline_stats=ps, **kw)
        while step.advance():
            pass
        res = step.close()
        assert step.result is res
    else:
        res = wordcount_streaming(
            [text], pipeline_stats=ps,
            device_accumulate=how == "stream-accumulate", **kw)
    counts = collections.Counter(WORDS.findall(text.decode()))
    want = {w: (c, ihash(w) % 10) for w, c in counts.items()}
    assert isinstance(res, PackedWordCounts) and isinstance(res, Mapping)
    assert len(res) == len(want)
    # len() and the engine's own keys=len(...) decoded nothing
    assert ps["finalize_decoded_keys"] == 0 == ps["finalize_decode_s"]
    assert res.stats["finalize_decoded_keys"] == 0
    assert res["alpha"] == want["alpha"]
    assert res.stats["finalize_decoded_keys"] == len(want)
    assert "Alpha" in res and "gamma" in res and "delta" not in res
    assert dict(res.items()) == want and set(res) == set(want)
    assert res == want and want == res
    assert res.stats["finalize_decoded_keys"] == len(want)  # once, kept
    assert res.stats["finalize_decode_s"] > 0


# ── the table packed when the step is dispatched ───────────────────────


def _word(i: int) -> str:
    return "".join(chr(97 + (i // 26 ** j) % 26) for j in range(5))


def _segments(rng, n, size, vocab_of):
    """``n`` pieces of about ``size`` bytes, piece k drawn from the
    word ordinals ``vocab_of(k)``."""
    out = []
    for k in range(n):
        ids = np.asarray(vocab_of(k))
        picks = rng.integers(0, len(ids), size // 6)
        out.append(" ".join(_word(int(ids[j])) for j in picks) + "\n")
    return "".join(out)


def _wcstream_stats(tmp_path, text, *flags):
    """One ``wcstream`` job over ``text`` in this process: its
    ``pipeline_stats`` and whether it committed the oracle's bytes."""
    import ast
    import contextlib
    import io

    from dsi_tpu.cli import wcstream
    from tests.harness import merged_output, oracle_output

    src = tmp_path / "in.txt"
    src.write_text(text, encoding="ascii")
    n = len(list(tmp_path.iterdir()))
    wd = tmp_path / f"out-{n}"
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = wcstream.main(["--nreduce", "10", "--chunk-bytes", "4096",
                            "--u-cap", "64", "--stats", "--workdir",
                            str(wd), *flags, str(src)])
    assert rc == 0, err.getvalue()
    m = re.search(r"^wcstream: pipeline_stats=(\{.*\})$", err.getvalue(),
                  re.M)
    same = merged_output(str(wd)) == oracle_output("wc", [str(src)],
                                                   str(tmp_path))
    return ast.literal_eval(m.group(1)), same


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("vocabulary", ["grows", "fixed"])
def test_step_tables_are_packed_at_dispatch(tmp_path, devices, vocabulary):
    """The host-merge pull takes the tensor packed when its step was
    dispatched, at the sticky prefix.  A fixed vocabulary never outgrows
    the start rung's prefix; one that grows overflows the rung (a replay,
    whose payload is packed late), then outgrows the prefix the replay
    left without overflowing the wider rung: a miss, packed late once,
    which raises the prefix for the steps behind it."""
    rng = np.random.default_rng(50)
    step = devices * 4096
    if vocabulary == "fixed":
        text = _segments(rng, 8, step, lambda k: range(40))
    else:
        text = (_segments(rng, 4, step, lambda k: range(20))
                # ~100 words a chunk: over the rung of 64, under 128
                + _segments(rng, 4, step, lambda k: range(100))
                # 75 words of its own every 2 KiB: 150-225 a chunk, under
                # the rung of 256 and over the prefix the replay left
                + _segments(rng, 6 * devices * 2, 2048,
                            lambda k: range(1000 + 75 * k, 1075 + 75 * k)))
    ps, same = _wcstream_stats(tmp_path, text, "--devices", str(devices))
    assert same  # the sequential reference's bytes
    assert ps["pulls_early"] + ps["pulls_late"] == ps["step_pulls"]
    assert ps["step_pulls"] == ps["steps"] >= 8
    if vocabulary == "fixed":
        assert ps["replays"] == 0 and ps["pulls_late"] == 0
    else:
        assert ps["replays"] >= 1
        # a replay pulls late once; the rest are misses of the prefix
        assert ps["pulls_late"] >= ps["replays"] + 1
        assert ps["pulls_early"] >= ps["pulls_late"]


def test_full_windows_are_compacted_beside_the_steps(tmp_path, monkeypatch):
    """A host-merge job whose accumulator's window fills (the class's
    default lowered: the engine has no argument for it) hands the full
    windows to the merger thread, commits the oracle's bytes, and was
    held for no more of its compactions than they took (a wait ends
    with the merger's thread, a little after its ``compact`` span)."""
    import threading

    from dsi_tpu.parallel.merge import PackedCounts

    monkeypatch.setattr(PackedCounts.__init__, "__defaults__", (256, None))
    rng = np.random.default_rng(52)
    text = _segments(rng, 24, 4096,
                     lambda k: range(40 * k, 40 * k + 60))
    ps, same = _wcstream_stats(tmp_path, text, "--devices", "1")
    assert same  # the sequential reference's bytes
    assert ps["merge_rows_in"] >= 4 * 256
    # every window that filled, and none but the last, partial one
    assert ps["merge_compacts"] - 1 <= ps["merge_compacts_async"] \
        <= ps["merge_compacts"]
    assert ps["merge_compacts_async"] >= ps["merge_rows_in"] // 512 >= 2
    assert ps["merge_rows_sorted"] == ps["merge_rows_in"]
    assert 0.0 < ps["compact_caller_s"] <= ps["compact_s"] + 0.05
    assert not [t for t in threading.enumerate()
                if t.name == "dsi-merge-compact"]


@pytest.mark.parametrize("depth", [2, 3])
def test_overflowing_steps_early_tensor_is_never_merged(depth):
    """Every step overflows the start rung until it widens: each such
    step's tensor, packed at dispatch from an inexact table, is dropped
    and the replay's merged in its place: the counts are the
    reference's, neither doubled nor short."""
    rng = np.random.default_rng(51)
    text = _segments(rng, 10, 8192, lambda k: range(300)).encode()
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    st: dict = {}
    res = wordcount_streaming([text], mesh=default_mesh(2), n_reduce=10,
                              chunk_bytes=1 << 12, u_cap=16, depth=depth,
                              pipeline_stats=st)
    assert {w: c for w, (c, _) in res.items()} == want
    assert 1 <= st["replays"] <= depth
    assert st["pulls_late"] >= st["replays"]
    assert st["pulls_early"] + st["pulls_late"] == st["step_pulls"] \
        == st["steps"]


@pytest.mark.parametrize("delta", [False, True], ids=["full", "delta"])
def test_killed_checkpointed_run_resumes_with_the_early_pack(
        tmp_path, monkeypatch, delta):
    """A run killed mid-stream, between a step's merge and its cursor,
    resumes to the uninterrupted run's bytes; the resumed steps are
    served by the tensors packed at their dispatch (a delta log trims
    their padding)."""
    from dsi_tpu.ckpt import FaultInjected, reset_faults

    rng = np.random.default_rng(52)
    text = _segments(rng, 12, 8192, lambda k: range(50 + 10 * k))
    flags = ["--devices", "2", "--checkpoint-dir", str(tmp_path / "ck"),
             "--checkpoint-every", "2"] + (["--ckpt-delta"] if delta else [])
    reset_faults()
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    monkeypatch.setenv("DSI_FAULT_POINT", "mid-fold")
    monkeypatch.setenv("DSI_FAULT_STEP", "7")
    with pytest.raises(FaultInjected):
        _wcstream_stats(tmp_path, text, *flags)
    for k in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
        monkeypatch.delenv(k)
    reset_faults()
    ps, same = _wcstream_stats(tmp_path, text, *flags, "--resume")
    assert same
    assert ps["resume_cursor"] > 0  # restored, not replayed from byte 0
    assert ps["pulls_early"] >= 1
    assert ps["pulls_early"] + ps["pulls_late"] == ps["step_pulls"]
