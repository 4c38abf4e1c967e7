"""Tests for the whole-corpus fused word-count path (ops/corpus_wc.py).

Differential against collections.Counter and the sequential oracle — the
reference's test discipline (test-mr.sh:52-53 sort|cmp parity), on CPU.
"""

import collections
import os
import re

import numpy as np
import pytest

from dsi_tpu.ops.corpus_wc import (
    CorpusResult,
    corpus_wordcount,
    pack_pieces,
    write_corpus_output,
)

PIECE = 1 << 12  # small static shapes for CPU test speed


@pytest.fixture(autouse=True)
def _quiet_compiles(monkeypatch):
    monkeypatch.setenv("DSI_COMPILE_QUIET", "1")


def counts_of(res: CorpusResult) -> dict:
    return {w: c for w, (c, _) in res.to_dict().items()}


def oracle(texts) -> dict:
    c = collections.Counter()
    for t in texts:
        c.update(re.findall(r"[A-Za-z]+", t))
    return dict(c)


def test_single_file_counts():
    texts = ["the quick brown fox the quick dog; the!fox\nruns"]
    res = corpus_wordcount([t.encode() for t in texts], piece_size=PIECE)
    assert res is not None
    assert counts_of(res) == oracle(texts)


def test_multi_file_merge():
    texts = ["alpha beta alpha", "beta gamma", "alpha delta gamma gamma"]
    res = corpus_wordcount([t.encode() for t in texts], piece_size=PIECE)
    assert counts_of(res) == oracle(texts)


def test_no_cross_file_token_merge():
    # File 1 ends with letters, file 2 starts with letters: the zero padding
    # between pieces must keep "abc" and "def" separate words.
    res = corpus_wordcount([b"abc", b"def"], piece_size=PIECE)
    assert counts_of(res) == {"abc": 1, "def": 1}


def test_file_larger_than_piece_splits_at_boundaries():
    words = [f"w{i}x" for i in range(3000)]
    text = " ".join(words)  # ~18 KB >> PIECE
    res = corpus_wordcount([text.encode()], piece_size=PIECE)
    assert counts_of(res) == oracle([text])


def test_first_occurrence_positions_and_lengths():
    raw = b"zed apple zed banana"
    res = corpus_wordcount([raw], piece_size=PIECE)
    words = res.words()
    # The rows are in lexicographic word order (the wire contract).
    assert words == ["apple", "banana", "zed"]
    by_word = dict(zip(words, zip(res.pos.tolist(), res.lens.tolist())))
    assert by_word["apple"] == (4, 5)
    assert by_word["zed"] == (0, 3)


def test_non_ascii_falls_back():
    assert corpus_wordcount(["héllo".encode()], piece_size=PIECE) is None


def test_word_longer_than_64_falls_back():
    assert corpus_wordcount([b"a" * 70 + b" ok"], piece_size=PIECE) is None


def test_wide_word_ladder():
    text = "w" * 40 + " tiny " + "w" * 40
    res = corpus_wordcount([text.encode()], piece_size=PIECE)
    assert counts_of(res) == {"w" * 40: 2, "tiny": 1}


def test_u_cap_retry():
    words = [f"q{i}z" for i in range(200)]
    text = " ".join(words)
    res = corpus_wordcount([text.encode()], piece_size=PIECE, u_cap=16)
    assert counts_of(res) == oracle([text])


def test_empty_and_letter_free_inputs():
    res = corpus_wordcount([b"", b"123 456 ..."], piece_size=PIECE)
    assert res is not None and counts_of(res) == {}


def test_pack_pieces_reserves_separator_byte():
    buf, n_pieces = pack_pieces([b"x" * (PIECE - 1), b"y"], piece_size=PIECE)
    assert n_pieces == 2
    assert buf[PIECE - 1] == 0  # the guaranteed zero tail byte


def test_ihash_matches_reference(tmp_path):
    from dsi_tpu.mr.worker import ihash

    raw = b"Apple zebra Quilt apple nine ten"
    res = corpus_wordcount([raw], piece_size=PIECE)
    got = res.ihashes().tolist()
    for w, h in zip(res.words(), got):
        assert h == ihash(w), w


def test_output_parity_with_sequential_oracle(tmp_path):
    from dsi_tpu.apps import wc
    from dsi_tpu.mr.sequential import run_sequential
    from dsi_tpu.utils.corpus import ensure_corpus

    files = ensure_corpus(str(tmp_path), n_files=2, file_size=3000)
    raws = [open(p, "rb").read() for p in files]
    oracle_out = str(tmp_path / "mr-correct.txt")
    run_sequential(wc.Map, wc.Reduce, files, oracle_out)

    res = corpus_wordcount(raws, piece_size=PIECE)
    assert res is not None
    write_corpus_output(res, 10, str(tmp_path))

    got = []
    for r in range(10):
        with open(tmp_path / f"mr-out-{r}") as f:
            got.extend(l for l in f if l.strip())
    want = [l for l in open(oracle_out) if l.strip()]
    assert sorted(got) == sorted(want)


def test_within_partition_order_matches_reference(tmp_path):
    # The reference's reduce writes keys in sorted order within each
    # mr-out-<r> (worker.go:124-146); our no-sort path must match that, not
    # just the global sorted merge.
    raw = b"pear kiwi lime pear fig date apple cherry mango plum"
    res = corpus_wordcount([raw], piece_size=PIECE)
    write_corpus_output(res, 10, str(tmp_path))
    for r in range(10):
        with open(tmp_path / f"mr-out-{r}") as f:
            keys = [l.split()[0] for l in f if l.strip()]
        assert keys == sorted(keys)


def test_pack6_encode_roundtrip():
    from dsi_tpu.ops.corpus_wc import pack6_encode

    buf = np.frombuffer(b"The quick brown fox! 00\n" * 8, dtype=np.uint8)
    assert len(buf) % 4 == 0
    packed, table = pack6_encode(buf)
    assert len(packed) == len(buf) * 3 // 4
    # Host-side inverse of the device decode.
    b = packed.reshape(-1, 3).astype(np.uint32)
    v = (b[:, 0] << 16) | (b[:, 1] << 8) | b[:, 2]
    codes = np.stack([(v >> 18) & 63, (v >> 12) & 63,
                      (v >> 6) & 63, v & 63], axis=1).reshape(-1)
    assert np.array_equal(table[codes], buf)


def test_pack6_refuses_wide_alphabet():
    from dsi_tpu.ops.corpus_wc import pack6_encode

    buf = np.arange(256, dtype=np.uint8).repeat(4)
    assert pack6_encode(buf) is None


def test_pack6_path_matches_raw_path():
    texts = ["the quick brown fox; jumps over the lazy dog.\n" * 20,
             "alpha beta gamma delta " * 30]
    raws = [t.encode() for t in texts]
    raw_res = corpus_wordcount(raws, piece_size=PIECE, pack6=False)
    p6_res = corpus_wordcount(raws, piece_size=PIECE, pack6=True)
    assert counts_of(raw_res) == counts_of(p6_res) == oracle(texts)
    assert np.array_equal(raw_res.pos, p6_res.pos)
    assert np.array_equal(raw_res.cnt, p6_res.cnt)


def test_pack6_falls_back_to_raw_when_alphabet_wide():
    # >64 distinct byte values but still ASCII letters + punctuation mix:
    # digits/symbols push the alphabet over 64; counts must still be exact.
    fill = "".join(chr(c) for c in range(33, 112))  # 79 printable symbols
    text = f"alpha {fill} beta alpha"
    res = corpus_wordcount([text.encode()], piece_size=PIECE, pack6=True)
    assert counts_of(res) == oracle([text])


def test_aot_cache_roundtrip_same_result():
    from dsi_tpu.backends import aotcache

    import dsi_tpu.ops.corpus_wc as corpus_mod

    text = b"cache me if you can cache me"
    r1 = corpus_wordcount([text], piece_size=PIECE)
    before = dict(aotcache.stats)
    # Force the next call past BOTH in-process layers (the dispatch
    # lru_cache and the aotcache memo) so it compiles again.
    corpus_mod._get_compiled.cache_clear()
    aotcache._memo.clear()
    r2 = corpus_wordcount([text], piece_size=PIECE)
    assert counts_of(r1) == counts_of(r2)
    # Nothing in process held the program any more: it was compiled
    # again (tier-1 runs without a persistent cache) and counted.
    assert aotcache.stats["compiles"] > before["compiles"]


def test_upload_wall_is_accounted():
    """The piece upload goes through ops/xfer.put_views, whose wall time
    is the bench's upload_s phase."""
    from dsi_tpu.ops import xfer

    xfer.stats["upload_s"] = 0.0
    texts = ["upload accounting check one two two three three three"]
    res = corpus_wordcount([t.encode() for t in texts], piece_size=PIECE)
    assert counts_of(res) == oracle(texts)
    assert xfer.stats["upload_s"] > 0.0
