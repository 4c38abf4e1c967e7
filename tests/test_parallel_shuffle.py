"""Multi-device SPMD shuffle: parity of the all_to_all data plane.

Runs the full map + all_to_all + reduce program on the virtual 8-device CPU
mesh (conftest.py) and checks it against (a) collections.Counter ground truth
and (b) the host app's partitioner (`ihash % n_reduce`, mr/worker.go:33-37,76),
i.e. the same differential-oracle discipline as test-mr.sh:52-53.
"""

import collections
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.mr.worker import ihash
from dsi_tpu.parallel.shuffle import (
    default_mesh,
    mapreduce_step,
    shard_text,
    wordcount_sharded,
    write_partitioned_output,
)

WORDS = re.compile(r"[A-Za-z]+")


def make_text(n_bytes: int, seed: int = 7) -> bytes:
    rng = np.random.default_rng(seed)
    vocab = [b"alpha", b"Bet", b"gamma", b"d", b"epsilonlongword", b"Zz",
             b"supercalifragilistic", b"mid"]
    parts = []
    size = 0
    while size < n_bytes:
        w = vocab[int(rng.integers(len(vocab)))]
        sep = b" " if rng.random() < 0.8 else b"\n"
        parts.append(w + sep)
        size += len(w) + 1
    return b"".join(parts)[:n_bytes]


def truth(data: bytes):
    return collections.Counter(WORDS.findall(data.decode("ascii")))


def test_shard_text_no_token_splits():
    data = make_text(5000)
    chunks, size = shard_text(data, 8)
    merged = collections.Counter()
    for row in chunks:
        merged.update(WORDS.findall(row.tobytes().decode("ascii", "ignore")))
    assert merged == truth(data)


def test_sharded_wordcount_matches_counter():
    data = make_text(20000)
    mesh = default_mesh(8)
    res = wordcount_sharded(data, mesh=mesh, n_reduce=10, max_word_len=16,
                            u_cap=256)
    assert res is not None
    want = truth(data)
    assert {w: c for w, (c, _) in res.items()} == dict(want)
    for w, (_, r) in res.items():
        assert r == ihash(w) % 10  # bit-exact reference partitioner


def test_sharded_wordcount_word_overflow_retries():
    # 20-byte word forces the 16-byte kernel to retry at 64.
    data = (b"abcdefghijklmnopqrst " * 50) + b"tail word"
    res = wordcount_sharded(data, mesh=default_mesh(8), max_word_len=16,
                            u_cap=256)
    assert res is not None
    assert res["abcdefghijklmnopqrst"][0] == 50


def test_token_overflow_retries_exact_bound():
    # single-letter tokens at maximum density: n_tokens == n//2, overflowing
    # the compact frac=4 buffer and forcing the exact n//2+1 retry
    data = b"a b c d e f g h " * 200
    res = wordcount_sharded(data, mesh=default_mesh(8), u_cap=256)
    assert res is not None
    assert {w: c for w, (c, _) in res.items()} == dict(truth(data))

    from dsi_tpu.ops.wordcount import count_words_host_result
    single = count_words_host_result(data)
    assert {w: (c,) for w, (c, _) in single.items()} == \
        {w: (c,) for w, c in truth(data).items()}


def test_sharded_wordcount_non_ascii_falls_back():
    data = "héllo world".encode("utf-8")
    assert wordcount_sharded(data, mesh=default_mesh(8)) is None


def test_partition_ownership():
    """Each device's output rows carry only partitions it owns (r % D == d)."""
    data = make_text(8000)
    mesh = default_mesh(8)
    chunks_np, _ = shard_text(data, 8)
    keys, lens, cnts, parts, scal = mapreduce_step(
        jax.numpy.asarray(chunks_np), n_dev=8, n_reduce=10, max_word_len=32,
        u_cap=256, mesh=mesh)
    scal = np.asarray(scal)
    parts = np.asarray(parts)
    for d in range(8):
        nu = int(scal[d, 0])
        assert (parts[d, :nu] % 8 == d).all()


def test_write_partitioned_output(tmp_path):
    data = make_text(4000)
    res = wordcount_sharded(data, mesh=default_mesh(8), u_cap=256)
    paths = write_partitioned_output(res, 10, str(tmp_path))
    assert len(paths) == 10
    merged = {}
    for p in paths:
        with open(p) as f:
            for line in f:
                w, c = line.split()
                merged[w] = int(c)
    assert merged == dict(truth(data))


def test_sharded_result_is_the_table_and_writes_what_its_dict_writes(
        tmp_path):
    """``wordcount_sharded`` hands back the merged table: equal to the
    oracle's dict, and written from its arrays to the bytes the dict
    formatting gives."""
    from dsi_tpu.parallel.merge import PackedWordCounts

    data = make_text(6000, seed=11)
    res = wordcount_sharded(data, mesh=default_mesh(8), u_cap=256)
    assert isinstance(res, PackedWordCounts)
    want = {w: (c, ihash(w) % 10) for w, c in truth(data).items()}
    assert len(res) == len(want)
    stats: dict = {}
    packed = write_partitioned_output(res, 10, str(tmp_path), stats=stats)
    assert stats == {**stats, "write_rows_packed": len(want),
                     "write_rows_dict": 0}
    assert res.stats["finalize_decoded_keys"] == 0
    plain_dir = tmp_path / "dict"
    plain_dir.mkdir()
    plain = write_partitioned_output(want, 10, str(plain_dir), stats=stats)
    assert stats["write_rows_dict"] == len(want)
    for a, b in zip(packed, plain):
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read(), a
    assert res == want and res.stats["finalize_decoded_keys"] == len(want)
