"""Unit tests for the vectorized host-side merge tables (parallel/merge.py).

These are pure-numpy properties (no mesh needed): the tables must agree
with a straightforward dict/Counter oracle on random inputs, across
compaction windows, mixed key widths, and count magnitudes past uint32.
"""

from __future__ import annotations

import os
import random
from collections import Counter

import numpy as np
import pytest

from dsi_tpu.parallel.merge import PackedCounts, PostingsTable


def _pack_word(w: str, k: int) -> np.ndarray:
    """Big-endian uint32 lanes, zero-padded — the kernel's packing
    (ops/wordcount.py tokenize_group_core)."""
    raw = w.encode("ascii").ljust(4 * k, b"\0")
    return np.frombuffer(raw, dtype=">u4").astype(np.uint32)


def _rows(words, counts, k):
    keys = np.stack([_pack_word(w, k) for w in words])
    lens = np.array([len(w) for w in words], dtype=np.int32)
    cnts = np.array(counts, dtype=np.int64)
    parts = np.array([hash(w) % 10 for w in words], dtype=np.int32)
    return keys, lens, cnts, parts


def test_packed_counts_matches_counter_oracle():
    rng = random.Random(7)
    vocab = ["".join(rng.choices("abcdefgh", k=rng.randint(1, 12)))
             for _ in range(200)]
    oracle: Counter = Counter()
    acc = PackedCounts(compact_rows=64)  # force many compactions
    for _ in range(30):
        batch = rng.choices(vocab, k=rng.randint(1, 50))
        local = Counter(batch)
        words = sorted(local)
        acc.add(*_rows(words, [local[w] for w in words], k=4))
        oracle.update(local)
    out = acc.finalize()
    assert {w: c for w, (c, _) in out.items()} == dict(oracle)
    # partition column survives the merge and is per-word stable
    for w, (_, p) in out.items():
        assert p == hash(w) % 10


def test_packed_counts_mixed_key_widths():
    acc = PackedCounts()
    # same word arriving from a 16-byte rung (k=4) and a 64-byte rung
    # (k=16) must merge: zero-padded lanes agree beyond the word
    acc.add(*_rows(["alpha", "beta"], [2, 3], k=4))
    acc.add(*_rows(["alpha", "gamma"], [5, 7], k=16))
    out = acc.finalize()
    assert {w: c for w, (c, _) in out.items()} == {
        "alpha": 7, "beta": 3, "gamma": 7}


def test_packed_counts_empty_and_large_counts():
    assert PackedCounts().finalize() == {}
    acc = PackedCounts()
    big = (1 << 31) + 5
    for _ in range(3):
        acc.add(*_rows(["x"], [big], k=4))
    assert acc.finalize()["x"][0] == 3 * big  # int64, no uint32 wrap


def test_packed_counts_ignores_empty_batches():
    acc = PackedCounts()
    acc.add(np.zeros((0, 4), np.uint32), np.zeros(0, np.int32),
            np.zeros(0, np.int64), np.zeros(0, np.int32))
    assert acc.finalize() == {}


def test_postings_table_matches_dict_oracle():
    rng = random.Random(11)
    vocab = ["".join(rng.choices("mnopqr", k=rng.randint(1, 8)))
             for _ in range(60)]
    kk = 4
    oracle: dict = {}
    table = PostingsTable()
    for wave in range(10):
        rows = []
        for w in set(rng.choices(vocab, k=20)):
            tf = rng.randint(1, 9)
            doc = rng.randint(0, 30)
            part = hash(w) % 10
            row = np.concatenate([
                _pack_word(w, kk),
                np.array([len(w), tf, doc, part], dtype=np.uint32)])
            rows.append(row)
            ent = oracle.setdefault(w, (part, []))
            ent[1].append((doc, tf))
        table.add(np.stack(rows), kk)
    out = table.finalize()
    assert set(out) == set(oracle)
    for w in oracle:
        assert out[w][0] == oracle[w][0]
        assert sorted(out[w][1]) == sorted(oracle[w][1])


def test_postings_table_empty_and_width_guard():
    assert PostingsTable().finalize() == {}
    t = PostingsTable()
    t.add(np.zeros((1, 8), np.uint32), 4)
    with pytest.raises(ValueError):
        t.add(np.zeros((1, 20), np.uint32), 16)


# ── the result is the merged table; mr-out-* is rendered from its arrays ──


_COUNTS = (1, 9, 10, 99, 100, 1 << 31, (1 << 53) + 1, 1 << 62)


def _acc_of(batches, k=4, part=lambda w: sum(map(ord, w)) % 10, **kw):
    """An accumulator fed ``batches`` of ``(word, count)`` pairs, and the
    ``{word: (count, partition)}`` a plain loop makes of them."""
    acc = PackedCounts(**kw)
    oracle: dict = {}
    for batch in batches:
        words = [w for w, _ in batch]
        keys, lens, cnts, _ = _rows(words, [c for _, c in batch], k)
        acc.add(keys, lens, cnts,
                np.array([part(w) for w in words], dtype=np.int32))
        for w, c in batch:
            oracle[w] = (oracle.get(w, (0, 0))[0] + c, part(w))
    return acc, oracle


def _random_batches(seed, alphabet, longest, n_words=300, n_batches=12):
    rng = random.Random(seed)
    vocab = sorted({"".join(rng.choices(alphabet, k=rng.randint(1, longest)))
                    for _ in range(n_words)})
    out = []
    for _ in range(n_batches):
        local = Counter(rng.choices(vocab, k=rng.randint(1, 80)))
        out.append(sorted(local.items()))
    return out


def _tail_case(name):
    """``(accumulator, oracle dict, n_reduce)`` of one writer-parity case."""
    letters = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
    if name == "lanes4":
        return (*_acc_of(_random_batches(3, letters, 16), k=4,
                         compact_rows=128), 10)
    if name == "lanes16":
        # words past 16 bytes: the table's own width is 16 lanes
        return (*_acc_of(_random_batches(5, letters, 64), k=16,
                         compact_rows=128), 10)
    if name == "mixed-widths":
        acc, oracle = _acc_of(_random_batches(7, "abc", 9), k=4)
        wide, more = _acc_of(_random_batches(8, "abc", 40), k=16)
        for buf in wide._bufs:
            acc.add(*buf)
        for w, (c, p) in more.items():
            oracle[w] = (oracle.get(w, (0, 0))[0] + c, p)
        return acc, oracle, 10
    if name == "count-digits":
        batch = [(f"w{chr(97 + i)}", c) for i, c in enumerate(_COUNTS)]
        return (*_acc_of([batch, [("solo", 7)]]), 10)
    if name == "prefix-chains-mixed-case":
        words = ["a", "ab", "abc", "abcd", "A", "Ab", "aB", "AB", "Z", "z",
                 "Za", "zA", "b", "B", "abcdefghijklmnop", "abcdefghijklmno"]
        rng = random.Random(9)
        batches = [[(w, rng.randint(1, 500)) for w in
                    rng.sample(words, len(words))] for _ in range(3)]
        # all in two partitions, so that a file holds whole chains
        return (*_acc_of(batches, part=lambda w: len(w) % 2), 2)
    if name == "empty-table":
        return PackedCounts(), {}, 10
    if name == "empty-partitions":
        return (*_acc_of(_random_batches(11, letters, 12),
                         part=lambda w: (2, 7)[len(w) % 2]), 10)
    if name == "one-partition":
        return (*_acc_of(_random_batches(13, letters, 12),
                         part=lambda w: 3), 5)
    if name == "single-buffer-device-order":
        # one never-compacted buffer: the device's order, not the table's
        words = ["pear", "Apple", "fig", "apple", "figs", "Zebra", "kiwi",
                 "a", "quince", "ap"]
        acc, oracle = _acc_of([[(w, i + 1) for i, w in enumerate(words)]],
                              part=lambda w: len(w) % 4)
        assert len(acc._bufs) == 1 and acc.stats["merge_compacts"] == 0
        return acc, oracle, 4
    if name == "snapshot-restore":
        acc, oracle = _acc_of(_random_batches(17, letters, 16),
                              compact_rows=64)
        image = {k: v.copy() for k, v in acc.snapshot().items()}
        back = PackedCounts()
        back.restore(image)
        return back, oracle, 10
    if name == "snapshot-restore-single-buffer":
        acc, oracle = _acc_of([[("delta", 4), ("Beta", 2), ("alpha", 1),
                                ("gamma", 3)]], part=lambda w: ord(w[0]) % 3)
        back = PackedCounts()
        back.restore(acc.snapshot())
        return back, oracle, 3
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "lanes4", "lanes16", "mixed-widths", "count-digits",
    "prefix-chains-mixed-case", "empty-table", "empty-partitions",
    "one-partition", "single-buffer-device-order", "snapshot-restore",
    "snapshot-restore-single-buffer"])
def test_table_rendering_equals_dict_formatting_byte_for_byte(tmp_path,
                                                              name):
    from dsi_tpu.parallel.merge import PackedWordCounts
    from dsi_tpu.parallel.shuffle import write_partitioned_output

    acc, oracle, n_reduce = _tail_case(name)
    table = acc.finalize()
    assert isinstance(table, PackedWordCounts) and len(table) == len(oracle)
    # an independent rendering: the reduce task's loop, written out
    want = [("".join(f"{w} {oracle[w][0]}\n" for w in sorted(oracle)
                     if oracle[w][1] == r)).encode("ascii")
            for r in range(n_reduce)]
    stats: dict = {}
    got = {}
    for how, result in (("table", table), ("dict", dict(oracle))):
        out = tmp_path / how
        out.mkdir()
        paths = write_partitioned_output(result, n_reduce, str(out),
                                         stats=stats)
        # an empty partition still commits an empty file; no temp is left
        assert sorted(os.listdir(out)) == sorted(
            f"mr-out-{r}" for r in range(n_reduce))
        got[how] = [open(p, "rb").read() for p in paths]
    assert got["table"] == got["dict"] == want
    assert stats["write_rows_packed"] == stats["write_rows_dict"] \
        == len(oracle)
    # the writer and len() read the arrays: no spelling became a str
    assert table.stats["finalize_decoded_keys"] == 0
    assert table.stats["finalize_decode_s"] == 0.0
    assert table == oracle and table.stats["finalize_decoded_keys"] \
        == len(oracle)


def test_count_digits_print_exactly():
    acc, _ = _acc_of([[("w", c)] for c in _COUNTS])
    table = acc.finalize()
    assert table.render_partition(ord("w") % 10) \
        == f"w {sum(_COUNTS)}\n".encode()
    for c in _COUNTS + (0, 10 ** 18, (1 << 63) - 1):
        acc, _ = _acc_of([[("n", c)]], part=lambda w: 0)
        assert acc.finalize().render_partition(0) == f"n {c}\n".encode(), c


def test_result_is_a_read_only_mapping_decoded_on_demand():
    from collections.abc import Mapping

    acc, oracle = _acc_of(_random_batches(19, "xyzXYZ", 10),
                          compact_rows=32)
    table = acc.finalize()
    assert isinstance(table, Mapping) and not isinstance(table, dict)
    stats = table.stats
    assert stats is acc.stats
    assert len(table) == len(oracle) and "decoded=False" in repr(table)
    assert stats["finalize_decoded_keys"] == 0
    word = next(iter(oracle))
    assert table[word] == oracle[word]               # the first keyed access
    assert stats["finalize_decoded_keys"] == len(oracle)
    assert stats["finalize_decode_s"] > 0
    assert word in table and "no such word" not in table
    assert table.get("no such word") is None
    assert dict(table.items()) == oracle == dict(zip(table.keys(),
                                                     table.values()))
    assert sorted(table) == sorted(oracle)
    assert table == oracle and oracle == table and not table != oracle
    assert table == acc.finalize() and table != {}
    assert stats["finalize_decoded_keys"] == 2 * len(oracle)  # the second's
    with pytest.raises(TypeError):
        table[word] = (1, 1)
    with pytest.raises(TypeError):
        hash(table)
    empty = PackedCounts().finalize()
    assert empty == {} and len(empty) == 0 and list(empty) == []


def test_writer_refuses_a_row_outside_the_partitions(tmp_path):
    from dsi_tpu.parallel.shuffle import write_partitioned_output

    acc, oracle = _acc_of([[("in", 1), ("out", 2)]],
                          part=lambda w: 5 if w == "out" else 1)
    with pytest.raises(ValueError, match="1 of 2 words"):
        write_partitioned_output(acc.finalize(), 3, str(tmp_path))
    with pytest.raises(IndexError):
        write_partitioned_output(oracle, 3, str(tmp_path))
    assert os.listdir(tmp_path) == []
