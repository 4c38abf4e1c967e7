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
        acc.add(*wide.snapshot().values())
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
        # one batch in no order of the table's, never compacted before
        # ``finalize``: sorted on entry, alone
        words = ["pear", "Apple", "fig", "apple", "figs", "Zebra", "kiwi",
                 "a", "quince", "ap"]
        acc, oracle = _acc_of([[(w, i + 1) for i, w in enumerate(words)]],
                              part=lambda w: len(w) % 4)
        assert len(acc._window) == 1 and acc._table is None
        assert acc.stats["merge_compacts"] == 0
        assert acc.stats["merge_runs_unsorted"] == 1
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


# ── the accumulator merges sorted runs: against two independent oracles ──


def _parent_table(batches):
    """What the accumulator's parent did, kept as a test helper only:
    every row of every batch one behind the other, one ``np.lexsort``
    over the lanes, the counts summed over each run of equal keys."""
    batches = [b for b in batches if len(b[0])]
    if not batches:
        return (np.zeros((0, 1), np.uint32), np.zeros(0, np.int32),
                np.zeros(0, np.int64), np.zeros(0, np.int32))
    k = max(b[0].shape[1] for b in batches)
    keys = np.concatenate([np.pad(np.asarray(b[0], np.uint32),
                                  ((0, 0), (0, k - b[0].shape[1])))
                           for b in batches])
    lens, cnts, parts = (np.concatenate([np.asarray(b[i]) for b in batches])
                         for i in (1, 2, 3))
    order = np.lexsort(tuple(keys[:, j] for j in range(k - 1, -1, -1)))
    skeys = keys[order]
    starts = np.flatnonzero(np.r_[True, (skeys[1:] != skeys[:-1]).any(1)])
    return (skeys[starts], lens[order][starts].astype(np.int32),
            np.add.reduceat(cnts[order].astype(np.int64), starts),
            parts[order][starts].astype(np.int32))


def _dict_oracle(batches):
    """``{key lanes without their zero padding: [count, len, part]}`` by
    a Python loop a row."""
    out: dict = {}
    for keys, lens, cnts, parts in batches:
        for row, ln, c, p in zip(np.asarray(keys).tolist(), lens, cnts,
                                 parts):
            while row and row[-1] == 0:
                row.pop()
            ent = out.setdefault(tuple(row), [0, int(ln), int(p)])
            ent[0] += int(c)
    return out


def _word_batch(words, counts, k):
    keys, lens, cnts, _ = _rows(words, counts, k)
    return keys, lens, cnts, np.array([sum(map(ord, w)) % 7 for w in words],
                                      dtype=np.int32)


def _sorted_batches(seed, n_batches, k=4, longest=12, vocab=400,
                    alphabet="abcdefghijklmnop", most=60):
    """Batches as a device hands them over: distinct words, in order."""
    rng = random.Random(seed)
    words = sorted({"".join(rng.choices(alphabet, k=rng.randint(1, longest)))
                    for _ in range(vocab)})
    out = []
    for _ in range(n_batches):
        local = Counter(rng.choices(words, k=rng.randint(1, most)))
        out.append(_word_batch(sorted(local), [local[w] for w in
                                               sorted(local)], k))
    return out


def _merge_case(name):
    """``(batches, compact_rows, batches that arrive unsorted)``."""
    rng = random.Random(len(name))
    if name == "sorted-batches":
        return _sorted_batches(1, 20), 256, 0
    if name == "unsorted-and-duplicates":
        out = []
        for _ in range(12):  # a caller's arbitrary ``add``
            words = rng.choices(["ab", "abc", "b", "Zed", "zed", "q" * 11,
                                 "mnop", "a"], k=rng.randint(2, 30))
            out.append(_word_batch(words, [rng.randint(1, 9) for _ in words],
                                   4))
        return out + _sorted_batches(2, 6), 64, 12
    if name == "mixed-lane-widths":
        out = []
        for i, k in enumerate((2, 4, 8, 4, 2, 8, 8, 2, 4)):
            out += _sorted_batches(10 + i, 3, k=k, longest=4 * k, vocab=80)
        return out, 128, 0
    if name == "empty-batches":
        empty = (np.zeros((0, 4), np.uint32), np.zeros(0, np.int32),
                 np.zeros(0, np.int64), np.zeros(0, np.int32))
        real = _sorted_batches(3, 6)
        return [empty, real[0], empty, empty] + real[1:] + [empty], 64, 0
    if name == "one-batch-never-compacted":
        return _sorted_batches(4, 1), 1 << 21, 0
    if name == "window-never-reaches-compact-rows":
        return _sorted_batches(5, 25), 1 << 21, 0
    if name == "dozens-of-compactions":
        return _sorted_batches(6, 120, vocab=900), 48, 0
    if name == "shared-first-eight-bytes":
        # the tie case of a sort on the first two lanes packed: words of
        # one eight-byte stem that differ in lanes 2 and 3, and the stem
        stems = ["internat", "abcdefgh", "Abcdefgh"]
        tails = ["", "a", "b", "ional", "ionally", "ionale", "zzzzzzzz",
                 "ab", "ba", "ionalism"]
        words = sorted({s + t for s in stems for t in tails}
                       | {"intern", "abc", "zebra"})
        out = []
        for _ in range(30):
            local = sorted(rng.sample(words, rng.randint(3, len(words))))
            out.append(_word_batch(local, [rng.randint(1, 5) for _ in local],
                                   4))
        return out, 100, 0
    if name == "counts-past-2-to-the-32":
        big = (1 << 31) + 12345
        return [_word_batch(["alpha", "beta", "x"], [big, 7, big], 4)
                for _ in range(9)], 8, 0
    raise AssertionError(name)


_MERGE_CASES = ["sorted-batches", "unsorted-and-duplicates",
                "mixed-lane-widths", "empty-batches",
                "one-batch-never-compacted",
                "window-never-reaches-compact-rows", "dozens-of-compactions",
                "shared-first-eight-bytes", "counts-past-2-to-the-32"]


@pytest.fixture(params=["native", "numpy"])
def merge_route(request, monkeypatch):
    """Both routes of a compaction: ``native/mergeruns.cpp``, and numpy
    alone, as ``DSI_NO_NATIVE=1`` leaves it."""
    from dsi_tpu import native

    if request.param == "numpy":
        monkeypatch.setattr(native, "_lib", False)
    elif not native.available():
        pytest.skip("no native library on this host")
    return request.param


def _run_case(batches, compact_rows):
    stats: dict = {}
    acc = PackedCounts(compact_rows=compact_rows, stats=stats)
    for b in batches:
        acc.add(*b)
    return acc, acc.finalize(), stats


_COUNTERS = ("merge_rows_in", "merge_rows_sorted", "merge_compacts",
             "merge_runs_in", "merge_runs_unsorted")


@pytest.mark.parametrize("name", _MERGE_CASES)
def test_accumulator_equals_the_parents_sort_and_a_dict(merge_route, name):
    batches, compact_rows, unsorted = _merge_case(name)
    acc, table, stats = _run_case(batches, compact_rows)
    want = _parent_table(batches)
    # the parent's table row for row: lanes, lengths, int64 sums, partitions
    for got, exp in zip((table.skeys, table.lens, table.cnts, table.parts),
                        want):
        assert got.dtype == exp.dtype and np.array_equal(got, exp)
    assert table.cnts.dtype == np.int64
    oracle = _dict_oracle(batches)
    assert len(table) == len(oracle)
    for row, ln, c, p in zip(table.skeys.tolist(), table.lens.tolist(),
                             table.cnts.tolist(), table.parts.tolist()):
        while row and row[-1] == 0:
            row.pop()
        assert oracle[tuple(row)] == [c, ln, p]
    given = [b for b in batches if len(b[0])]
    rows = sum(len(b[0]) for b in given)
    assert stats["merge_rows_in"] == rows
    assert stats["merge_runs_in"] == len(given)
    assert stats["merge_runs_unsorted"] == unsorted
    # every row is ordered once in its window, an unsorted batch's once
    # more on entry; the merged table's rows never
    sorted_on_entry = sum(len(b[0]) for b in batches[:unsorted])
    after_entry = rows - sorted_on_entry + sum(
        len(set(map(tuple, b[0].tolist()))) for b in batches[:unsorted])
    assert stats["merge_rows_sorted"] == sorted_on_entry + after_entry
    if name == "dozens-of-compactions":
        assert stats["merge_compacts"] >= 36
    if "never" in name:
        assert stats["merge_compacts"] == 1  # the one in ``finalize``
    if name == "counts-past-2-to-the-32":
        assert table["alpha"][0] == 9 * ((1 << 31) + 12345) > 1 << 32


@pytest.mark.parametrize("name", _MERGE_CASES)
def test_both_routes_count_alike_and_repeat(monkeypatch, name):
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library on this host")
    batches, compact_rows, _ = _merge_case(name)
    _, first, stats = _run_case(batches, compact_rows)
    _, again, stats2 = _run_case(batches, compact_rows)
    monkeypatch.setattr(native, "_lib", False)
    _, plain, stats3 = _run_case(batches, compact_rows)
    counts = {k: stats[k] for k in _COUNTERS}
    assert counts == {k: stats2[k] for k in _COUNTERS} \
        == {k: stats3[k] for k in _COUNTERS}
    for a, b, c in zip(*((t.skeys, t.lens, t.cnts, t.parts)
                         for t in (first, again, plain))):
        assert a.tobytes() == b.tobytes() == c.tobytes()


def test_compactions_follow_the_rows_handed_over_not_the_table(merge_route):
    """The trigger counts the window alone: a vocabulary of three times
    ``compact_rows`` words compacts once every ``compact_rows`` rows, not
    once a batch as soon as the table is that large."""
    compact_rows, per_batch, n_batches = 256, 32, 96
    rng = np.random.default_rng(43)
    words = np.sort(rng.choice(1 << 20, 3 * compact_rows, replace=False))
    stats: dict = {}
    acc = PackedCounts(compact_rows=compact_rows, stats=stats)
    oracle = Counter()
    for _ in range(n_batches):
        pick = np.sort(rng.choice(words, per_batch, replace=False))
        keys = np.stack([pick >> 8, pick & 0xFF], axis=1).astype(np.uint32)
        acc.add(keys, np.full(per_batch, 5), np.ones(per_batch, np.int64),
                pick % 3)
        oracle.update(pick.tolist())
    table = acc.finalize()
    assert len(table) == len(oracle) > 2 * compact_rows
    assert table.cnts.sum() == n_batches * per_batch
    got = dict(zip(((table.skeys[:, 0].astype(np.int64) << 8)
                    | table.skeys[:, 1]).tolist(), table.cnts.tolist()))
    assert got == dict(oracle)
    # 96 batches of 32 rows: a window of 256 rows fills every 8 batches
    assert stats["merge_compacts"] == n_batches * per_batch // compact_rows
    assert stats["merge_rows_sorted"] == stats["merge_rows_in"]


def _images_equal(a, b):
    return sorted(a) == sorted(b) and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a)


def test_snapshot_restore_finalize_equals_the_uninterrupted_run(merge_route):
    batches = _sorted_batches(21, 40, vocab=700)
    _, whole, _ = _run_case(batches, 128)
    acc = PackedCounts(compact_rows=128)
    for b in batches[:17]:
        acc.add(*b)
    image = {k: v.copy() for k, v in acc.snapshot().items()}
    assert sorted(image) == ["cnts", "keys", "lens", "parts"]
    assert acc._window == []  # compacted first: the image is the table
    back = PackedCounts(compact_rows=128)
    back.restore(image)
    for b in batches[17:]:
        acc.add(*b)
        back.add(*b)
    for table in (acc.finalize(), back.finalize()):
        assert np.array_equal(table.skeys, whole.skeys)
        assert np.array_equal(table.cnts, whole.cnts)
        assert np.array_equal(table.lens, whole.lens)
        assert np.array_equal(table.parts, whole.parts)
    # what a snapshot handed out is not written again by a later merge
    assert _images_equal(image, {k: v for k, v in zip(
        ("keys", "lens", "cnts", "parts"), _parent_table(batches[:17]))})
    assert PackedCounts().snapshot() == {}
    back.restore({})
    assert len(back.finalize()) == 0


def test_an_image_of_the_parents_layout_restores(merge_route):
    """The parent wrote whatever its one buffer held: a compacted table
    (sorted, distinct), or a single batch as it was handed over, in any
    order.  Both install as the merged table."""
    batches = _sorted_batches(22, 9)
    compacted = dict(zip(("keys", "lens", "cnts", "parts"),
                         _parent_table(batches)))
    words = ["pear", "Apple", "fig", "apple", "figs", "Zebra", "kiwi"]
    raw = dict(zip(("keys", "lens", "cnts", "parts"),
                   _word_batch(words, range(1, 8), 4)))
    more = _sorted_batches(23, 5)
    for image, before in ((compacted, batches), (raw, [tuple(
            raw[k] for k in ("keys", "lens", "cnts", "parts"))])):
        stats: dict = {}
        acc = PackedCounts(compact_rows=64, stats=stats)
        acc.restore(image)
        assert acc._window == [] and stats["merge_runs_in"] == 0
        assert stats["merge_runs_unsorted"] == 0  # an image is no batch
        for b in more:
            acc.add(*b)
        table = acc.finalize()
        for got, exp in zip((table.skeys, table.lens, table.cnts,
                             table.parts), _parent_table(before + more)):
            assert np.array_equal(got, exp)
        # and the change's image is the parent's, name for name
        assert _images_equal(
            acc.snapshot(), dict(zip(("keys", "lens", "cnts", "parts"),
                                     _parent_table(before + more))))


# ── a full window is compacted on a merger thread ──


class _InlineCounts(PackedCounts):
    """The accumulator as it was until a full window went to a merger
    thread, kept as the plain reference: every compaction on the
    caller's thread, inside the ``add`` that filled the window."""

    def add(self, keys, lens, cnts, parts):
        compact_rows, self._compact_rows = self._compact_rows, 1 << 62
        try:
            super().add(keys, lens, cnts, parts)
        finally:
            self._compact_rows = compact_rows
        if self._pending >= compact_rows:
            self._compact()


def _compact_rows_for(batches, handovers, snapshot_at=None):
    """The largest ``compact_rows`` at which ``batches`` fill the window
    ``handovers`` times (a snapshot before batch ``snapshot_at`` empties
    it)."""
    def fills(compact_rows):
        n = pending = 0
        for i, b in enumerate(batches):
            if i == snapshot_at:
                pending = 0
            pending += len(b[0])
            if pending >= compact_rows:
                n, pending = n + 1, 0
        return n

    total = sum(len(b[0]) for b in batches)
    return next(c for c in range(total, 0, -1) if fills(c) == handovers)


#: A wait for the merger ends when its thread has ended, a little after
#: the ``compact`` span it waited for: what ``compact_caller_s`` may read
#: over ``compact_s`` on a busy host.
_JOIN_SLACK_S = 0.05


def _mergers():
    import threading

    return [t for t in threading.enumerate()
            if t.name == "dsi-merge-compact"]


def _tables_equal(a, b):
    return all(x.dtype == y.dtype and x.tobytes() == y.tobytes()
               for x, y in zip((a.skeys, a.lens, a.cnts, a.parts),
                               (b.skeys, b.lens, b.cnts, b.parts)))


@pytest.mark.parametrize("handovers", [1, 2, 5])
def test_a_handed_over_window_merges_as_the_inline_compaction(
        merge_route, handovers):
    """Result, counters and a mid-stream checkpoint image are those of
    the accumulator that compacts inline, whichever thread merged."""
    batches = _sorted_batches(52, 60, vocab=900) \
        + _merge_case("unsorted-and-duplicates")[0][:3]
    cut = 2 * len(batches) // 3
    compact_rows = _compact_rows_for(batches, handovers, cut)
    stats, want_stats = {}, {}
    acc = PackedCounts(compact_rows=compact_rows, stats=stats)
    want = _InlineCounts(compact_rows=compact_rows, stats=want_stats)
    for b in batches[:cut]:
        acc.add(*b)
        want.add(*b)
    image, want_image = acc.snapshot(), want.snapshot()
    assert _images_equal(image, want_image)
    assert acc._window == [] and acc._inflight is None
    back = PackedCounts(compact_rows=compact_rows)
    back.restore({k: v.copy() for k, v in image.items()})
    for b in batches[cut:]:
        for a in (acc, want, back):
            a.add(*b)
    table = want.finalize()
    assert _tables_equal(acc.finalize(), table)
    assert _tables_equal(back.finalize(), table)
    assert {k: stats[k] for k in _COUNTERS} \
        == {k: want_stats[k] for k in _COUNTERS}
    assert stats["merge_compacts"] > stats["merge_compacts_async"] \
        == handovers
    assert want_stats["merge_compacts_async"] == 0
    # the inline one was held for every second of its compactions
    assert want_stats["compact_caller_s"] == pytest.approx(
        want_stats["compact_s"])
    assert 0.0 < stats["compact_caller_s"] \
        <= stats["compact_s"] + _JOIN_SLACK_S
    assert not _mergers()


def test_a_second_full_window_waits_for_the_first(monkeypatch):
    """One compaction in flight at most: the ``add`` that fills the next
    window is held, in ``compact_caller_s``, until the first is merged."""
    import threading

    from dsi_tpu.parallel import merge

    gate, merging, most = threading.Event(), [], [0]
    merge_runs = merge._merge_runs

    def held(runs):
        if threading.current_thread().name == "dsi-merge-compact":
            merging.append(1)
            most[0] = max(most[0], len(merging))
            assert gate.wait(30)
            try:
                return merge_runs(runs)
            finally:
                merging.pop()
        return merge_runs(runs)

    monkeypatch.setattr(merge, "_merge_runs", held)
    batches = _sorted_batches(53, 12, most=40)
    stats: dict = {}
    acc = PackedCounts(compact_rows=_compact_rows_for(batches, 2),
                       stats=stats)
    for b in batches:
        acc.add(*b)
        if stats["merge_compacts_async"] == 1:
            break
    assert stats["merge_runs_in"] < len(batches) and len(_mergers()) == 1
    assert stats["compact_caller_s"] == 0.0
    timer = threading.Timer(0.3, gate.set)
    timer.start()
    for b in batches[stats["merge_runs_in"]:]:
        acc.add(*b)  # one of them fills the window, and waits
    timer.join(30)
    assert stats["merge_compacts_async"] == 2 and most[0] == 1
    assert stats["compact_caller_s"] >= 0.2
    table = acc.finalize()
    assert stats["compact_caller_s"] <= stats["compact_s"] + _JOIN_SLACK_S
    assert _tables_equal(table, _run_case(batches, 1 << 21)[1])
    assert not _mergers()


@pytest.mark.parametrize("call", ["add", "finalize", "snapshot", "restore"])
def test_what_the_merger_raises_is_raised_in_the_caller(monkeypatch, call):
    import threading

    from dsi_tpu.parallel import merge

    merge_runs = merge._merge_runs

    def broken(runs):
        if threading.current_thread().name == "dsi-merge-compact":
            raise MemoryError("no room for the window")
        return merge_runs(runs)

    monkeypatch.setattr(merge, "_merge_runs", broken)
    batches = _sorted_batches(54, 8)
    acc = PackedCounts(compact_rows=_compact_rows_for(batches[:6], 1))
    for b in batches[:6]:
        acc.add(*b)
    assert acc.stats["merge_compacts_async"] == 1
    acc._inflight.join(30)  # over: the next call of any kind finds it
    with pytest.raises(MemoryError, match="no room for the window"):
        {"add": lambda: acc.add(*batches[6]), "finalize": acc.finalize,
         "snapshot": acc.snapshot, "restore": lambda: acc.restore({})}[call]()
    assert not _mergers()


def test_no_thread_where_the_window_never_fills_or_is_given_up(monkeypatch):
    from dsi_tpu.parallel import merge

    batches = _sorted_batches(55, 25)
    started = []
    start = merge._Compaction.start
    monkeypatch.setattr(merge._Compaction, "start",
                        lambda self: (started.append(self), start(self)))
    acc, table, stats = _run_case(batches, 1 << 21)
    assert acc.snapshot() and not started
    assert stats["merge_compacts_async"] == 0 \
        and stats["merge_compacts"] == 1
    # given up with a compaction in flight: ``close`` leaves none behind
    acc = PackedCounts(compact_rows=_compact_rows_for(batches, 1))
    for b in batches:
        acc.add(*b)
    assert len(started) == 1
    acc.close()
    assert not started[0].is_alive() and not _mergers()
    acc.close()  # and again, with nothing in flight


def test_counters_hold_while_merger_and_caller_switch_at_every_turn(
        merge_route):
    """The two threads share ``stats``: with the interpreter switching
    threads as often as it can, dozens of hand-overs, unsorted batches
    among them, lose no update of any counter."""
    import sys

    batches = _merge_case("unsorted-and-duplicates")[0] \
        + _sorted_batches(56, 150, vocab=900)
    rng = random.Random(56)
    rng.shuffle(batches)
    want_stats: dict = {}
    want = _InlineCounts(compact_rows=48, stats=want_stats)
    for b in batches:
        want.add(*b)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        acc, table, stats = _run_case(batches, 48)
    finally:
        sys.setswitchinterval(interval)
    assert _tables_equal(table, want.finalize())
    assert {k: stats[k] for k in _COUNTERS} \
        == {k: want_stats[k] for k in _COUNTERS}
    assert stats["merge_compacts_async"] == stats["merge_compacts"] - 1 > 30
    assert 0.0 < stats["compact_caller_s"] \
        <= stats["compact_s"] + _JOIN_SLACK_S
    assert not _mergers()


def test_native_merge_refuses_a_table_it_cannot_read():
    """``mergeruns.cpp`` reads raw pointers: a column of another dtype,
    stride or length, lanes of another width, and an output without room
    are refused before the call."""
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library on this host")
    a = _sorted_batches(31, 1)[0]
    b = _sorted_batches(32, 1)[0]
    a, b = (tuple(np.ascontiguousarray(x) for x in t) for t in (a, b))

    def room(n, k=4):
        return (np.empty((n, k), np.uint32), np.empty(n, np.int32),
                np.empty(n, np.int64), np.empty(n, np.int32))

    n = native.merge_runs2(a, b, room(len(a[0]) + len(b[0])))
    assert max(len(a[0]), len(b[0])) <= n <= len(a[0]) + len(b[0])
    with pytest.raises(ValueError, match="no room"):
        native.merge_runs2(a, b, room(len(a[0])))
    out = room(len(a[0]) + len(b[0]))
    for bad in ((a[0], a[1].astype(np.int64), a[2], a[3]),   # dtype
                (a[0], a[1], a[2][::-1][::-1][:-1], a[3]),   # length
                (a[0][:, :2], a[1], a[2], a[3]),             # stride, width
                (np.zeros((len(a[0]), 8), np.uint32),) + a[1:]):  # width
        with pytest.raises(ValueError):
            native.merge_runs2(bad, b, out)
    with pytest.raises(ValueError):
        native.rows_increase(a[0][:, ::2])
    with pytest.raises(ValueError):
        native.rows_increase(a[0].astype(np.int64))
    assert native.rows_increase(a[0]) is True
    assert native.rows_increase(np.ascontiguousarray(a[0][::-1])) is False


# ── the postings table merges the runs its rows arrive in ──


_FIELDS = ("skeys", "lens", "parts", "starts", "ends", "tfs", "docs")


def _lexsort_group(bufs, kk):
    """What ``PostingsTable._group`` did until it merged runs, kept as the
    oracle: every row one behind the other, one stable ``np.lexsort``
    over the key lanes, the table read through the permutation."""
    rows = np.concatenate(bufs)
    keys = rows[:, :kk]
    order = np.lexsort(tuple(keys[:, j] for j in range(kk - 1, -1, -1)))
    skeys = keys[order]
    starts = np.flatnonzero(np.r_[True, (skeys[1:] != skeys[:-1]).any(1)])
    return {"skeys": np.ascontiguousarray(skeys[starts]), "starts": starts,
            "ends": np.append(starts[1:], len(rows)),
            "lens": rows[order[starts], kk],
            "parts": rows[order[starts], kk + 3],
            "tfs": np.ascontiguousarray(rows[order, kk + 1]),
            "docs": np.ascontiguousarray(rows[order, kk + 2])}


def _wave(rng, vocab, kk, docs, most):
    """A wave's posting rows as the device leaves them: a row a distinct
    word a document, in (word, document) order."""
    rows = []
    for doc in docs:
        for w in rng.sample(vocab, rng.randint(1, min(most, len(vocab)))):
            rows.append((w, doc))
    rows.sort()
    return np.stack([np.concatenate([
        _pack_word(w, kk),
        np.array([len(w), rng.randint(1, 99), doc, sum(map(ord, w)) % 10],
                 np.uint32)]) for w, doc in rows])


def _vocab(rng, n, longest, alphabet="abcdefghijklmnop"):
    return sorted({"".join(rng.choices(alphabet, k=rng.randint(1, longest)))
                   for _ in range(n)})


def _group_case(name):
    """``(buffers handed to add, kk, runs merged or None, rows sorted)``."""
    rng = random.Random(name)
    if name.startswith("runs-"):  # a document a wave: a word once a run
        n = int(name[5:])
        vocab = _vocab(rng, 200, 12)
        return [_wave(rng, vocab, 4, [d], 40) for d in range(n)], 4, n, 0
    if name == "shared-words-a-page-wave":
        # ties across runs and within one: a word once a document
        vocab = _vocab(rng, 30, 6)
        return [_wave(rng, vocab, 4, range(9 * i, 9 * i + 9), 25)
                for i in range(12)], 4, 12, 0
    if name == "a-drain-of-five-waves":  # one buffer, several runs
        vocab = _vocab(rng, 400, 10)
        waves = [_wave(rng, vocab, 4, [2 * i, 2 * i + 1], 150)
                 for i in range(5)]
        return [np.concatenate(waves), _wave(rng, vocab, 4, [10], 50)], \
            4, None, 0
    if name == "shuffled":  # no runs to speak of: sorted on entry
        vocab = _vocab(rng, 300, 10)
        rows = _wave(rng, vocab, 4, range(4), 200)
        rng.shuffle(order := list(range(len(rows))))
        return [_wave(rng, vocab, 4, [7], 90), rows[order],
                _wave(rng, vocab, 4, [8], 90)], 4, 3, len(rows)
    if name.startswith("kk-"):
        kk = int(name[3:])
        vocab = _vocab(rng, 150, 4 * kk)
        return [_wave(rng, vocab, kk, [2 * i, 2 * i + 1], 60)
                for i in range(9)], kk, 9, 0
    if name == "shared-first-eight-bytes":
        stems = ["internat", "abcdefgh", "Abcdefgh"]
        tails = ["", "a", "b", "ional", "ionally", "ionale", "zzzzzzzz",
                 "ab", "ba", "ionalism"]
        vocab = sorted({s + t for s in stems for t in tails}
                       | {"intern", "abc", "zebra"})
        return [_wave(rng, vocab, 4, [3 * i, 3 * i + 1, 3 * i + 2], 33)
                for i in range(20)], 4, 20, 0
    raise AssertionError(name)


_GROUP_CASES = ["runs-1", "runs-2", "runs-7", "runs-300",
                "shared-words-a-page-wave", "a-drain-of-five-waves",
                "shuffled", "kk-1", "kk-2", "kk-4", "kk-8",
                "shared-first-eight-bytes"]


def _grouped(bufs, kk):
    table = PostingsTable()
    for b in bufs:
        table.add(b, kk)
    stats: dict = {}
    return table, table.finalize_packed(stats), stats


def _assert_fields(got, want):
    for f in _FIELDS:
        a, b = getattr(got, f), want[f]
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert a.flags.c_contiguous and np.array_equal(a, b), f


@pytest.mark.parametrize("name", _GROUP_CASES)
def test_group_equals_the_stable_lexsort_field_for_field(merge_route, name):
    bufs, kk, runs, rows_sorted = _group_case(name)
    _, got, stats = _grouped(bufs, kk)
    _assert_fields(got, _lexsort_group(bufs, kk))
    assert stats["postings_rows"] == sum(map(len, bufs))
    assert stats["index_terms"] == len(got)
    assert stats["group_rows_sorted"] == rows_sorted
    if runs is not None:
        assert stats["group_runs"] == runs
    else:  # the drain's five waves are found from its rows
        assert 6 <= stats["group_runs"] <= len(bufs[0]) // 8 + 2


@pytest.mark.parametrize("name", _GROUP_CASES)
def test_group_snapshot_restore_groups_bit_identically(merge_route, name):
    """The image is one buffer of every row in insertion order: the runs
    are found again from its rows, and the group is the same."""
    bufs, kk, _, _ = _group_case(name)
    table, whole, _ = _grouped(bufs, kk)
    image = {k: v.copy() for k, v in table.snapshot().items()}
    assert np.array_equal(image["rows"], np.concatenate(bufs))
    back = PostingsTable()
    back.restore(image)
    stats: dict = {}
    _assert_fields(back.finalize_packed(stats), {
        f: getattr(whole, f) for f in _FIELDS})
    assert 1 <= stats["group_runs"]


@pytest.mark.parametrize("name", _GROUP_CASES)
def test_group_routes_agree_byte_for_byte_and_count_alike(monkeypatch,
                                                          name):
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library on this host")
    bufs, kk, _, _ = _group_case(name)
    _, with_lib, counts = _grouped(bufs, kk)
    monkeypatch.setattr(native, "_lib", False)
    _, without, counts_numpy = _grouped(bufs, kk)
    for f in _FIELDS:
        assert getattr(with_lib, f).tobytes() == getattr(without,
                                                         f).tobytes(), f
    for key in ("postings_rows", "index_terms", "group_runs",
                "group_rows_sorted"):
        assert counts[key] == counts_numpy[key], key


def test_group_of_an_empty_table(merge_route):
    from dsi_tpu.parallel.merge import PackedPostings

    stats: dict = {}
    got = PostingsTable().finalize_packed(stats)
    empty = PackedPostings(0)
    _assert_fields(got, {f: getattr(empty, f) for f in _FIELDS})
    assert stats["postings_rows"] == stats["index_terms"] == 0
    assert stats["group_runs"] == stats["group_rows_sorted"] == 0
    table = PostingsTable()
    table.add(np.zeros((0, 8), np.uint32), 4)  # an empty wave is no run
    table.restore({})
    assert len(table.finalize_packed()) == 0


def test_native_group_refuses_rows_it_cannot_read():
    """``mergeruns.cpp`` reads raw pointers: rows of another dtype, width
    or stride, cuts outside their buffer and columns without room are
    refused before the call."""
    from dsi_tpu import native

    if not native.available():
        pytest.skip("no native library on this host")
    bufs, kk, _, _ = _group_case("runs-2")
    n = sum(map(len, bufs))

    def room(rows):
        return (np.empty((rows, kk), np.uint32), np.empty(rows, np.uint32),
                np.empty(rows, np.uint32), np.empty(rows, np.int64),
                np.empty(rows, np.uint32), np.empty(rows, np.uint32))

    none = [np.zeros(0, np.int64)] * 2
    assert native.merge_posting_runs(bufs, none, kk, room(n)) > 0
    with pytest.raises(ValueError, match="room"):
        native.merge_posting_runs(bufs, none, kk, room(n - 1))
    with pytest.raises(ValueError, match="cuts"):
        native.merge_posting_runs(bufs, [np.array([len(bufs[0])]), none[0]],
                                  kk, room(n))
    for bad in (bufs[0].astype(np.int64), bufs[0][:, :-1], bufs[0][::2],
                np.asfortranarray(bufs[0])):
        with pytest.raises(ValueError, match="posting rows"):
            native.merge_posting_runs([bad, bufs[1]], none, kk, room(n))
        with pytest.raises(ValueError, match="posting rows"):
            native.run_cuts(bad, kk, np.empty(4, np.int64))
    with pytest.raises(ValueError, match="cuts"):
        native.run_cuts(bufs[0], kk, np.empty(4, np.int32))
    cuts = np.empty(4, np.int64)
    assert native.run_cuts(bufs[0], kk, cuts) == 0
    assert native.run_cuts(np.ascontiguousarray(bufs[0][::-1]), kk,
                           cuts) >= len(bufs[0]) // 2
    assert cuts[0] >= 1
