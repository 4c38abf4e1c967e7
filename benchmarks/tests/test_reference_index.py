"""The plain reference of a ``planrun --chain indexer`` job against
documents small enough to index by hand, and the cut that makes a job's
documents (``docs.py``), which the reference and the driver share."""

import json
import os
import types

import numpy as np

import corpus
import docs
import reference_index
from drivers import index_inproc

CUT = {"doc_min_bytes": 2_000, "doc_max_bytes": 16_000, "topk": 16}


def test_index_top_and_join_worked_out_by_hand():
    """Three documents.  ``the`` is in all three; ``cat`` and ``Cat`` are
    two words; a digit splits ``x9y`` into ``x`` and ``y``; ``b`` and
    ``dog`` tie at df 2 with ``cat``, and the tie breaks by word."""
    got = reference_index.index_lines(
        [("d00000.txt", b"the cat sat; the cat"),
         ("d00001.txt", b"Cat x9y the dog b"),
         ("d00002.txt", b"dog, cat the b\n")], topk=3)
    assert got == sorted([
        "the 3 d00000.txt,d00001.txt,d00002.txt",
        "cat 2 d00000.txt,d00002.txt", "sat 1 d00000.txt",
        "Cat 1 d00001.txt", "x 1 d00001.txt", "y 1 d00001.txt",
        "dog 2 d00001.txt,d00002.txt", "b 2 d00001.txt,d00002.txt",
        "#top 1 3 the", "#join the 3 d00000.txt,d00001.txt,d00002.txt",
        "#top 2 2 b", "#join b 2 d00001.txt,d00002.txt",
        "#top 3 2 cat", "#join cat 2 d00000.txt,d00002.txt"])


def test_fewer_terms_than_topk_and_an_empty_collection():
    got = reference_index.index_lines([("d00000.txt", b"one two")], topk=16)
    assert [l for l in got if l.startswith("#top")] == [
        "#top 1 1 one", "#top 2 1 two"]
    assert reference_index.index_lines([("d00000.txt", b" 1 2 ")], 16) == []


def _shelves(tmp_path, n, size, seed=5):
    params = corpus.effective({"vocab_per_file": 900}, {})
    paths = []
    for i in range(n):
        p = tmp_path / f"pg-{i:02d}.txt"
        p.write_bytes(corpus.generate_bytes(size, seed * 1000 + i, params))
        paths.append(str(p))
    return paths


def test_every_byte_is_in_exactly_one_document_in_order(tmp_path):
    paths = _shelves(tmp_path, 3, 150_000)
    cut = list(docs.spans(paths, CUT))
    assert [name for name, _ in cut] == [docs.name(i)
                                         for i in range(len(cut))]
    whole = b"".join(open(p, "rb").read() for p in paths)
    assert b"".join(data for _, data in cut) == whole
    sizes = [len(data) for _, data in cut]
    # no document shorter than the shortest or as long as the longest,
    # whatever was left at a file's end; several of each class
    assert min(sizes) >= CUT["doc_min_bytes"]
    assert max(sizes) < CUT["doc_max_bytes"]
    assert len(cut) > 30 and len({s.bit_length() for s in sizes}) == 3
    # no word is cut in two: a document ends at a file's end or on
    # whitespace
    ends = set(np.cumsum([os.path.getsize(p) for p in paths]))
    at = 0
    for _, data in cut:
        at += len(data)
        assert at in ends or data[-1:] in (b" ", b"\n")
    # the same files give the same cut; other files another
    assert [len(d) for _, d in docs.spans(paths, CUT)] == sizes
    assert [len(d) for _, d in docs.spans(paths[::-1], CUT)] != sizes


def test_a_short_tail_joins_or_halves_and_a_short_file_is_one_document():
    rng = np.random.default_rng(1)
    text = b"ab " * 40_000                        # whitespace every 3 B
    for n in (1_000, 2_500, 17_000, 18_100, 33_333, 120_000):
        ends = docs.cuts(text[:n], rng, 2_000, 16_000)
        sizes = np.diff([0] + ends)
        assert ends[-1] == n and (sizes > 0).all()
        if n < 2_000:
            assert len(ends) == 1                 # shorter than any: one
        else:
            assert sizes.min() >= 2_000 and sizes.max() < 16_000, (n, sizes)


def test_reference_and_driver_cut_alike(tmp_path):
    """The driver writes the documents the reference indexes: same names,
    same bytes, written once a seed beside the corpus."""
    paths = _shelves(tmp_path, 2, 60_000)
    cell = types.SimpleNamespace(
        files=list(paths), traffic={"reference_params": CUT})
    written = index_inproc._documents(cell)
    want = list(docs.spans(paths, CUT))
    assert [os.path.basename(p) for p in written] == [n for n, _ in want]
    assert [open(p, "rb").read() for p in written] == [d for _, d in want]
    assert os.path.dirname(written[0]).startswith(str(tmp_path))
    stamp = os.path.getmtime(written[0])
    assert index_inproc._documents(cell) == written
    assert os.path.getmtime(written[0]) == stamp  # not written again
    # what the harness compares: the reference over the files equals the
    # plain index over the written documents
    assert reference_index.lines(paths, CUT) == reference_index.index_lines(
        [(os.path.basename(p), open(p, "rb").read()) for p in written], 16)


def test_the_driver_renders_the_join_as_the_reference_writes_it(tmp_path):
    names = ["d00000.txt", "d00001.txt", "d00002.txt"]
    with open(tmp_path / "plan-join.json", "w") as f:
        json.dump({"topk": [[3, "the"], [2, "b"]],
                   "join": {"the": {"df": 3, "part": 1, "docs": [2, 0, 1]},
                            "b": {"df": 2, "part": 0, "docs": [1, 2]}}}, f)
    index_inproc._render_join(str(tmp_path), names)
    assert sorted(open(tmp_path / "mr-out-join").read().splitlines()) == [
        "#join b 2 d00001.txt,d00002.txt",
        "#join the 3 d00000.txt,d00001.txt,d00002.txt",
        "#top 1 3 the", "#top 2 2 b"]
