"""``planrun --chain indexer --workdir`` commits the whole inverted index.

The chain is driven through its entry point over seeded documents cut by
the benchmark's own helper (``benchmarks/docs.py``) from one generated
file, so that they share a vocabulary.  The merged, sorted ``mr-out-*``
must equal, byte for byte, what ``mrsequential`` with ``apps/indexer``
writes over the same names, and (with ``plan-join.json`` rendered as the
benchmark's driver renders it) what ``benchmarks/reference_index.py``
computes, a file that imports nothing of the program.  The default
handoff, ``--staged`` and ``--device-accumulate`` are each held to both.
"""

import contextlib
import importlib.util
import io
import json
import os
import sys

import numpy as np
import pytest

from dsi_tpu.cli import planrun as cli
from dsi_tpu.mr.worker import ihash
from dsi_tpu.parallel.merge import PackedPostings, PostingsTable

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _load(name):
    if BENCH not in sys.path:   # reference_index imports docs flat
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


corpus = _load("corpus")
docs = _load("docs")
reference = _load("reference")
reference_index = _load("reference_index")

#: Documents of 3,000 to 40,000 B: chunk sizes 4 KiB to 64 KiB.
CUT = {"doc_min_bytes": 3_000, "doc_max_bytes": 40_000, "topk": 16}
#: The first capacity rung of the runs below; the longest documents hold
#: more distinct words and replay wider.
U_CAP = 512

MODES = {"chained": (), "staged": ("--staged",),
         "device-accumulate": ("--device-accumulate",)}


@pytest.fixture(scope="module")
def collection(tmp_path_factory):
    """``(directory, names)``: seeded documents of unequal length, as
    files named as the cut names them."""
    root = tmp_path_factory.mktemp("shelf")
    params = corpus.effective({"vocab_per_file": 4_000}, {})
    shelf = root / "pg-00.txt"
    shelf.write_bytes(corpus.generate_bytes(260_000, 7_000, params))
    directory = root / "docs"
    directory.mkdir()
    names = []
    for name, data in docs.spans([str(shelf)], CUT):
        (directory / name).write_bytes(data)
        names.append(name)
    sizes = {1 << max(8, os.path.getsize(directory / n).bit_length())
             for n in names}
    assert len(sizes) >= 2, sizes            # at least two chunk sizes
    return str(directory), names, str(shelf)


def _run(directory, names, workdir, *flags):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", "1",
                       "--nreduce", "10", "--u-cap", str(U_CAP), "--stats",
                       "--workdir", workdir, *flags,
                       *[os.path.join(directory, n) for n in names]])
    return rc, err.getvalue()


def _sequential(directory, names, out):
    """``mrsequential`` with ``apps/indexer``, started in the documents'
    directory over the same names."""
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


def _render_join(workdir, names):
    """As ``benchmarks/drivers/index_inproc`` renders it."""
    with open(os.path.join(workdir, "plan-join.json")) as f:
        found = json.load(f)
    lines = [f"#top {rank} {df} {word}"
             for rank, (df, word) in enumerate(found["topk"], 1)]
    for word, entry in found["join"].items():
        held = sorted({names[d] for d in entry["docs"]})
        lines.append(f"#join {word} {len(held)} {','.join(held)}")
    return lines


@pytest.mark.parametrize("mode", sorted(MODES))
def test_committed_index_equals_the_oracle_and_the_plain_reference(
        collection, tmp_path, mode):
    directory, names, shelf = collection
    workdir = str(tmp_path / "wd")
    rc, err = _run(directory, names, workdir, *MODES[mode])
    assert rc == 0, err[-2000:]
    assert sorted(os.listdir(workdir)) == sorted(
        [f"mr-out-{r}" for r in range(10)] + ["plan-join.json"])
    got = reference.read_output(workdir)
    assert got == _sequential(directory, names, str(tmp_path / "seq.txt"))
    # the plain reference cuts the shelf itself, as the harness has it do
    want = reference_index.lines([shelf], CUT)
    assert sorted(got + _render_join(workdir, names)) == want
    # one document is wider than the first --u-cap rung, and was replayed
    longest = max(len(set(reference._WORD.findall(
        open(os.path.join(directory, n), "rb").read()))) for n in names)
    assert longest > U_CAP
    assert "'replays': 0" not in err


def test_names_are_basenames_and_partitions_follow_ihash(collection,
                                                         tmp_path):
    directory, names, _ = collection
    workdir = str(tmp_path / "wd")
    # the same document twice, from two directories: one name, once
    twin = tmp_path / "elsewhere"
    twin.mkdir()
    (twin / names[0]).write_bytes(
        open(os.path.join(directory, names[0]), "rb").read())
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", "1",
                       "--nreduce", "4", "--workdir", workdir,
                       os.path.join(directory, names[0]),
                       os.path.join(directory, names[1]),
                       str(twin / names[0])])
    assert rc == 0, err.getvalue()[-2000:]
    seen = 0
    for r in range(4):
        with open(os.path.join(workdir, f"mr-out-{r}"),
                  encoding="ascii") as f:
            rows = [line.rstrip("\n").split(" ") for line in f]
        assert rows == sorted(rows)          # sorted within a partition
        for word, n, held in rows:
            assert ihash(word) % 4 == r
            held = held.split(",")
            assert held == sorted(set(held)) and int(n) == len(held)
            assert set(held) <= {names[0], names[1]}
        seen += len(rows)
    assert seen == len(_sequential(directory, names[:2],
                                   str(tmp_path / "seq.txt")))


def test_host_path_exit_commits_nothing(collection, tmp_path):
    directory, names, _ = collection
    bad = tmp_path / "bad.txt"
    bad.write_bytes("café au lait\n".encode("utf-8"))
    workdir = str(tmp_path / "wd")
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["--chain", "indexer", "--devices", "1",
                       "--workdir", workdir,
                       os.path.join(directory, names[0]), str(bad)])
    assert rc == 1 and "needs the host path" in err.getvalue()
    left = os.listdir(workdir) if os.path.isdir(workdir) else []
    assert not [n for n in left if n.startswith(("mr-out", "plan-join"))]


def _table(words_docs, kk):
    """A ``PostingsTable`` of ``{word: [doc, ...]}`` rows, each document's
    rows added as a wave's would be."""
    table = PostingsTable()
    by_doc = {}
    for word, held in words_docs.items():
        for d in held:
            by_doc.setdefault(d, []).append(word)
    for d, words in sorted(by_doc.items(), reverse=True):  # any order
        rows = np.zeros((len(words), kk + 4), np.uint32)
        for i, word in enumerate(words):
            raw = word.encode("ascii")
            rows[i, :kk] = np.frombuffer(raw.ljust(4 * kk, b"\x00"), ">u4")
            rows[i, kk:] = (len(raw), 1, d, ihash(word) % 3)
        table.add(rows, kk)
    return table


@pytest.mark.parametrize("kk, words_docs", [
    (4, {"solo": [5], "everywhere": list(range(12)), "Pair": [11, 0],
         "a": [3]}),
    (16, {"w" * 64: [1, 0], "x" * 63: [2], "short": [0, 1, 2]}),
], ids=["one-and-every-document", "64-byte-word"])
def test_packed_postings_render_what_the_reduce_formats(kk, words_docs):
    from dsi_tpu.apps import indexer

    n_docs = 1 + max(d for held in words_docs.values() for d in held)
    names = [f"book-{d % 7}-{'x' * (d % 4)}.txt" for d in range(n_docs)]
    stats = {}
    packed = _table(words_docs, kk).finalize_packed(stats=stats)
    assert stats["postings_rows"] == sum(map(len, words_docs.values()))
    assert stats["index_terms"] == len(words_docs) and "group_s" in stats
    with pytest.raises(ValueError):
        packed.render_partition(0)           # an index names its documents
    packed.named(names)
    for r in range(3):
        want = "".join(
            f"{word} {indexer.Reduce(word, [names[d] for d in held])}\n"
            for word, held in sorted(words_docs.items())
            if ihash(word) % 3 == r)
        assert packed.render_partition(r) == want.encode("ascii")
    # the staged baseline's table, out of Python objects, renders alike
    again = PackedPostings.from_postings(
        {w: (ihash(w) % 3, held) for w, held in words_docs.items()})
    again.named(names)
    assert [again.render_partition(r) for r in range(3)] == \
        [packed.render_partition(r) for r in range(3)]
