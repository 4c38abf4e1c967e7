"""TF-IDF: host app semantics, framework e2e parity, and the SPMD
multi-chip path on the virtual 8-device mesh (BASELINE.json's last config).
"""

import math
import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.apps import tfidf
from dsi_tpu.utils.corpus import ensure_corpus
from tests.harness import merged_output, oracle_output, run_distributed_threads


def test_map_emits_per_doc_term_counts():
    kva = tfidf.Map("docA", "red fish blue fish")
    assert [(kv.key, kv.value) for kv in kva] == [
        ("blue", "docA\t1"), ("fish", "docA\t2"), ("red", "docA\t1")]


def test_reduce_scores_and_formats(monkeypatch):
    monkeypatch.setenv("DSI_TFIDF_NDOCS", "4")
    out = tfidf.Reduce("fish", ["docB\t3", "docA\t2"])
    idf = math.log(4 / 2)
    assert out == f"2 docA:{2 * idf:.6f},docB:{3 * idf:.6f}"


def test_reduce_requires_ndocs(monkeypatch):
    monkeypatch.delenv("DSI_TFIDF_NDOCS", raising=False)
    with pytest.raises(RuntimeError, match="DSI_TFIDF_NDOCS"):
        tfidf.Reduce("w", ["d\t1"])


def test_idf_zero_when_word_in_every_doc(monkeypatch):
    monkeypatch.setenv("DSI_TFIDF_NDOCS", "2")
    out = tfidf.Reduce("the", ["a\t5", "b\t1"])
    assert out == "2 a:0.000000,b:0.000000"


def test_framework_e2e_matches_sequential_oracle(tmp_path, monkeypatch):
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=5,
                          file_size=20_000)
    monkeypatch.setenv("DSI_TFIDF_NDOCS", str(len(files)))
    want = oracle_output("tfidf", files, str(tmp_path))
    wd = tmp_path / "dist"
    os.makedirs(wd)
    run_distributed_threads("tfidf", files, str(wd), n_workers=3, n_reduce=7)
    assert merged_output(str(wd)) == want


def test_spmd_waves_match_sequential_oracle(tmp_path, monkeypatch):
    """The multi-chip path: 11 documents in waves over the 8-device virtual
    mesh (so the last wave has padding documents), all_to_all shuffle,
    host scoring — mr-out-* byte-identical to the sequential oracle."""
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import tfidf_sharded, write_tfidf_output

    n_docs = 11
    files = ensure_corpus(str(tmp_path / "inputs"), n_files=n_docs,
                          file_size=3_000)
    monkeypatch.setenv("DSI_TFIDF_NDOCS", str(n_docs))
    want = oracle_output("tfidf", files, str(tmp_path))

    docs = []
    for p in files:
        with open(p, "rb") as f:
            docs.append(f.read())
    mesh = default_mesh(8)
    res = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 11)
    assert res is not None, "SPMD path unexpectedly fell back"
    wd = tmp_path / "spmd"
    os.makedirs(wd)
    write_tfidf_output(res, files, 10, str(wd))
    assert merged_output(str(wd)) == want


def test_wave_planning_tracks_per_wave_longest():
    from dsi_tpu.parallel.tfidf import plan_waves

    # One 10x outlier among uniform docs: longest-first order isolates it.
    lens = [1000] * 15 + [10_000]
    waves = plan_waves(lens, n_dev=8)
    assert len(waves) == 2
    assert waves[0][1] == 1 << 14      # the outlier's wave only
    assert waves[1][1] == 1 << 10      # uniform waves stay small
    assert 15 in waves[0][0]           # outlier scheduled first
    # Every doc appears exactly once across waves.
    seen = sorted(i for idxs, _ in waves for i in idxs)
    assert seen == list(range(16))


def test_outlier_document_compiles_few_shapes(tmp_path, monkeypatch):
    """One 10x outlier doc must not inflate every wave's
    buffers — <= 3 compiled shapes, and parity with the oracle holds."""
    import dsi_tpu.parallel.tfidf as m
    from dsi_tpu.parallel.shuffle import default_mesh

    rng = np.random.default_rng(5)
    vocab = ["".join(chr(97 + c) for c in rng.integers(0, 26, size=6))
             for _ in range(200)]

    def doc(n):
        return " ".join(vocab[i] for i in rng.integers(0, 200, n)).encode()

    docs = [doc(60) for _ in range(15)] + [doc(700)]  # one ~10x outlier
    sizes_used = []
    real_chunk = m._wave_chunk

    def spy(d, idxs, n_dev, size):
        sizes_used.append(size)
        return real_chunk(d, idxs, n_dev, size)

    monkeypatch.setattr(m, "_wave_chunk", spy)
    mesh = default_mesh(8)
    res = m.tfidf_sharded(docs, mesh=mesh, n_reduce=5, u_cap=1 << 11)
    assert res is not None
    assert len(set(sizes_used)) <= 3
    assert max(sizes_used) >= 4 * min(sizes_used)  # small waves stayed small

    # Exactness across the mixed shapes: df per word vs a host oracle.
    import collections
    import re
    want = collections.Counter()
    for d in docs:
        for w in set(re.findall(r"[A-Za-z]+", d.decode())):
            want[w] += 1
    got_df = {w: len(pairs) for w, (_, pairs) in res.items()}
    assert got_df == dict(want)


def test_partition_slices_union_equals_full_run(tmp_path):
    """The bounded-host-memory lever: per-partition-slice runs must union
    to exactly the full result, with each slice holding only its words."""
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import tfidf_sharded

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=9,
                          file_size=2_500)
    docs = []
    for p in files:
        with open(p, "rb") as f:
            docs.append(f.read())
    mesh = default_mesh(8)
    full = tfidf_sharded(docs, mesh=mesh, n_reduce=6, u_cap=1 << 11)
    assert full is not None

    lo = tfidf_sharded(docs, mesh=mesh, n_reduce=6, u_cap=1 << 11,
                       partitions={0, 1, 2})
    hi = tfidf_sharded(docs, mesh=mesh, n_reduce=6, u_cap=1 << 11,
                       partitions={3, 4, 5})
    assert set(lo) | set(hi) == set(full)
    assert not set(lo) & set(hi)  # a word lives in exactly one slice
    for w, (part, pairs) in lo.items():
        assert part in {0, 1, 2}
        assert sorted(pairs) == sorted(full[w][1])
    for w, (part, pairs) in hi.items():
        assert part in {3, 4, 5}
        assert sorted(pairs) == sorted(full[w][1])


def test_spmd_falls_back_on_non_ascii(tmp_path):
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import tfidf_sharded

    docs = [b"plain ascii words", "unicode café text".encode("utf-8")]
    res = tfidf_sharded(docs, mesh=default_mesh(8), n_reduce=5,
                        u_cap=1 << 8)
    assert res is None  # caller must route the job to the host path


def test_packed_and_lazy_docs_match_dict(tmp_path):
    """FileDocs + packed=True must agree with resident docs + dict result
    (the GB-soak memory path)."""
    import numpy as np

    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.tfidf import FileDocs, tfidf_sharded

    rng = random.Random(7)
    paths = []
    for i in range(5):
        # Letter-only words (digits split tokens: maximal letter runs).
        words = ["w" + "abcdefghij"[rng.randint(0, 9)]
                 + "xyzpq"[rng.randint(0, 4)] + "end"[rng.randint(0, 2):]
                 for _ in range(400)]
        p = tmp_path / f"doc-{i}.txt"
        p.write_bytes((" ".join(words)).encode())
        paths.append(str(p))
    docs = [open(p, "rb").read() for p in paths]
    mesh = default_mesh(4)
    want = tfidf_sharded(docs, mesh=mesh, n_reduce=10)
    lazy = FileDocs(paths)
    assert lazy.lengths == [len(d) for d in docs]
    got = tfidf_sharded(lazy, mesh=mesh, n_reduce=10, packed=True)
    assert got is not None and want is not None
    assert got.to_dict() == want
    # Point lookups agree and omit absent words.
    some = list(want)[:20] + ["notaword"]
    hits = got.lookup_many(some)
    assert "notaword" not in hits
    for w in some[:20]:
        assert hits[w] == want[w]
    # Vectorized invariant surface used by the soak.
    assert got.n_postings == sum(len(ps) for _, ps in want.values())
    assert (got.postings_per_word() >= 1).all()
