"""The generator: the same seed gives the same bytes, parameters are data."""

import re

import pytest

import corpus


def test_same_seed_same_bytes_other_seed_other_bytes():
    p = corpus.effective({"vocab_per_file": 500}, {})
    a = corpus.generate_bytes(50_000, 7, p)
    assert a == corpus.generate_bytes(50_000, 7, p)
    assert a != corpus.generate_bytes(50_000, 8, p)


def test_exact_size_ascii_and_words_the_kernels_accept():
    p = corpus.effective({}, {"vocab_per_file": 2000})
    blob = corpus.generate_bytes(100_001, 3, p)
    assert len(blob) == 100_001 and blob.isascii()
    words = re.findall(rb"[A-Za-z]+", blob)
    assert max(map(len, words)) <= 12
    assert 500 < len(set(words)) <= 2000 + 1   # + the cut last word
    assert b"\n" in blob and b", " in blob or b". " in blob


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_pinned_counts_hold_whatever_the_seed(seed):
    """What the program turns into array shapes is the same for every
    seed: distinct words per file, newlines per file, a newline last."""
    p = corpus.effective({"vocab_per_file": 3000, "exact_vocabulary": 1,
                          "newlines_per_file": 2000}, {})
    blob = corpus.generate_bytes(200_000, seed, p)
    assert len(blob) == 200_000 and blob.isascii()
    words = re.findall(rb"[A-Za-z]+", blob)
    assert len(set(words)) == 3000 and max(map(len, words)) <= 12
    assert blob.count(b"\n") == 2000 and blob.endswith(b"\n")
    assert blob != corpus.generate_bytes(200_000, seed + 10, p)


def test_traffic_overrides_configuration_and_unknown_keys_are_refused():
    p = corpus.effective({"files": 8, "vocab_per_file": 400_000},
                         {"vocab_per_file": 20_000})
    assert p["files"] == 8 and p["vocab_per_file"] == 20_000
    assert corpus.params_key(p) != corpus.params_key(
        corpus.effective({"files": 8, "vocab_per_file": 400_000}, {}))
    with pytest.raises(ValueError, match="unknown corpus parameter"):
        corpus.effective({"vocabulary": 3}, {})


def test_ensure_caches_per_seed_and_holds_one_seed(tmp_path):
    p = corpus.effective({"files": 2, "file_bytes": 10_000,
                          "vocab_per_file": 100}, {})
    first = corpus.ensure(str(tmp_path), p, 1)
    assert first["generated"] and len(first["files"]) == 2
    again = corpus.ensure(str(tmp_path), p, 1)
    assert not again["generated"] and again["dir"] == first["dir"]
    other = corpus.ensure(str(tmp_path), p, 2)
    assert other["generated"]
    import os
    assert not os.path.exists(first["dir"])   # one seed at a time
