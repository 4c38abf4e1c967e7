"""App semantics: wc tokenization vs the Go spec, grep, indexer."""

import os

from dsi_tpu.apps import grep, indexer, wc
from dsi_tpu.mr.types import KeyValue


def test_wc_splits_on_non_letters():
    # Go splits on ANY non-letter rune, including digits and underscore
    # (mrapps/wc.go:23: !unicode.IsLetter).
    kva = wc.Map("f", "one two2three_four\nfive,six")
    assert [kv.key for kv in kva] == ["one", "two", "three", "four", "five", "six"]
    assert all(kv.value == "1" for kv in kva)


def test_wc_reduce_counts():
    assert wc.Reduce("word", ["1", "1", "1"]) == "3"
    assert wc.Reduce("word", []) == "0"


def test_wc_empty_and_punct_only():
    assert wc.Map("f", "") == []
    assert wc.Map("f", "123 ... __ \n") == []


def test_wc_tokenizer_go_isletter_unicode_parity():
    # Go's unicode.IsLetter is category L ONLY: Ⅳ (Nl, Roman numeral) and
    # ² (No) are separators, while ª (Lo) and µ (Ll) are letters.  A \w-based
    # regex gets these wrong ('bⅣcªd' must be two
    # words, not one).
    assert [kv.key for kv in wc.Map("f", "bⅣcªd")] == ["b", "cªd"]
    assert [kv.key for kv in wc.Map("f", "x²y µz 漢字")] == \
        ["x", "y", "µz", "漢字"]
    # Combining marks (Mn) split words under Go semantics: e + U+0301 is
    # two runs "e", nothing — the mark itself is not a letter.
    assert [kv.key for kv in wc.Map("f", "cafe\u0301s")] == ["cafe", "s"]
    assert [kv.key for kv in wc.Map("f", "caf\u00e9s")] == ["caf\u00e9s"]


def test_grep_matches_lines(monkeypatch):
    monkeypatch.setenv("DSI_GREP_PATTERN", r"wh(ale|ite)")
    kva = grep.Map("f", "the white whale\nno match here\nwhale ho\n")
    assert [kv.key for kv in kva] == ["the white whale", "whale ho"]
    assert grep.Reduce("the white whale", ["", ""]) == "2"


def test_grep_default_matches_nothing(monkeypatch):
    monkeypatch.delenv("DSI_GREP_PATTERN", raising=False)
    assert grep.Map("f", "anything\nat all") == []


def test_indexer_dedups_within_doc_and_sorts():
    kva = indexer.Map("doc1", "apple banana apple")
    assert kva == [KeyValue("apple", "doc1"), KeyValue("banana", "doc1")]
    assert indexer.Reduce("apple", ["doc2", "doc1", "doc2"]) == "2 doc1,doc2"
