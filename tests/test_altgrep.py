"""Alternation grep tier (ops/altk.py): differential vs host re, split
semantics, and fallback routing."""

import os

import pytest

pytest.importorskip("jax")

from dsi_tpu.apps import grep, tpu_grep
from dsi_tpu.ops.altk import altgrep_host_result, split_alternation

TEXT = (b"the quick brown fox\njumps over The lazy dog\n"
        b"no match here\nCats and dogs\n42 is the answer\n\nfox")


def host_lines(data: bytes, pattern: str):
    os.environ["DSI_GREP_PATTERN"] = pattern
    try:
        return [kv.key for kv in grep.Map("f", data.decode())]
    finally:
        del os.environ["DSI_GREP_PATTERN"]


def test_split_alternation():
    assert split_alternation("the|and") == ["the", "and"]
    assert split_alternation("a|b|c") == ["a", "b", "c"]
    assert split_alternation("a|a|b") == ["a", "b"]  # dedup, order kept
    assert split_alternation("a|a") is None          # collapses to 1 branch
    assert split_alternation(r"a\|b") is None        # escaped: literal |
    assert split_alternation(r"a\||b") == [r"a\|", "b"]
    assert split_alternation("[a|b]x") is None       # | inside a class
    assert split_alternation("[Tt]he|[Aa]nd") == ["[Tt]he", "[Aa]nd"]
    assert split_alternation("a|") is None           # empty branch
    assert split_alternation("|a") is None
    assert split_alternation("plain") is None        # no alternation
    assert split_alternation("[ab|cd") is None       # unterminated class


@pytest.mark.parametrize("pat", [
    "the|and",                # literal | literal
    "fox|dog|Cats",           # three branches
    "[Tt]he|[Cc]ats",         # class | class
    "fox|[Dd]og",             # mixed tiers
    "^the|dog$",              # per-branch anchors, re binding
    r"\d\d|lazy",             # escape-class branch
    "zzz|qqq",                # no matches
    "e| ",                    # high-frequency single bytes
])
def test_alternation_matches_host_regex(pat):
    got = altgrep_host_result(TEXT, pat)
    assert got is not None, f"{pat!r} unexpectedly routed to host"
    assert got == host_lines(TEXT, pat)


@pytest.mark.parametrize("pat", [
    "a|b*",        # variable-length branch
    "(a|b)",       # group
    "a|",          # empty branch
    "plain",       # not an alternation (tier 1/2 territory)
    "a|h\xe9llo",  # non-ASCII branch
])
def test_ineligible_patterns_route_to_host(pat):
    assert altgrep_host_result(TEXT, pat) is None


def test_nul_data_with_class_branch_routes_to_host():
    assert altgrep_host_result(b"a\x00b\nthe\n", "[Tt]he|and") is None
    # ...but all-literal branches tolerate NUL (padding can't match them)
    assert altgrep_host_result(b"a\x00b\nthe\n", "the|and") == ["the"]


def test_branch_longer_than_data():
    assert altgrep_host_result(b"tiny\nthe\n", "the|" + "a" * 300) == ["the"]
    # ...and where no branch fits the data, no kernel runs at all.
    assert altgrep_host_result(b"ab", "abc|abd") == []


def test_tpu_map_dispatches_alternation():
    os.environ["DSI_GREP_PATTERN"] = "fox|[Dd]og"
    try:
        kva = tpu_grep.tpu_map("f", TEXT)
    finally:
        del os.environ["DSI_GREP_PATTERN"]
    assert kva is not None
    assert [kv.key for kv in kva] == host_lines(TEXT, "fox|[Dd]og")


def test_mostly_empty_lines_with_alternation():
    data = b"\n" * 3000 + b"needle\n" + b"\n" * 3000 + b"pin\n"
    assert altgrep_host_result(data, "needle|pin") == ["needle", "pin"]


def test_fuzz_generated_alternations_vs_oracle():
    """Alternations of branches drawn from the class-pattern grammar and
    plain literals: every generated pattern must be accepted and agree
    with the per-line re.search oracle (the same discipline as
    tests/test_ops_regexk.py's grammar fuzz)."""
    import random
    import re

    rng = random.Random(31)
    alphabet = "abcxyzAB01 .,;"

    def gen_branch():
        if rng.random() < 0.4:  # literal branch
            return "".join(rng.choices("abcxyzAB01", k=rng.randint(1, 4)))
        atoms = []
        for _ in range(rng.randint(1, 4)):
            r = rng.random()
            if r < 0.4:
                atoms.append(rng.choice("abcxyzAB"))
            elif r < 0.55:
                atoms.append(".")
            elif r < 0.7:
                atoms.append(rng.choice([r"\d", r"\w", r"\s"]))
            else:
                neg = "^" if rng.random() < 0.3 else ""
                items = "".join(rng.sample("abcxyz019", rng.randint(1, 3)))
                atoms.append(f"[{neg}{items}]")
        b = "".join(atoms)
        if rng.random() < 0.2:
            b = "^" + b
        if rng.random() < 0.2:
            b = b + "$"
        return b

    for trial in range(40):
        pattern = "|".join(gen_branch()
                           for _ in range(rng.randint(2, 4)))
        if split_alternation(pattern) is None:
            continue  # duplicate-free split may collapse below 2 branches
        lines = ["".join(rng.choices(alphabet, k=rng.randint(0, 24)))
                 for _ in range(rng.randint(1, 30))]
        data = "\n".join(lines).encode()
        got = altgrep_host_result(data, pattern)
        assert got is not None, (trial, pattern)
        want = [ln for ln in data.decode().split("\n")
                if re.search(pattern, ln)]
        assert got == want, (trial, pattern, lines)
