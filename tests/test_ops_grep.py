"""Device grep kernel: differential vs the host regex app."""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.apps import grep, tpu_grep
from dsi_tpu.ops.altk import altgrep_host_result
from dsi_tpu.ops.grepk import (
    grep_host_result,
    hit_ends,
    is_literal_pattern,
    lines_from_hits,
)
from dsi_tpu.ops.nfak import nfagrep_host_result
from dsi_tpu.ops.regexk import classgrep_host_result

TEXT = (b"the quick brown fox\njumps over the lazy dog\n"
        b"no match here\nfoxes and boxes\n\nfox")


def host_lines(data: bytes, pattern: str):
    os.environ["DSI_GREP_PATTERN"] = pattern
    try:
        return [kv.key for kv in grep.Map("f", data.decode())]
    finally:
        del os.environ["DSI_GREP_PATTERN"]


def test_literal_detection():
    assert is_literal_pattern("fox")
    assert is_literal_pattern("lazy dog")
    assert not is_literal_pattern("[Tt]he")
    assert not is_literal_pattern("fox.*")
    assert not is_literal_pattern("")
    assert not is_literal_pattern("a\nb")
    assert not is_literal_pattern("héllo")


@pytest.mark.parametrize("pat", ["fox", "the", "dog", "zzz", "e", " "])
def test_kernel_matches_host_regex(pat):
    assert grep_host_result(TEXT, pat) == host_lines(TEXT, pat)


def test_empty_lines_and_final_line():
    out = grep_host_result(TEXT, "fox")
    assert out is not None
    assert out[-1] == "fox"  # final line without trailing newline


def test_mostly_empty_lines():
    data = b"\n" * 3000 + b"needle\n" + b"\n" * 3000
    assert grep_host_result(data, "needle") == ["needle"]


# ── the tier contract: matched line ends as packed bits ────────────────

#: Every tier on a pattern of its own that finds "fox" (the anchored
#: ones only at a line's end: the class tier's window test and the NFA
#: tier's one-position end latch, which sits ON the newline).
TIERS = {
    "literal": (grep_host_result, "fox"),
    "class": (classgrep_host_result, "[Ff]ox"),
    "class_end": (classgrep_host_result, "fox$"),
    "alt": (altgrep_host_result, "fox|F[o0]x"),
    "nfa": (nfagrep_host_result, "fo+x"),
    "nfa_end": (nfagrep_host_result, "fo*x$"),
}

#: The shapes a position-space layout can get wrong.
SHAPES = {
    "last_line_without_newline": b"a fox\nnothing\nthe fox",
    "ends_in_newline": b"a fox\nnothing\nthe fox\n",
    "empty_lines": b"\n\nfox\n\n\nno\n\nfox\n\n",
    "every_byte_a_newline": b"\n" * 300,
    "match_at_first_and_last_byte": b"fox is\nno\nis fox",
    "single_line": b"one fox",
    "single_line_no_match": b"nothing here",
    "one_byte_short_of_a_power_of_two": b"x" * 250 + b"\nfox",  # 255 B
    "hits_in_adjacent_lines": b"no\nfox\nfox\nfox\nno\nfox\nfox",
    "every_line_a_hit": b"fox\n" * 40 + b"fox",
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("tier", sorted(TIERS))
def test_tier_lines_equal_re_over_split(tier, shape, monkeypatch):
    monkeypatch.setenv("DSI_NFA_DISPATCH", "device")
    run, pattern = TIERS[tier]
    data = SHAPES[shape]
    want = [ln for ln in data.decode().split("\n")
            if re.search(pattern, ln)]
    assert run(data, pattern) == want


def _kernel_cases():
    import jax.numpy as jnp

    from dsi_tpu.ops import grepk, nfak, regexk

    chunk = jnp.asarray(grepk._pad_pow2(b"the\nx\nthe"))
    ranges, a_start, a_end = regexk.parse_class_pattern("[Tt]he")
    branches, n_atoms = nfak.parse_nfa_pattern("th+e")
    table, v0 = nfak._build_table(branches, n_atoms)
    return {
        "grep_kernel": (grepk.grep_kernel,
                        (chunk, jnp.asarray(np.frombuffer(b"the", np.uint8)))),
        "classgrep_kernel": (
            lambda c: regexk.classgrep_kernel(
                c, ranges=ranges, anchor_start=a_start, anchor_end=a_end),
            (chunk,)),
        "nfa_kernel": (
            lambda c, t, v: nfak.nfa_kernel(
                c, t, v, s_bucket=table.shape[1], block=256),
            (chunk, jnp.asarray(table), jnp.asarray(v0))),
    }


@pytest.mark.parametrize("kernel",
                         ["grep_kernel", "classgrep_kernel", "nfa_kernel"])
def test_kernel_jaxpr_holds_no_scatter_gather_or_sort(kernel):
    """The mechanism, pinned where no device trace is at hand: matched
    line ends come from two scans read at the line ends, and leave as
    bits packed by slices, shifts and ORs."""
    fn, args = _kernel_cases()[kernel]

    def names(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    prims = set(names(jax.make_jaxpr(fn)(*args).jaxpr))
    assert {"cumsum", "cummax"} <= prims
    # The NFA tier looks its per-byte transition matrices up in its
    # table (``table[col]``, the match itself); its line flags gather
    # nothing, like the other two.
    allowed = {"gather"} if kernel == "nfa_kernel" else set()
    bad = [p for p in prims - allowed
           if "scatter" in p or "gather" in p or "sort" in p]
    assert not bad, sorted(prims)


def _words(n_words: int, positions) -> np.ndarray:
    """Packed words as the kernels lay them out: bit k of word w is
    position ``k * n_words + w``."""
    words = np.zeros(n_words, np.uint32)
    for p in positions:
        words[p % n_words] |= np.uint32(1) << np.uint32(p // n_words)
    return words


@pytest.mark.parametrize("positions", [
    [0],                                   # first bit
    [255],                                 # last bit
    [3 + 8 * k for k in range(32)],        # every bit of one word
    [],                                    # none
    [5, 6, 7, 100, 254, 255],
], ids=["first", "last", "full_word", "none", "mixed"])
def test_hit_ends_inverts_the_packing(positions):
    assert hit_ends(_words(8, positions)).tolist() == positions


def test_lines_from_hits_takes_lines_by_offset():
    text = "ab\n\ncd\nef"  # line ends at 2, 3, 6 and in the padding
    n_words = 8
    assert lines_from_hits(text, _words(n_words, [2, 6, 255]), 4) == [
        "ab", "cd", "ef"]
    assert lines_from_hits(text, _words(n_words, [3]), 4) == [""]
    assert lines_from_hits(text, _words(n_words, []), 4) == []
    # A device line count the host does not share: the host decides.
    assert lines_from_hits(text, _words(n_words, [2]), 5) is None


def test_pattern_longer_than_data():
    assert grep_host_result(b"tiny", "a" * 300) == []


def test_regex_routing_tiers():
    # Class patterns leave the literal kernel (tier 1) but are now served
    # on device by the class kernel (tier 2, ops/regexk.py)...
    assert grep_host_result(TEXT, "[Tt]he") is None
    os.environ["DSI_GREP_PATTERN"] = "[Tt]he"
    try:
        kva = tpu_grep.tpu_map("f", TEXT)
        assert kva is not None and all("he" in kv.key for kv in kva)
    finally:
        del os.environ["DSI_GREP_PATTERN"]
    # ...variable-length regex is now served by tier 4 (the NFA
    # matrix-scan kernel, ops/nfak.py; pinned past the dispatch cost
    # model, which routes to host wherever the kernel measures slower)...
    os.environ["DSI_GREP_PATTERN"] = "th+e"
    os.environ["DSI_NFA_DISPATCH"] = "device"
    try:
        kva = tpu_grep.tpu_map("f", TEXT)
        assert kva is not None
        assert [kv.key for kv in kva] == [
            "the quick brown fox", "jumps over the lazy dog"]
    finally:
        del os.environ["DSI_GREP_PATTERN"]
        del os.environ["DSI_NFA_DISPATCH"]
    # ...while groups/backrefs still route to the host app.
    os.environ["DSI_GREP_PATTERN"] = "(th)+e"
    try:
        assert tpu_grep.tpu_map("f", TEXT) is None  # router: host handles it
    finally:
        del os.environ["DSI_GREP_PATTERN"]


def test_tpu_map_emits_per_line_records():
    os.environ["DSI_GREP_PATTERN"] = "fox"
    try:
        kva = tpu_grep.tpu_map("f", TEXT)
    finally:
        del os.environ["DSI_GREP_PATTERN"]
    assert [kv.key for kv in kva] == ["the quick brown fox",
                                      "foxes and boxes", "fox"]
    assert all(kv.value == "" for kv in kva)


def test_line_count_mismatch_falls_back(monkeypatch):
    # A host/device line-count disagreement must return None (host regex
    # path), not crash the worker task mid-job.
    import dsi_tpu.ops.grepk as grepk

    import dsi_tpu.ops.regexk as regexk

    real = grepk._grep_jit

    def skewed(chunk, pat):
        hit_bits, n_lines = real(chunk, pat)
        return hit_bits, n_lines + 1

    monkeypatch.setattr(grepk, "_grep_jit", skewed)
    assert grep_host_result(TEXT, "fox") is None

    # A literal is also a valid class pattern, so tier 2 (regexk) would
    # otherwise serve the task; skew its line counts the same way to
    # assert the FULL device->host fallback chain.
    real_c = regexk._classgrep_compiled

    def skewed_c(n, ranges, a_start, a_end):
        fn = real_c(n, ranges, a_start, a_end)

        def wrap(chunk):
            hit_bits, n_lines = fn(chunk)
            return hit_bits, n_lines + 1

        return wrap

    monkeypatch.setattr(regexk, "_classgrep_compiled", skewed_c)
    assert regexk.classgrep_host_result(TEXT, "fox") is None

    # A literal is ALSO a valid tier-4 NFA pattern; skew its line counts
    # too so the router truly has no healthy device tier left.
    import dsi_tpu.ops.nfak as nfak

    real_n = nfak._nfa_compiled

    def skewed_n(n, s_bucket, block):
        fn = real_n(n, s_bucket, block)

        def wrap(chunk, table, v0):
            hit_bits, n_lines = fn(chunk, table, v0)
            return hit_bits, n_lines + 1

        return wrap

    monkeypatch.setattr(nfak, "_nfa_compiled", skewed_n)
    assert nfak.nfagrep_host_result(TEXT, "fox") is None

    # ...and the app-level router then serves the task via the host Map.
    monkeypatch.setenv("DSI_GREP_PATTERN", "fox")
    assert tpu_grep.tpu_map("f", TEXT) is None  # worker falls back to Map
    assert [kv.key for kv in grep.Map("f", TEXT.decode())] == [
        "the quick brown fox", "foxes and boxes", "fox"]


def test_control_byte_pattern_rejected():
    # NUL would match the chunk's zero padding; control bytes must route to
    # the host regex path
    assert not is_literal_pattern("\x00")
    assert not is_literal_pattern("a\x01b")
    assert grep_host_result(b"abc\x00x\ndef", "\x00") is None


def test_cold_shape_compiles_and_runs_on_every_tier():
    """No tier refuses a shape because its program has not been compiled
    yet: a cold program compiles (counted in aotcache.stats) and the
    device path answers."""
    import dsi_tpu.ops.altk as altk
    import dsi_tpu.ops.grepk as grepk
    import dsi_tpu.ops.regexk as regexk
    from dsi_tpu.backends import aotcache

    # A size no other test uses, so every tier's shape is new here.
    data = b"the quick fox\nplain line\n" * 8 + b"x" * 3000
    before = aotcache.stats["compiles"]
    assert grepk.grep_host_result(data, "fox") is not None
    assert regexk.classgrep_host_result(data, "[Tt]he") is not None
    assert altk.altgrep_host_result(data, "fox|[Tt]he") is not None
    assert aotcache.stats["compiles"] >= before + 2

