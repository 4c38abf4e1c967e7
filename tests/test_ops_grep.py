"""Device grep kernel: differential vs the host regex app."""

import os

import pytest

pytest.importorskip("jax")

from dsi_tpu.apps import grep, tpu_grep
from dsi_tpu.ops.grepk import grep_host_result, is_literal_pattern

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


def test_line_buffer_overflow_retry():
    data = b"\n" * 3000 + b"needle\n" + b"\n" * 3000
    assert grep_host_result(data, "needle") == ["needle"]


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

    def skewed(chunk, pat, *, l_cap):
        line_match, n_lines, overflow = real(chunk, pat, l_cap=l_cap)
        return line_match, n_lines + 1, overflow

    monkeypatch.setattr(grepk, "_grep_jit", skewed)
    assert grep_host_result(TEXT, "fox") is None

    # A literal is also a valid class pattern, so tier 2 (regexk) would
    # otherwise serve the task; skew its line counts the same way to
    # assert the FULL device->host fallback chain.
    real_c = regexk._classgrep_compiled

    def skewed_c(n, ranges, a_start, a_end, l_cap):
        fn = real_c(n, ranges, a_start, a_end, l_cap)

        def wrap(chunk):
            line_match, n_lines, overflow = fn(chunk)
            return line_match, n_lines + 1, overflow

        return wrap

    monkeypatch.setattr(regexk, "_classgrep_compiled", skewed_c)
    assert regexk.classgrep_host_result(TEXT, "fox") is None

    # A literal is ALSO a valid tier-4 NFA pattern; skew its line counts
    # too so the router truly has no healthy device tier left.
    import dsi_tpu.ops.nfak as nfak

    real_n = nfak._nfa_compiled

    def skewed_n(n, s_bucket, block, l_cap):
        fn = real_n(n, s_bucket, block, l_cap)

        def wrap(chunk, table, v0):
            line_match, n_lines, overflow = fn(chunk, table, v0)
            return line_match, n_lines + 1, overflow

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

