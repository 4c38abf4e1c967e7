"""NFA matrix-scan grep tier (ops/nfak.py): differential vs host re,
routing contract, multi-block correctness, and grammar fuzz."""

import os
import random
import re

import pytest

pytest.importorskip("jax")

from dsi_tpu.apps import grep, tpu_grep
from dsi_tpu.ops.nfak import nfagrep_host_result, parse_nfa_pattern

TEXT = (b"the quick brown fox\njumps over the lazy dogs\n"
        b"no match here\ncolour and color\nab ac abc abbbc\n"
        b"42 is the answer\n\nfox")


@pytest.fixture(autouse=True)
def _force_device_dispatch(monkeypatch):
    """These tests exercise the kernel itself; pin past the cost-model
    gate (which routes to host wherever the kernel measures slower —
    its own tests below override the pin)."""
    monkeypatch.setenv("DSI_NFA_DISPATCH", "device")


def oracle(data: bytes, pat: str):
    return [ln for ln in data.decode().split("\n") if re.search(pat, ln)]


@pytest.mark.parametrize("pat", [
    "ab*c", "colou?r", "[0-9]+", "a.*z",          # variable-length core
    "qu+ick", "o[ux]*r", "a?b?c", "x*y",          # modifier mix
    "^the", "dogs$", "^a.*c$", "f.x$", "x+$",     # anchors
    "ab*c|fox", "z*fox|dogs?$", "^x*y|[0-9]+",    # alternation
    "fox", "the",                                 # plain (tier overlap)
    r"\d+ is", r"\w+ \w+", r"[a-z]+\s[a-z]+",     # escape classes
])
def test_matches_re_oracle(pat):
    got = nfagrep_host_result(TEXT, pat)
    assert got is not None, f"{pat!r} unexpectedly routed to host"
    assert got == oracle(TEXT, pat), pat


@pytest.mark.parametrize("pat", [
    "ab{2}c", "b{2,}", "a{1,3}b", "[0-9]{2}",   # bounded reps (expanded)
    "ab*?c", "qu+?ick", "colou??r", "b{1,3}?",  # non-greedy == greedy
    "a{2", "x}y", "e}?",                        # literal braces, re-style
    "x{,2}s", "o{,1}x",                         # {,n} == {0,n} (re>=3.11)
])
def test_bounded_reps_and_nongreedy_match_oracle(pat):
    got = nfagrep_host_result(TEXT, pat)
    assert got is not None, f"{pat!r} unexpectedly routed to host"
    assert got == oracle(TEXT, pat), pat


@pytest.mark.parametrize("pat", [
    "a*",          # nullable: matches every line incl. empty — host
    "x*y*",        # nullable via both atoms
    "a{0,3}",      # nullable via bounded rep
    "^$",          # empty anchored
    "(ab)*",       # group
    "a{3,2}",      # inverted bounds: re errors
    "{2}",         # bare quantifier: re 'nothing to repeat'
    "a{2}{3}",     # multiple repeat: re errors
    "a**",         # stacked modifiers
    "a|",          # empty branch
    r"\bword",     # word boundary
    "h\xe9llo",    # non-ASCII
    "a" * 60,      # wider than the largest state bucket
    "a{1,60}",     # expansion exceeds the state bucket
])
def test_ineligible_routes_to_host(pat):
    assert nfagrep_host_result(TEXT, pat) is None


def test_nul_data_routes_to_host():
    assert nfagrep_host_result(b"a\x00b\nfox\n", "fox+") is None


def test_stray_modifier_routes_to_host():
    # re rejects '*a' as an error; the tier must not silently treat the
    # modifier as a literal.
    assert nfagrep_host_result(TEXT, "*a") is None
    assert nfagrep_host_result(TEXT, "a|+b") is None


def test_short_lines_one_program_one_attempt(monkeypatch, tmp_path):
    """64 lines of 3 bytes (the shape the retired line-slot ladder
    replayed at a second, separately compiled rung): one program, one
    attempt, the oracle's lines."""
    import json

    import dsi_tpu.obs.trace as obs_trace
    import dsi_tpu.ops.nfak as nfak
    from dsi_tpu.obs import Tracer

    tracer = Tracer(enabled=True, trace_dir=str(tmp_path / "trace"))
    monkeypatch.setattr(obs_trace, "_global", tracer)
    compiled = []
    real_compiled = nfak._nfa_compiled

    def spy_compiled(n, s, b):
        compiled.append((n, s, b))
        return real_compiled(n, s, b)

    monkeypatch.setattr(nfak, "_nfa_compiled", spy_compiled)
    data = b"ab\n" * 64
    try:
        got = nfak.nfagrep_host_result(data, "ab+")
        with open(tracer.flush()[0], encoding="utf-8") as f:
            events = [json.loads(line) for line in f][1:]
    finally:
        tracer.enabled = False
    assert got == oracle(data, "ab+")
    assert compiled == [(len(nfak._pad_pow2(data)), 16, 256)]
    (kernel,) = [e for e in events if e["name"] == "kernel"]
    assert (kernel["program"], kernel["attempt"]) == ("nfa_kernel", 0)
    assert "cap" not in kernel
    (pull,) = [e for e in events if e["name"] == "pull"]
    assert pull["bytes"] == len(nfak._pad_pow2(data)) // 8


def test_multi_block_spanning():
    """Data far larger than one 256-byte scan block, with matches that
    sit inside, start, and end at block boundaries."""
    rng = random.Random(5)
    lines = []
    for i in range(200):
        pad = "".join(rng.choices("qwert yuiop", k=rng.randint(0, 40)))
        lines.append(pad + ("abbbc" if i % 7 == 0 else "")
                     + ("xyz" if i % 11 == 0 else ""))
    data = "\n".join(lines).encode()
    for pat in ["ab+c", "xy?z$", "^q.*c"]:
        assert nfagrep_host_result(data, pat) == oracle(data, pat), pat


def test_empty_lines_and_no_trailing_newline():
    data = b"\n\nab\n\nabb\n"
    assert nfagrep_host_result(data, "ab+") == oracle(data, "ab+")
    data2 = b"ab\n\nabb"  # final line without newline
    assert nfagrep_host_result(data2, "ab+$") == oracle(data2, "ab+$")


def test_mostly_empty_lines():
    data = b"\n" * 3000 + b"needle\n" + b"\n" * 3000 + b"needles\n"
    assert nfagrep_host_result(data, "needles?$") == ["needle", "needles"]


def test_tpu_map_dispatches_tier4():
    os.environ["DSI_GREP_PATTERN"] = "qu+ick|dogs$"
    try:
        kva = tpu_grep.tpu_map("f", TEXT)
    finally:
        del os.environ["DSI_GREP_PATTERN"]
    assert kva is not None
    assert [kv.key for kv in kva] == oracle(TEXT, "qu+ick|dogs$")


def test_pattern_independent_program():
    """The compiled program is shared across patterns (table ships as an
    argument): two different patterns at one chunk shape must not
    trigger a second compile."""
    from dsi_tpu.backends import aotcache

    data = b"alpha beta\ngamma delta\n" * 8
    nfagrep_host_result(data, "al.*a")
    before = aotcache.stats["compiles"]
    nfagrep_host_result(data, "de[kl]ta+")
    assert aotcache.stats["compiles"] == before


def test_fuzz_generated_patterns_vs_oracle():
    """Patterns built from the supported grammar with random modifiers
    and alternation; every accepted pattern must agree with the re
    oracle, and None routes are only allowed for nullable collapses."""
    rng = random.Random(37)
    alphabet = "abcxyzAB01 .,;"

    def gen_atom():
        r = rng.random()
        if r < 0.45:
            return rng.choice("abcxyzAB")
        if r < 0.6:
            return "."
        if r < 0.72:
            return rng.choice([r"\d", r"\w", r"\s"])
        neg = "^" if rng.random() < 0.25 else ""
        items = "".join(rng.sample("abcxyz019", rng.randint(1, 3)))
        return f"[{neg}{items}]"

    def gen_branch():
        atoms = []
        for _ in range(rng.randint(1, 5)):
            a = gen_atom()
            r = rng.random()
            if r < 0.3:
                a += rng.choice("*+?")
                if rng.random() < 0.25:
                    a += "?"  # non-greedy
            elif r < 0.45:
                lo = rng.randint(0, 2)
                hi = rng.choice(["", lo + rng.randint(0, 2)])
                a += ("{%d}" % lo if hi == lo and rng.random() < 0.5
                      else "{%d,%s}" % (lo, hi))
            atoms.append(a)
        b = "".join(atoms)
        if rng.random() < 0.15:
            b = "^" + b
        if rng.random() < 0.15:
            b = b + "$"
        return b

    accepted = 0
    for trial in range(60):
        pattern = "|".join(gen_branch()
                           for _ in range(rng.randint(1, 3)))
        lines = ["".join(rng.choices(alphabet, k=rng.randint(0, 30)))
                 for _ in range(rng.randint(1, 40))]
        data = "\n".join(lines).encode()
        got = nfagrep_host_result(data, pattern)
        if got is None:
            # Only legitimate host routes: a nullable pattern.
            assert parse_nfa_pattern(pattern) is None, (trial, pattern)
            continue
        accepted += 1
        assert got == oracle(data, pattern), (trial, pattern, lines)
    assert accepted >= 30, "fuzz generated too few device-eligible patterns"


# ── tier-4 dispatch cost model (round 5) ───────────────────────────────


def test_cost_model_pins(monkeypatch):
    import dsi_tpu.ops.nfak as nfak

    monkeypatch.setenv("DSI_NFA_DISPATCH", "host")
    assert nfak.tier4_preferred(16) is False
    assert nfak.nfagrep_host_result(TEXT, "qu+ick") is None  # host serves
    monkeypatch.setenv("DSI_NFA_DISPATCH", "device")
    assert nfak.tier4_preferred(16) is True


def test_cost_model_routes_to_winner(monkeypatch):
    import dsi_tpu.ops.nfak as nfak

    monkeypatch.delenv("DSI_NFA_DISPATCH", raising=False)
    key = nfak._cost_key(16)
    monkeypatch.setitem(nfak._cost_cache, key,
                        {"host_mbps": 20.0, "kernel_mbps": 2.0})
    nfak._cost_loaded = True
    assert nfak.tier4_preferred(16) is False
    assert nfak.nfagrep_host_result(TEXT, "qu+ick") is None
    monkeypatch.setitem(nfak._cost_cache, key,
                        {"host_mbps": 2.0, "kernel_mbps": 20.0})
    assert nfak.tier4_preferred(16) is True
    got = nfak.nfagrep_host_result(TEXT, "qu+ick")
    assert got == oracle(TEXT, "qu+ick")


def test_cost_model_calibrates_and_persists(monkeypatch, tmp_path):
    """With no measurement the process calibrates on the spot and keeps
    the result beside the one compile cache."""
    import dsi_tpu.ops.nfak as nfak

    monkeypatch.delenv("DSI_NFA_DISPATCH", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr("dsi_tpu.utils.compilecache.enabled", lambda: True)
    monkeypatch.setattr(nfak, "_cost_cache", {})
    monkeypatch.setattr(nfak, "_cost_loaded", False)
    pref = nfak.tier4_preferred(16)
    assert pref in (True, False)  # measured, not None
    entry = nfak._load_costs()[nfak._cost_key(16)]
    assert entry["host_mbps"] > 0 and entry["kernel_mbps"] > 0
    import json

    on_disk = json.load(open(tmp_path / "nfa_cost.json"))
    assert on_disk[nfak._cost_key(16)] == entry
