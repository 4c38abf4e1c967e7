"""Class-pattern device grep (ops/regexk.py) vs the host ``re`` oracle.

Differential discipline as everywhere else: for every supported pattern,
the kernel's matching lines must equal a per-line ``re.search`` scan (the
host app's exact semantics, apps/grep.py:34); unsupported patterns must
return None so the host path decides.
"""

import random
import re

import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.ops.regexk import classgrep_host_result, parse_class_pattern


def _oracle(data: bytes, pattern: str):
    pat = re.compile(pattern)
    return [l for l in data.decode("ascii").split("\n") if pat.search(l)]


SUPPORTED = [
    "[Tt]he",                 # the reference harness's own pattern
    "gr[ae]y",
    "w.rd",
    r"\d\d",
    r"[a-f]x[0-9A-F]",
    "[^aeiou ]ight",
    r"^The",
    r"ed$",
    r"^[A-Z].....$",
    r"\.txt",
    r"\w\s\w",
    r"[a\]b]",                # escaped ']' inside a class
    r"[\d;]x",
]

TEXT = (
    "The quick brown fox\n"
    "bracket ] here; 7x marks\n"
    "a gray day, a grey sky\n"
    "word w0rd weird ward\n"
    "42 is the answer; 0xAF too\n"
    "light fight might sight eight aight\n"
    "Theodore spoke\n"
    "they walked and talked\n"
    "file.txt and fileAtxt\n"
    "SHOUTY\n"
    "ends with ed\n"
    "no trailing newline"
).encode()


@pytest.mark.parametrize("pattern", SUPPORTED)
def test_supported_patterns_match_re_oracle(pattern):
    got = classgrep_host_result(TEXT, pattern)
    assert got is not None, f"{pattern!r} unexpectedly unsupported"
    assert got == _oracle(TEXT, pattern), pattern


@pytest.mark.parametrize("pattern", [
    "a*b", "a+?", "x{2,3}", "(ab)", "a|b", r"\bword", "", "[]", "[z-a]x",
    "a^b", "café",
])
def test_unsupported_patterns_route_to_host(pattern):
    assert parse_class_pattern(pattern) is None
    assert classgrep_host_result(TEXT, pattern) is None


def test_nul_bytes_route_to_host():
    assert classgrep_host_result(b"a\x00b\nxy\n", "[ab]") is None


def test_whitespace_class_covers_ascii_control_separators():
    # re's \s (str mode) matches \x1c-\x1f; these bytes pass the ascii
    # gate, so the kernel's class table must include them.
    data = b"a\x1cb\nc d\nef\n"
    assert classgrep_host_result(data, r"\w\s\w") == _oracle(data, r"\w\s\w")


def test_fuzz_class_patterns_vs_oracle():
    rng = random.Random(13)
    alphabet = "abcDE12 .,"
    for trial in range(25):
        lines = ["".join(rng.choices(alphabet, k=rng.randint(0, 30)))
                 for _ in range(rng.randint(1, 40))]
        data = "\n".join(lines).encode()
        pattern = rng.choice(SUPPORTED + ["[abc]", r"\d", "..", "[^a]b"])
        got = classgrep_host_result(data, pattern)
        assert got is not None
        assert got == _oracle(data, pattern), (trial, pattern, lines)


def test_fuzz_generated_class_patterns_vs_oracle():
    """Random patterns BUILT from the supported grammar (not a fixed
    list): every generated pattern must be accepted and agree with the
    per-line re.search oracle."""
    rng = random.Random(29)
    alphabet = "abcxyzAB01 .,;"

    def gen_atom():
        r = rng.random()
        if r < 0.3:
            return rng.choice("abcxyzAB"), None
        if r < 0.45:
            return ".", None
        if r < 0.6:
            return rng.choice([r"\d", r"\w", r"\s"]), None
        neg = "^" if rng.random() < 0.3 else ""
        items = []
        for _ in range(rng.randint(1, 3)):
            if rng.random() < 0.5:
                lo, hi = sorted(rng.sample("abcdwxyz", 2))
                items.append(f"{lo}-{hi}")
            else:
                items.append(rng.choice("abcxyz019"))
        return f"[{neg}{''.join(items)}]", None

    for trial in range(40):
        pattern = "".join(gen_atom()[0]
                          for _ in range(rng.randint(1, 5)))
        if rng.random() < 0.2:
            pattern = "^" + pattern
        if rng.random() < 0.2:
            pattern = pattern + "$"
        lines = ["".join(rng.choices(alphabet, k=rng.randint(0, 24)))
                 for _ in range(rng.randint(1, 30))]
        data = "\n".join(lines).encode()
        got = classgrep_host_result(data, pattern)
        assert got is not None, (trial, pattern)
        assert got == _oracle(data, pattern), (trial, pattern, lines)


def test_more_lines_than_an_eighth_of_the_bytes():
    # 600 empty lines, then 3-byte ones: no line count overflows anything
    data = b"\n" * 600 + b"xa\n" * 40
    got = classgrep_host_result(data, "[xy]a")
    assert got == _oracle(data, "[xy]a")


def test_anchors_respect_line_boundaries():
    data = b"abc\nxabc\nabcx\nabc"
    assert classgrep_host_result(data, "^abc") == _oracle(data, "^abc")
    assert classgrep_host_result(data, "abc$") == _oracle(data, "abc$")
    assert classgrep_host_result(data, "^abc$") == _oracle(data, "^abc$")
