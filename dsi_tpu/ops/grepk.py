"""TPU grep kernel: literal substring search over a whole chunk.

Device replacement for the grep app's map hot loop (per-line regex scan,
reference intent at ``mrapps/dgrep.go:27-35``): the pattern-match mask for
every byte position is computed with ``len(pattern)`` shifted elementwise
compares (no gathers, no loops over positions), and which lines hold a
match is read at the line ENDS from two scans (:func:`line_flags_from_match`,
shared by all four grep tiers; no scatter, no per-line buffer) — the same
static-shape, vector-only discipline as ``ops/wordcount.py``.  The host
half is here too: the matched line ends come back as packed bits and each
line is taken from the text by its end offset (:func:`lines_from_hits`).

Scope: fixed ASCII literal patterns without newlines; anything else (regex
metacharacters, non-ASCII) falls back to the host app — correctness never
depends on the kernel (``backends/tpu.py`` contract).
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsi_tpu.obs import span as _span
from dsi_tpu.ops.wordcount import _pad_pow2, _shift_left


@jax.named_scope("line_flags")
def line_flags_from_match(chunk: jax.Array, match: jax.Array):
    """Per-position match mask -> matched line ENDS as packed bits, shared
    by all four grep tiers.  Returns (hit_bits uint32 [n / 32], n_lines
    i32).

    A line ends at every newline and, the last one, at ``n - 1``
    (``_pad_pow2`` leaves at least one zero byte, and padding cannot
    match).  With ``run`` the running match count, the line that ends at
    p holds ``run[p] - run[q]`` flags, q the newline before it; ``run``
    never falls, so ``run[q]`` is the running maximum of ``run`` over the
    newlines strictly before p.  A flag on a newline's own position (the
    NFA tier's end latch) counts for the line that newline ends.  Two
    scans, no per-line buffer: no line count can overflow anything.

    Bit k of word w is position ``k * (n / 32) + w``: 32 contiguous slabs
    OR-ed elementwise (:func:`hit_ends` is the host's inverse)."""
    n = chunk.shape[0]
    is_nl = chunk == 10
    is_end = is_nl | (jnp.arange(n, dtype=jnp.int32) == n - 1)
    run = jnp.cumsum(match.astype(jnp.int32))
    at_nl = jnp.where(is_nl, run, 0)
    prev = lax.cummax(jnp.pad(at_nl[:-1], (1, 0)))  # strictly before
    hit = is_end & (run > prev)
    n_lines = jnp.sum(is_nl.astype(jnp.int32)) + 1
    words = n // 32
    hit_bits = jnp.zeros(words, jnp.uint32)
    for k in range(32):  # static unroll: slab k is bit k
        hit_bits |= hit[k * words:(k + 1) * words].astype(jnp.uint32) << k
    return hit_bits, n_lines


def ascii_text(data: bytes, nul_ok: bool = True) -> Optional[str]:
    """``data`` as text, or None when the host path has to decide: a
    byte is not ASCII, or (``nul_ok`` false: the class and NFA tiers) a
    NUL inside a line would disagree with host ``re``."""
    with _span("decode", lane="host", bytes=len(data)):
        if not nul_ok and b"\x00" in data:
            return None
        try:
            return data.decode("ascii")
        except UnicodeDecodeError:
            return None


def pad_chunk(data: bytes) -> np.ndarray:
    """The split as the kernels take it: zero-padded to a power of two."""
    with _span("materialize", lane="host", bytes=len(data)):
        return _pad_pow2(data)


def upload_chunk(chunk_np: np.ndarray) -> jax.Array:
    with _span("upload", bytes=chunk_np.nbytes):
        return jnp.asarray(chunk_np)


def run_kernel(program: str, run):
    """One ``kernel`` span of ``program``: dispatch to the blocking read
    of the line count.  ``run()`` -> (hit_bits, n_lines).  A program not
    compiled yet compiles here, logged and counted like any other."""
    with _span("kernel", program=program, attempt=0):
        hit_bits, n_lines = run()
        return hit_bits, int(n_lines)


def hit_ends(words: np.ndarray) -> np.ndarray:
    """Positions of the set bits of ``line_flags_from_match``'s packed
    words, ascending.  Only the non-zero words are expanded, so the cost
    follows the hits, not the 2^24 positions."""
    nz = np.flatnonzero(words)
    bits = (words[nz, None] >> np.arange(32, dtype=np.uint32)) & 1
    row, k = np.nonzero(bits)
    return np.sort(k * len(words) + nz[row])


def lines_from_hits(text: str, hit_bits, nl: int) -> Optional[List[str]]:
    """Map the device's matched line ends back to text lines, each taken
    by offset; None on a host/device line-count disagreement (the host
    path decides: correctness never depends on a kernel,
    ``backends/tpu.py`` contract)."""
    with _span("pull") as sp:
        words = np.asarray(hit_bits)
        sp.set(bytes=words.nbytes)
    with _span("decode", lane="host") as sp:
        if text.count("\n") + 1 != nl:
            return None
        out = []
        for e in hit_ends(words).tolist():
            e = min(e, len(text))  # the last line ends in the padding
            out.append(text[text.rfind("\n", 0, e) + 1:e])
        sp.set(records=len(out))
        return out


def grep_kernel(chunk: jax.Array, pattern: jax.Array):
    """Match lines of ``chunk`` containing the literal ``pattern``.

    Returns (hit_bits uint32 [n / 32], n_lines i32), the shared tier
    contract (:func:`line_flags_from_match`).  Lines are '\\n'-delimited;
    the host takes each matched line by its end offset
    (:func:`lines_from_hits`).  Padding zeros can never match (patterns
    are printable ASCII).
    """
    m = pattern.shape[0]
    match = jnp.ones(chunk.shape[0], jnp.bool_)
    with jax.named_scope("match"):
        for j in range(m):  # static unroll over the (short) pattern
            match &= _shift_left(chunk, j) == pattern[j]
    return line_flags_from_match(chunk, match)


def _grep_example(n: int, m: int):
    return (jax.ShapeDtypeStruct((n,), np.uint8),
            jax.ShapeDtypeStruct((m,), np.uint8))


@functools.lru_cache(maxsize=64)
def _grep_compiled(n: int, m: int):
    from dsi_tpu.backends.aotcache import cached_compile

    return cached_compile("grep_kernel", grep_kernel, _grep_example(n, m))


def _grep_jit(chunk, pattern):
    """The grep kernel as an explicitly compiled, memoized program
    (backends/aotcache.py)."""
    fn = _grep_compiled(int(chunk.shape[0]), int(pattern.shape[0]))
    return fn(chunk, pattern)


_REGEX_META = set(".^$*+?{}[]()|\\")


def is_literal_pattern(pat: str) -> bool:
    """True when the regex ``pat`` is a plain literal the kernel can run:
    printable ASCII (0x20..0x7E) only — control bytes could match the
    chunk's zero padding — and no regex metacharacters; a match can then
    never span lines, and byte-equality search == regex search."""
    return (bool(pat)
            and all(0x20 <= ord(c) <= 0x7E for c in pat)
            and not set(pat) & _REGEX_META)


def grep_host_result(data: bytes, pattern: str) -> Optional[List[str]]:
    """Matching lines of ``data`` (split on '\\n', in order), or None when
    the pattern needs the host regex path."""
    if not is_literal_pattern(pattern):
        return None
    text = ascii_text(data)
    if text is None:
        return None
    if len(pattern) > len(data):
        return []  # a literal longer than the data cannot match any line
    chunk = upload_chunk(pad_chunk(data))
    pat = jnp.asarray(np.frombuffer(pattern.encode("ascii"), dtype=np.uint8))
    hit_bits, nl = run_kernel("grep_kernel", lambda: _grep_jit(chunk, pat))
    return lines_from_hits(text, hit_bits, nl)
