"""TPU grep kernel: literal substring search over a whole chunk.

Device replacement for the grep app's map hot loop (per-line regex scan,
reference intent at ``mrapps/dgrep.go:27-35``): the pattern-match mask for
every byte position is computed with ``len(pattern)`` shifted elementwise
compares (no gathers, no loops over positions), line membership is a cumsum
over newline bytes, and per-line match flags are a sorted segment-max —
the same static-shape, vector-only discipline as ``ops/wordcount.py``.

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

from dsi_tpu.obs import span as _span
from dsi_tpu.ops.wordcount import _pad_pow2, _shift_left


@jax.named_scope("line_flags")
def line_flags_from_match(chunk: jax.Array, match: jax.Array, l_cap: int):
    """Per-position match mask -> per-line flags, shared by the literal
    kernel here and the class-pattern kernel (``ops/regexk.py``): line
    membership is a cumsum over newline bytes, per-line flags a sorted
    segment-max.  Returns (line_match [l_cap] i32 in line order,
    n_lines i32, overflow bool)."""
    is_nl = chunk == 10
    cum = jnp.cumsum(is_nl.astype(jnp.int32))
    line_id = cum - is_nl.astype(jnp.int32)  # newlines strictly before i
    n_lines = cum[-1] + 1
    overflow = n_lines > l_cap
    seg = jnp.minimum(line_id, l_cap)
    line_match = jax.ops.segment_max(
        match.astype(jnp.int32), seg, num_segments=l_cap + 1,
        indices_are_sorted=True)[:l_cap]
    return line_match, n_lines, overflow


def line_cap_rungs(n: int):
    """The shared l_cap rung schedule: average line >= 8 bytes first,
    then the n+1 hard bound (every byte a '\\n').  One definition so
    the warm ladders and the retry loop can never drift onto different
    compiled shapes."""
    return (max(n // 8, 1), n + 1)


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


def retry_line_caps(n: int, run, program: str):
    """Walk :func:`line_cap_rungs` (exactness_retry discipline) until a
    rung's line buffer holds every line.  ``run(l_cap)`` ->
    (line_match, n_lines, overflow).  A rung not compiled yet compiles
    here, logged and counted like any other program.  Each attempt is
    one ``kernel`` span of ``program``: dispatch to the first blocking
    scalar read."""
    for attempt, l_cap in enumerate(line_cap_rungs(n)):
        with _span("kernel", program=program, attempt=attempt, cap=l_cap):
            line_match, n_lines, overflow = run(l_cap)
            overflow = bool(overflow)
        if not overflow:
            break
    return line_match, int(n_lines)


def lines_from_flags(text: str, line_match, nl: int) -> Optional[List[str]]:
    """Map device line flags back to text lines; None on a host/device
    line-count disagreement (the host path decides — correctness never
    depends on a kernel, ``backends/tpu.py`` contract)."""
    with _span("pull") as sp:
        flags = np.asarray(line_match[:nl])
        sp.set(bytes=flags.nbytes)
    with _span("decode", lane="host") as sp:
        lines = text.split("\n")
        if len(lines) != nl:
            return None
        out = [lines[i] for i in range(nl) if flags[i]]
        sp.set(records=len(out))
        return out


def grep_kernel(chunk: jax.Array, pattern: jax.Array, *, l_cap: int):
    """Match lines of ``chunk`` containing the literal ``pattern``.

    Returns (line_match [l_cap] i32 flags in line order, n_lines i32,
    overflow bool).  Lines are '\\n'-delimited; the host maps flags back to
    text with ``text.split('\\n')``.  Padding zeros can never match
    (patterns are printable ASCII).
    """
    m = pattern.shape[0]
    match = jnp.ones(chunk.shape[0], jnp.bool_)
    with jax.named_scope("match"):
        for j in range(m):  # static unroll over the (short) pattern
            match &= _shift_left(chunk, j) == pattern[j]
    return line_flags_from_match(chunk, match, l_cap)


def _grep_example(n: int, m: int):
    return (jax.ShapeDtypeStruct((n,), np.uint8),
            jax.ShapeDtypeStruct((m,), np.uint8))


@functools.lru_cache(maxsize=64)
def _grep_compiled(n: int, m: int, l_cap: int):
    from dsi_tpu.backends.aotcache import cached_compile

    return cached_compile("grep_kernel", grep_kernel, _grep_example(n, m),
                          static={"l_cap": l_cap})


def _grep_jit(chunk, pattern, *, l_cap: int):
    """The grep kernel as an explicitly compiled, memoized program
    (backends/aotcache.py)."""
    fn = _grep_compiled(int(chunk.shape[0]), int(pattern.shape[0]), l_cap)
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
    the pattern needs the host regex path.  Retries the static line buffer
    on overflow (exactness_retry discipline, avg line >= 8 bytes first)."""
    if not is_literal_pattern(pattern):
        return None
    text = ascii_text(data)
    if text is None:
        return None
    if len(pattern) > len(data):
        return []  # a literal longer than the data cannot match any line
    chunk = upload_chunk(pad_chunk(data))
    pat = jnp.asarray(np.frombuffer(pattern.encode("ascii"), dtype=np.uint8))
    n = int(chunk.shape[0])
    line_match, nl = retry_line_caps(
        n, lambda l_cap: _grep_jit(chunk, pat, l_cap=l_cap), "grep_kernel")
    return lines_from_flags(text, line_match, nl)
