"""TPU grep tier 4: variable-length regex via log-depth NFA matrix scan.

Tiers 1-3 (``grepk``/``regexk``/``altk``) cover fixed-length patterns;
this tier runs the variable-length operators on device: ``* + ?``,
bounded reps ``{m}``/``{m,}``/``{m,n}`` (expanded into optional atoms),
their non-greedy forms (existence per line is greediness-independent),
and top-level alternations mixing them — ``ab*c``, ``[0-9]{2,4}``,
``colou?r``, ``^x.*?y$``.  Groups, backrefs, and nullable patterns
(which match every line) still fall back to the host app — correctness
never depends on a kernel (``backends/tpu.py`` contract).

TPU-first shape — no data-dependent control flow, log-depth, MXU-heavy:

1. The pattern compiles (host-side, Glushkov construction) to an NFA of
   S <= 48 states; every byte value becomes a boolean S x S transition
   matrix, assembled into a ``[256, S, S]`` table.
2. Matching a chunk is then an associative product of per-byte matrices
   over the boolean semiring.  The kernel computes per-block transition
   matrices with a K-step batched-matmul scan, an exclusive
   ``lax.associative_scan`` product across blocks (log depth), and a
   vmapped K-step vector re-walk that emits a per-position "matched"
   latch bit — turned into matched line ends by the same two-scan
   machinery as every other grep tier (``grepk.line_flags_from_match``).
3. The table and start vector are program ARGUMENTS, not constants: one
   compiled executable (per chunk-size/state-bucket) serves EVERY
   pattern — compile it once and all variable-length patterns share it.

Line discipline: content classes exclude ``\\n``/``\\0``, so no match
window spans lines or padding; the line-end bytes reset all NFA states
to the line-start states, and the absorbing "matched" latch survives to
the line's last position, where the line's flag count is read.  Inputs
containing NUL route to the host (NUL acts as a line-end here but not
in ``re``), same as ``regexk``.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dsi_tpu.ops.altk import split_top_level
from dsi_tpu.ops.grepk import (
    ascii_text,
    line_flags_from_match,
    lines_from_hits,
    pad_chunk,
    run_kernel,
    upload_chunk,
)
from dsi_tpu.ops.regexk import ATOM_REJECT, atom_members
from dsi_tpu.ops.wordcount import _pad_pow2

#: State-count buckets (compiled-program granularity): S = 4 fixed
#: states + one per pattern atom, rounded up to the smallest bucket.
_S_BUCKETS = (16, 32, 48)
#: Fixed state indices: 0 = always-alive sentinel, 1 = line-start state,
#: atoms at 2..., end-latch = bucket-2, latch = bucket-1 (_build_table).
_S_ANY, _S_LINE = 0, 1
#: Bytes that end a line for the automaton: newline and the chunk's
#: zero padding.
_LINE_END = (0, 10)


class _Atom:
    __slots__ = ("bitmap", "nullable", "repeat")

    def __init__(self, bitmap: np.ndarray, mod: str):
        self.bitmap = bitmap            # [256] bool, False at 0 and 10
        self.nullable = mod in ("?", "*")   # NOT `mod in "?*"`: '' is a
        self.repeat = mod in ("+", "*")     # substring of every string


def _parse_branch(branch: str):
    """One alternation branch -> (atoms, anchor_start, anchor_end) or
    None.  Anchors bind per branch, exactly re's loosest-| semantics."""
    if not branch or not all(0x01 <= ord(c) <= 0x7E for c in branch):
        return None
    a_start = branch.startswith("^")
    if a_start:
        branch = branch[1:]
    a_end = branch.endswith("$") and not branch.endswith("\\$")
    if a_end:
        branch = branch[:-1]
    if not branch:
        return None
    atoms: List[_Atom] = []
    i = 0
    while i < len(branch):
        if branch[i] in ATOM_REJECT and branch[i] not in "{}":
            # Groups, stray anchors — and a modifier with no atom before
            # it ('*a'), which re rejects as an error.  Braces fall
            # through: a lone '}' is a literal in re, and '{' is handled
            # just below.
            return None
        if branch[i] == "{":
            peek, pi = _parse_bounded_rep(branch, i)
            if peek is not None or pi < 0:
                # A VALID rep shape with nothing to repeat: re errors
                # ("nothing to repeat") — host owns it.  An invalid body
                # ('{2,x}') is a literal brace in re; fall through and
                # parse it as a literal atom.
                return None
        parsed = atom_members(branch, i)
        if parsed is None:
            return None
        members, i = parsed
        mod = ""
        reps: Optional[Tuple[int, int]] = None  # (min, max); max<0 = inf
        if i < len(branch) and branch[i] in "*+?":
            mod = branch[i]
            i += 1
        elif i < len(branch) and branch[i] == "{":
            reps, i = _parse_bounded_rep(branch, i)
            if reps is None and i < 0:
                return None  # malformed in a way re also rejects
            if reps is not None and max(reps) > _S_BUCKETS[-1]:
                # Reject oversized counts BEFORE the expansion loop: the
                # parse runs in every worker task on every platform, and
                # 'a{2000000000}' must fail in microseconds, not expand.
                return None
        if (mod or reps is not None) and i < len(branch) \
                and branch[i] == "?":
            # Non-greedy (*? +? ?? {m,n}?): greediness affects WHICH
            # match is found, never WHETHER one exists, and per-line
            # flags only need existence — greedy-equivalent here.
            i += 1
        if (mod or reps is not None) and i < len(branch) \
                and branch[i] in "*+?":
            return None  # stacked modifiers: host
        members = members - {0, 10}
        if not members and mod not in ("?", "*") and (
                reps is None or reps[0] > 0):
            return None  # required atom can only match padding/newline
        bitmap = np.zeros(256, bool)
        bitmap[list(members)] = True
        if reps is None:
            atoms.append(_Atom(bitmap, mod))
        else:
            # X{m,n} expands to m required copies + (n-m) optional ones;
            # X{m,} to m copies with the last one repeating.  The atom
            # budget (state bucket) naturally bounds the expansion.
            lo, hi = reps
            for _ in range(lo):
                atoms.append(_Atom(bitmap, ""))
            if hi < 0:
                if lo == 0:
                    atoms.append(_Atom(bitmap, "*"))
                else:
                    atoms[-1] = _Atom(bitmap, "+")
            else:
                for _ in range(hi - lo):
                    atoms.append(_Atom(bitmap, "?"))
        if len(atoms) > _S_BUCKETS[-1]:
            return None  # expansion exceeds the largest state bucket
    if all(a.nullable for a in atoms):
        return None  # nullable pattern matches EVERY line: host owns it
    return atoms, a_start, a_end


def _parse_bounded_rep(branch: str, i: int):
    """Parse ``{m}``, ``{m,}``, or ``{m,n}`` at ``branch[i]``.

    Returns ``((lo, hi), next_i)`` with ``hi == -1`` for unbounded, or
    ``(None, i)`` when the brace is not a valid bounded rep (re then
    treats it as a literal '{' — the caller re-parses it as an atom), or
    ``(None, -1)`` for ``{m,n}`` with ``m > n`` (re raises: host)."""
    j = branch.find("}", i)
    if j == -1:
        return None, i
    body = branch[i + 1:j]
    parts = body.split(",")
    if not all(p.isdigit() or p == "" for p in parts) or len(parts) > 2:
        return None, i
    if len(parts) == 1:
        if not parts[0]:
            return None, i  # bare '{}' is a literal brace pair in re
        lo = hi = int(parts[0])
    else:
        # re treats '{,n}' as the quantifier {0,n} (and '{,}' as {0,})
        # on every supported interpreter — "omitting m specifies a lower
        # bound of zero" has been documented re behavior since long
        # before 3.10 (verified against re/_parser.py's brace parse).
        lo = int(parts[0]) if parts[0] else 0
        hi = -1 if parts[1] == "" else int(parts[1])
    if hi >= 0 and lo > hi:
        return None, -1
    return (lo, hi), j + 1


def parse_nfa_pattern(pat: str):
    """Full pattern -> (branches, n_atoms) or None, where each branch is
    (atoms, anchor_start, anchor_end)."""
    raw = split_top_level(pat)
    if raw is None:
        return None
    branches = []
    total = 0
    for b in raw:
        parsed = _parse_branch(b)
        if parsed is None:
            return None
        branches.append(parsed)
        total += len(parsed[0])
    if total + 4 > _S_BUCKETS[-1]:
        return None  # pattern too wide for the largest state bucket
    return branches, total


def _bucket(n_atoms: int) -> int:
    need = n_atoms + 4
    for s in _S_BUCKETS:
        if need <= s:
            return s
    raise AssertionError("parse_nfa_pattern admitted an oversized pattern")


def _build_table(branches, n_atoms: int) -> Tuple[np.ndarray, np.ndarray]:
    """Glushkov NFA -> ([256, S, S] float32 transition table, [S] float32
    start vector).  Row-vector convention: v' = v @ M[byte]."""
    S = _bucket(n_atoms)
    latch = S - 1       # persisting: set mid-line, dies at newline
    end_latch = S - 2   # one-position: set BY a line-end byte for $
    M = np.zeros((256, S, S), np.float32)
    content = np.ones(256, bool)
    content[list(_LINE_END)] = False

    # Fixed machinery: the sentinel is always alive; the line-start state
    # is entered (from the sentinel) by every line-end byte; the latch
    # survives every byte except newline (padding keeps the final line's
    # verdict alive to the line's end at n - 1).
    M[:, _S_ANY, _S_ANY] = 1.0
    for b in _LINE_END:
        M[b, _S_ANY, _S_LINE] = 1.0
    M[content, latch, latch] = 1.0
    M[0, latch, latch] = 1.0

    pos = 2  # first atom state index
    for atoms, a_start, a_end in branches:
        idx = list(range(pos, pos + len(atoms)))
        pos += len(atoms)

        def successors(i: int) -> List[int]:
            out = []
            if atoms[i].repeat:
                out.append(i)
            j = i + 1
            while j < len(atoms):
                out.append(j)
                if not atoms[j].nullable:
                    break
                j += 1
            return out

        firsts = []
        for j, a in enumerate(atoms):
            firsts.append(j)
            if not a.nullable:
                break
        lasts = []
        for j in range(len(atoms) - 1, -1, -1):
            lasts.append(j)
            if not atoms[j].nullable:
                break
        last_set = set(lasts)

        # Start edges: anchored branches begin only at line starts;
        # unanchored also from the always-alive sentinel (match can
        # start anywhere).
        srcs = [_S_LINE] if a_start else [_S_ANY, _S_LINE]
        edges = [(s, j) for s in srcs for j in firsts]
        edges += [(idx[i], j) for i in range(len(atoms))
                  for j in successors(i)]
        for src, j in edges:
            bm = atoms[j].bitmap
            M[bm, src, idx[j]] = 1.0
            if j in last_set and not a_end:
                # Entering an accepting position completes a match.
                M[bm, src, latch] = 1.0
        if a_end:
            # $-anchored: the match completes only when a line-end byte
            # arrives while an accepting position is active.  It must
            # set the ONE-POSITION end-latch, not the persisting latch:
            # a latch born at the newline would survive through (and
            # falsely flag) the entire NEXT line, since the persisting
            # latch only dies at newlines.
            for j in last_set:
                for b in _LINE_END:
                    M[b, idx[j], end_latch] = 1.0

    v0 = np.zeros(S, np.float32)
    v0[_S_ANY] = 1.0
    v0[_S_LINE] = 1.0
    return M, v0


def nfa_kernel(chunk: jax.Array, table: jax.Array, v0: jax.Array, *,
               s_bucket: int, block: int):
    """Match lines of ``chunk`` against the NFA in ``table``.

    Returns (hit_bits uint32 [n / 32], n_lines i32) — the shared tier
    contract.  ``table``/``v0`` are runtime arguments: the compiled
    program is pattern-independent.
    """
    n = chunk.shape[0]
    k = min(block, n)
    nb = n // k
    cols = chunk.reshape(nb, k).T.astype(jnp.int32)  # [k, nb]
    latch_idx = s_bucket - 1

    # 1: per-block transition matrices (K-step batched-matmul scan over
    # the boolean semiring; f32 matmul + threshold keeps it exact — row
    # sums are bounded by S, far under f32 integer precision).
    eye = jnp.broadcast_to(jnp.eye(s_bucket, dtype=jnp.float32),
                           (nb, s_bucket, s_bucket))

    def bstep(B, col):
        Mb = table[col]                       # [nb, S, S]
        return (jnp.matmul(B, Mb) > 0).astype(jnp.float32), None

    B, _ = jax.lax.scan(bstep, eye, cols)

    # 2: exclusive prefix product across blocks (log depth).
    P = jax.lax.associative_scan(
        lambda a, b: (jnp.matmul(a, b) > 0).astype(jnp.float32), B, axis=0)
    entry = jnp.concatenate([eye[:1], P[:-1]], axis=0)   # [nb, S, S]
    u = (jnp.einsum("s,bst->bt", v0, entry) > 0).astype(jnp.float32)

    # 3: vector re-walk per block, all blocks in parallel, emitting the
    # per-position latch bit.
    def vstep(v, col):
        Mb = table[col]
        v2 = (jnp.einsum("bs,bst->bt", v, Mb) > 0).astype(jnp.float32)
        # Either latch flavor flags the position: persisting (S-1, set
        # mid-line) or one-position end-latch (S-2, set at line ends).
        return v2, jnp.maximum(v2[:, latch_idx], v2[:, latch_idx - 1])

    _, latch = jax.lax.scan(vstep, u, cols)              # [k, nb]
    mask = latch.T.reshape(n) > 0
    return line_flags_from_match(chunk, mask)


@functools.lru_cache(maxsize=64)
def _nfa_compiled(n: int, s_bucket: int, block: int):
    from dsi_tpu.backends.aotcache import cached_compile

    sds = jax.ShapeDtypeStruct
    example = (sds((n,), jnp.uint8),
               sds((256, s_bucket, s_bucket), jnp.float32),
               sds((s_bucket,), jnp.float32))
    return cached_compile(f"nfagrep_s{s_bucket}", nfa_kernel, example,
                          static={"s_bucket": s_bucket, "block": block})


#: In-process view of the persisted calibration table (loaded once; a
#: calibration updates both).
_cost_cache: dict = {}
_cost_loaded = False


def _cost_path() -> Optional[str]:
    """The calibration table's file, beside the one compile cache; None
    where the cache is switched off (the table is then per process)."""
    from dsi_tpu.utils.compilecache import cache_dir, enabled

    return os.path.join(cache_dir(), "nfa_cost.json") if enabled() else None


def _load_costs() -> dict:
    global _cost_loaded
    if not _cost_loaded:
        import json

        path = _cost_path()
        if path is not None:
            try:
                with open(path) as f:
                    _cost_cache.update(json.load(f))
            except (OSError, ValueError):
                pass
        _cost_loaded = True
    return _cost_cache


def _save_cost(key: str, entry: dict) -> None:
    import json

    costs = _load_costs()
    costs[key] = entry
    path = _cost_path()
    if path is None:
        return
    tmp = path + f".tmp{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(tmp), exist_ok=True)
        # dsicheck: allow[raw-write] calibration cost cache:
        # temp+rename for atomicity, no fsync — a lost entry just
        # re-measures, and _save_cost already swallows OSError because
        # persistence here is an optimization, never a failure
        with open(tmp, "w") as f:
            json.dump(costs, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass  # cost persistence is an optimization, never a failure


def _cost_key(s_bucket: int) -> str:
    d = jax.devices()[0]
    return f"{d.platform}-{d.device_kind}-jax{jax.__version__}|s{s_bucket}"


#: Representative calibration pattern per state bucket (must parse into
#: that bucket: atoms + 4 rounded up — see _bucket).
_CAL_PATTERNS = {16: "qu+ick|dogs?$", 32: "a{5,20}b", 48: "a{20,40}b"}


def _cal_text(n_lines: int = 4000) -> bytes:
    lines = []
    for i in range(n_lines):
        lines.append(f"the quick{'k' * (i % 3)} brown fox jumped over "
                     f"line {'x' * (i % 17)} with dog{'s' * (i % 2)} and "
                     f"{'a' * (i % 31)}b tokens".encode())
    return b"\n".join(lines)


def calibrate_tier4(s_bucket: int, quick: bool = False) -> dict:
    """Measure host ``re`` vs the NFA kernel once for this (device,
    state bucket) and persist the result beside the compile cache.  The
    kernel compiles here if this process has not compiled it yet.

    ``quick=True`` is the inline-dispatch variant (see
    :func:`tier4_preferred`): an ~8x smaller corpus and a single timing
    rep, bounding what a cold worker task pays before its first answer.
    The persisted entry is marked ``{"quick": true}``; a later full
    calibration simply overwrites it."""
    import re as _re
    import time

    pat = _CAL_PATTERNS[s_bucket]
    data = _cal_text(500 if quick else 4000)
    text = data.decode()
    rx = _re.compile(pat)

    def best(f, reps=1 if quick else 3):
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            out.append(time.perf_counter() - t0)
        return min(out)

    host_s = best(lambda: [ln for ln in text.split("\n") if rx.search(ln)])

    branches, n_atoms = parse_nfa_pattern(pat)
    assert _bucket(n_atoms) == s_bucket, (pat, _bucket(n_atoms))
    table_np, v0_np = _build_table(branches, n_atoms)
    chunk = jnp.asarray(_pad_pow2(data))
    n = int(chunk.shape[0])
    block = min(256, n)
    table = jnp.asarray(table_np)
    v0 = jnp.asarray(v0_np)
    fn = _nfa_compiled(n, s_bucket, block)

    def kernel():
        jax.block_until_ready(fn(chunk, table, v0))

    kernel()  # warm (load or compile) outside the timed reps
    kern_s = best(kernel)

    mb = len(data) / 1e6
    entry = {"host_mbps": round(mb / host_s, 3),
             "kernel_mbps": round(mb / kern_s, 3)}
    if quick:
        entry["quick"] = True  # lower-fidelity entry; warm-time overwrites
    _save_cost(_cost_key(s_bucket), entry)
    return entry


def tier4_preferred(s_bucket: int) -> Optional[bool]:
    """Should an eligible variable-length pattern run on the kernel?

    ``DSI_NFA_DISPATCH=device|host`` pins the answer.  Otherwise the
    persisted calibration for this (device, bucket) decides; with no
    measurement, the process calibrates on the spot with the BOUNDED
    quick variant (small corpus, one rep) — on every platform alike, so
    the answer is always a measurement of the device in use (the
    S^3-work kernel measured ~10x slower than host ``re`` on XLA:CPU;
    not measured on the chip)."""
    pin = os.environ.get("DSI_NFA_DISPATCH")
    if pin in ("device", "host"):
        return pin == "device"
    entry = _load_costs().get(_cost_key(s_bucket))
    if entry is None:
        entry = calibrate_tier4(s_bucket, quick=True)
    return entry["kernel_mbps"] > entry["host_mbps"]


def nfagrep_host_result(data: bytes, pattern: str) -> Optional[List[str]]:
    """Matching lines of ``data`` (split on '\\n', in order), or None
    when the pattern or data needs the host regex path."""
    parsed = parse_nfa_pattern(pattern)
    if parsed is None:
        return None
    text = ascii_text(data, nul_ok=False)
    if text is None:
        return None
    branches, n_atoms = parsed
    if not tier4_preferred(_bucket(n_atoms)):
        return None  # measured slower than host re here: host serves it
    table_np, v0_np = _build_table(branches, n_atoms)
    s_bucket = table_np.shape[1]
    # _pad_pow2 guarantees >= 1 trailing zero — the line-end byte the
    # $ latch and final-line handling depend on.
    chunk = upload_chunk(pad_chunk(data))
    n = int(chunk.shape[0])
    hit_bits, nl = run_kernel(
        "nfa_kernel", lambda: _nfa_compiled(n, s_bucket, min(256, n))(
            chunk, jnp.asarray(table_np), jnp.asarray(v0_np)))
    return lines_from_hits(text, hit_bits, nl)
