"""Whole-corpus word count: ONE device program, position-coded results.

This is the bench's fast path.  It was designed to minimize host<->device
bytes and round trips rather than FLOPs; how those costs compare on a
host-attached chip is not measured on the chip yet.

* **One program, one launch** — every input file is padded into fixed-size
  pieces which the program concatenates in HBM (zero padding separates
  files, so no token can straddle a file boundary); tokenize + sort +
  group + count runs over the whole corpus at once.  This replaces the
  reference's nMap independent map tasks + reduce merge
  (``mr/coordinator.go:152``, ``mr/worker.go:110-146``) with a single
  fused XLA program.
* **Uploads are pieced** — each piece is its own host view, and all of
  them go up in one ``device_put`` through ``ops/xfer.put_views``, which
  accounts the upload's wall time.
* **Downloads are position-coded** — the host already holds the corpus
  bytes, so the device never ships word spellings back.  Each unique word
  returns as ``(first_occurrence_position << 7 | byte_length, count)`` —
  8 bytes per unique word in ONE contiguous 1-D uint32 pull (including the
  overflow scalars, so there is exactly one D2H round trip).  The host
  slices the spelling out of its own corpus copy (~2 MB for a 16.7 MB
  corpus, against ~28 MB of full-capacity tables).
* Tokens are maximal ASCII-letter runs — exactly Go's
  ``strings.FieldsFunc(contents, !unicode.IsLetter)`` on ASCII text
  (``mrapps/wc.go:23``); any byte >= 0x80 is detected on device and the
  caller falls back to the host path (same exactness contract as
  ``ops/wordcount.py``).

The program is compiled explicitly (``backends/aotcache.py``) and
persisted by JAX's compile cache (``utils/compilecache.py``): the first
process against a cache directory pays the XLA compile.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dsi_tpu.ops.wordcount import (
    exactness_retry,
    group_sorted,
    lex_sort,
    token_lanes,
)

# pos<<7|len packing needs pos < 2**25: cap the padded corpus at 32 MiB per
# program.  (Bigger corpora use more pieces per program invocation or the
# streaming path, parallel/streaming.py.)
_POS_BITS = 25
_LEN_MASK = 0x7F

_FNV_OFFSET = np.uint32(0x811C9DC5)
_FNV_PRIME = np.uint32(0x01000193)


def corpus_kernel(*pieces, max_word_len: int = 16, u_cap: int = 1 << 18,
                  t_cap_frac: int = 4):
    """Count every word of the concatenated pieces; emit position-coded rows.

    Returns ONE 1-D uint32 array of length ``2*u_cap + 4``:
    ``rows[u_cap, 2]`` flattened (``pos << 7 | len``, ``count``; rows in
    lexicographic word order, pad rows zero) followed by the scalars
    ``[n_unique, max_len, has_high, token_overflow]``.
    """
    import jax.numpy as jnp

    chunk = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
    return _corpus_core(chunk, max_word_len, u_cap, t_cap_frac)


def corpus_kernel_packed(*pieces_and_table, max_word_len: int = 16,
                         u_cap: int = 1 << 18, t_cap_frac: int = 4):
    """``corpus_kernel`` over a 6-bit transport encoding of the corpus.

    The host packs 4 corpus bytes into 3 wire bytes when the corpus uses
    <= 64 distinct byte values (ASCII text trivially does), cutting upload
    bytes by 25% (whether that pays on a host-attached chip is not
    measured).  Inputs: packed pieces (each ``3/4 * piece_size``
    bytes) plus the 64-entry code→byte table; first op on device is the
    exact inverse transform, so everything downstream of ``chunk`` is
    byte-identical to the unpacked path.
    """
    import jax.numpy as jnp

    *pieces, table = pieces_and_table
    pk = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
    b = pk.reshape(-1, 3).astype(jnp.uint32)
    v = (b[:, 0] << 16) | (b[:, 1] << 8) | b[:, 2]
    codes = jnp.stack([(v >> 18) & 63, (v >> 12) & 63,
                       (v >> 6) & 63, v & 63], axis=1).reshape(-1)
    # Table lookup as a 64-way select chain, NOT a gather: the selects fuse
    # into one elementwise pass over the array (a 16M-element gather from a
    # 64-entry table defeats fusion and measured 3x slower end-to-end).
    chunk = jnp.zeros_like(codes, dtype=jnp.uint8)
    for k in range(64):
        chunk = jnp.where(codes == k, table[k], chunk)
    return _corpus_core(chunk, max_word_len, u_cap, t_cap_frac)


def _corpus_core(chunk, max_word_len: int, u_cap: int, t_cap_frac: int):
    import jax.numpy as jnp

    n = chunk.shape[0]
    if n > 1 << _POS_BITS:
        raise ValueError(f"corpus_kernel caps at {1 << _POS_BITS} bytes")
    t_cap = n // t_cap_frac + 1

    # Lanes, lengths and first-byte positions of the tokens, by the
    # word-count program's own movement (ops/wordcount.token_lanes).
    packed_cols, lengths, n_tokens, _, start_pos = token_lanes(
        chunk, max_word_len=max_word_len, t_cap_frac=t_cap_frac,
        with_pos=True)
    token_overflow = n_tokens > t_cap
    valid = lengths > 0  # a token holds a letter; a pad row has length 0
    max_len = jnp.max(lengths, initial=0)
    # Position and length ride grouping as ONE pre-packed payload column
    # (pos << 7 | len — already the wire encoding).
    poslen_tok = jnp.where(
        valid,
        (start_pos.astype(jnp.uint32) << 7)
        | lengths.astype(jnp.uint32), 0)

    # Stable sort over the key lanes (wordcount.py lex_sort): within a
    # group of equal words the original token order (ascending position)
    # survives, so each group's FIRST row carries the word's first
    # occurrence position (its length is group-invariant).
    *scols, sposlen = lex_sort(packed_cols, (poslen_tok,))
    _, totals, upos, ovalid, n_unique = group_sorted(
        tuple(scols), jnp.ones(t_cap, jnp.int32), u_cap)
    poslen = jnp.where(ovalid, sposlen[upos], 0)
    rows = jnp.stack([poslen, totals.astype(jnp.uint32)], axis=1)
    has_high = jnp.any(chunk >= 128)
    scalars = jnp.stack([
        n_unique.astype(jnp.uint32),
        max_len.astype(jnp.uint32),
        has_high.astype(jnp.uint32),
        token_overflow.astype(jnp.uint32)])
    return jnp.concatenate([rows.reshape(-1), scalars])


def pack6_encode(buf: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """6-bit transport encoding: (packed_bytes [3n/4], code→byte table [64]),
    or None when the corpus uses more than 64 distinct byte values.
    ``len(buf)`` must be a multiple of 4 (piece sizes are powers of two)."""
    used = np.flatnonzero(np.bincount(buf, minlength=256))
    if len(used) > 64:
        return None
    table = np.zeros(64, dtype=np.uint8)
    table[:len(used)] = used.astype(np.uint8)
    lut = np.zeros(256, dtype=np.uint8)
    lut[used] = np.arange(len(used), dtype=np.uint8)
    c = lut[buf].astype(np.uint32).reshape(-1, 4)
    v = (c[:, 0] << 18) | (c[:, 1] << 12) | (c[:, 2] << 6) | c[:, 3]
    packed = np.stack([(v >> 16) & 255, (v >> 8) & 255, v & 255],
                      axis=1).astype(np.uint8).reshape(-1)
    return packed, table


def pack_pieces(raws: Sequence[bytes],
                piece_size: int = 1 << 21) -> Tuple[np.ndarray, int]:
    """Lay the files out as fixed-size zero-padded pieces.

    Returns (buf [n_pieces * piece_size] uint8, n_pieces).  A file larger
    than one piece is split at non-letter boundaries (no token straddles a
    split; same rule as ``parallel/shuffle.shard_text``); zero padding at
    each piece tail separates files.  Positions reported by the kernel index
    into exactly this buffer.
    """
    from dsi_tpu.parallel.shuffle import _is_letter_byte

    spans: List[bytes] = []
    for raw in raws:
        off = 0
        while len(raw) - off > piece_size - 1:
            cut = off + piece_size - 1
            while cut > off and _is_letter_byte(raw[cut - 1]) \
                    and _is_letter_byte(raw[cut]):
                cut -= 1
            if cut == off:  # one >2MB letter run: host path will handle it
                cut = off + piece_size - 1
            spans.append(raw[off:cut])
            off = cut
        spans.append(raw[off:])
    n_pieces = len(spans)
    buf = np.zeros(n_pieces * piece_size, dtype=np.uint8)
    for i, s in enumerate(spans):
        buf[i * piece_size:i * piece_size + len(s)] = np.frombuffer(
            s, dtype=np.uint8)
    return buf, n_pieces


class CorpusResult:
    """Position-coded result + the corpus buffer the positions index."""

    __slots__ = ("buf", "pos", "lens", "cnt")

    def __init__(self, buf: np.ndarray, pos: np.ndarray, lens: np.ndarray,
                 cnt: np.ndarray) -> None:
        self.buf = buf      # [N] uint8, W zero bytes of tail padding
        self.pos = pos      # [nu] int64 first-occurrence byte offsets
        self.lens = lens    # [nu] int64 word byte lengths
        self.cnt = cnt      # [nu] int64 counts; rows in lexicographic order

    def words(self) -> List[str]:
        b = self.buf.tobytes()
        return [b[p:p + l].decode("ascii")
                for p, l in zip(self.pos.tolist(), self.lens.tolist())]

    def to_dict(self, n_reduce: int = 10) -> Dict[str, Tuple[int, int]]:
        """{word: (count, reduce_partition)} — the contract of
        ``count_words_host_result`` for drop-in use."""
        parts = (self.ihashes() % np.uint32(n_reduce)).tolist()
        cnts = self.cnt.tolist()
        return {w: (cnts[i], parts[i])
                for i, w in enumerate(self.words())}

    def byte_matrix(self, width: int) -> np.ndarray:
        """[nu, width] uint8 word-byte matrix, zero past each length."""
        mat = self.buf[self.pos[:, None] + np.arange(width)]
        return np.where(np.arange(width) < self.lens[:, None], mat, 0)

    def ihashes(self, mat: np.ndarray | None = None) -> np.ndarray:
        """Vectorized reference ihash (fnv1a32 & 0x7fffffff,
        mr/worker.go:33-37) over all unique words at once.  Pass a
        pre-built ``byte_matrix`` to avoid materialising it twice."""
        if mat is None:
            mat = self.byte_matrix(int(self.lens.max(initial=1)))
        h = np.full(len(self.pos), _FNV_OFFSET, np.uint32)
        for j in range(mat.shape[1]):
            upd = (h ^ mat[:, j]) * _FNV_PRIME
            h = np.where(j < self.lens, upd, h)
        return h & np.uint32(0x7FFFFFFF)


def corpus_wordcount(raws: Sequence[bytes], *, piece_size: int | None = None,
                     max_word_len: int = 16, u_cap: int = 1 << 18,
                     use_aot: bool = True,
                     pack6: bool = False) -> Optional[CorpusResult]:
    """Exact whole-corpus counts, or None when the host path is needed
    (non-ASCII bytes or a word longer than 64 — same escape contract as
    ``count_words_host_result``).  Retries wider static shapes on overflow.

    ``pack6=True`` ships the corpus 6 bits per byte (25% fewer upload
    bytes — the upload is this platform's measured wall) when its alphabet
    fits in 64 symbols, transparently reverting to raw bytes when not."""
    import jax

    buf, n_pieces, piece_size = _resolve_pieces(raws, piece_size)
    if n_pieces == 0:
        return CorpusResult(np.zeros(64, np.uint8), *(np.zeros(0, np.int64)
                                                      for _ in range(3)))
    if len(buf) > 1 << _POS_BITS:
        # Position coding needs pos < 2^25: beyond ~32 MiB per program the
        # caller must chunk the corpus (or use parallel/streaming.py) —
        # None routes there, same contract as the other escapes.
        return None
    n = len(buf)
    table = None
    if pack6:
        enc = pack6_encode(buf)
        if enc is None:
            pack6 = False
        else:
            wire, table = enc
    if pack6:
        wire_piece = piece_size * 3 // 4
    else:
        wire, wire_piece = buf, piece_size
    views = [wire[i * wire_piece:(i + 1) * wire_piece]
             for i in range(n_pieces)]
    if table is not None:
        views.append(table)

    def run(mwl: int, cap: int):
        # The shared overflow/retry discipline (exactness_retry) drives mwl
        # and cap; the token-buffer frac retry is local, as in the other
        # callers (wordcount, shuffle, tfidf).
        for frac in (4, 2):  # exact token bound is n//2+1
            fn = _get_compiled(n_pieces, piece_size, mwl, cap,
                               frac, use_aot, pack6)
            from dsi_tpu.ops import xfer  # host-side; NOT a kernel dep

            dev_args = xfer.put_views(views)
            out = np.asarray(fn(*dev_args))   # the ONE D2H round trip
            nu, max_len, has_high, tok_of = (int(x) for x in out[-4:])
            if not tok_of:
                break

        def payload():
            rows = out[:-4].reshape(-1, 2)[:nu].astype(np.int64)
            return CorpusResult(np.concatenate([buf, np.zeros(64, np.uint8)]),
                                rows[:, 0] >> 7, rows[:, 0] & _LEN_MASK,
                                rows[:, 1])

        return bool(has_high), nu, max_len, payload

    payload = exactness_retry(run, n, max_word_len, u_cap)
    return None if payload is None else payload()


def _resolve_pieces(raws: Sequence[bytes], piece_size: int | None):
    """Piece derivation.  Default piece size: smallest power of two
    holding the largest file plus its separator byte, capped at 2 MiB —
    bigger files split into multiple pieces."""
    if piece_size is None:
        longest = max((len(r) for r in raws), default=1)
        piece_size = min(1 << 21, 1 << max(12, (longest + 1).bit_length()))
    buf, n_pieces = pack_pieces(raws, piece_size)
    return buf, n_pieces, piece_size


def _example_and_fn(n_pieces: int, piece_size: int, pack6: bool):
    import jax

    if pack6:
        example = tuple(
            jax.ShapeDtypeStruct((piece_size * 3 // 4,), np.uint8)
            for _ in range(n_pieces)) + (
            jax.ShapeDtypeStruct((64,), np.uint8),)
        return example, corpus_kernel_packed, "corpus_wc_p6"
    example = tuple(jax.ShapeDtypeStruct((piece_size,), np.uint8)
                    for _ in range(n_pieces))
    return example, corpus_kernel, "corpus_wc"


@functools.lru_cache(maxsize=64)
def _get_compiled(n_pieces: int, piece_size: int, mwl: int, cap: int,
                  frac: int, use_aot: bool, pack6: bool = False):
    static = {"max_word_len": mwl, "u_cap": cap, "t_cap_frac": frac}
    example, fn, name = _example_and_fn(n_pieces, piece_size, pack6)
    from dsi_tpu.backends.aotcache import cached_compile

    return cached_compile(name, fn, example, static=static, x64=True)


def render_lines(mat: np.ndarray, lens: np.ndarray,
                 cnt: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Render ``"<word> <count>\\n"`` lines for every row, fully vectorized.

    Returns (buf [total_bytes] uint8, ends [nu] int64 — exclusive end offset
    of each row's line in ``buf``).  No per-row Python: word bytes come from
    one boolean-mask flatten of the byte matrix, count digits from one
    vectorized divmod grid (counts are int64; rows are word-count totals).
    """
    nu, width = mat.shape
    if nu == 0:
        return np.zeros(0, np.uint8), np.zeros(0, np.int64)
    c = np.maximum(cnt, 1).astype(np.int64)
    dlen = np.full(nu, 1, np.int64)
    p = np.int64(10)
    while True:  # digits(count): bounded by the corpus' total token count
        more = c >= p
        if not more.any():
            break
        dlen += more
        p *= 10
    max_d = int(dlen.max())

    total = lens + 1 + dlen + 1  # word, space, digits, newline
    ends = np.cumsum(total)
    starts = ends - total
    buf = np.zeros(int(ends[-1]), np.uint8)

    col = np.arange(width)
    wmask = col < lens[:, None]
    buf[(starts[:, None] + col)[wmask]] = mat[wmask]
    buf[starts + lens] = 32  # space

    dcol = np.arange(max_d)
    dmask = dcol < dlen[:, None]
    # Most-significant digit first: digit j = cnt // 10^(dlen-1-j) % 10.
    pow10 = np.power(np.int64(10), np.maximum(dlen[:, None] - 1 - dcol, 0))
    digits = (cnt.astype(np.int64)[:, None] // pow10) % 10
    buf[(starts[:, None] + 1 + lens[:, None] + dcol)[dmask]] = \
        (48 + digits[dmask]).astype(np.uint8)
    buf[ends - 1] = 10  # newline
    return buf, ends


def write_corpus_output(res: CorpusResult, n_reduce: int,
                        workdir: str = ".") -> List[str]:
    """Materialise mr-out-<r> files straight from the position-coded table.

    Rows are first put in lexicographic word order host-side (ASCII byte
    order == Python ``sorted`` order on str; the identity permutation on
    the kernel's rows, which arrive in that order, and what makes the
    writer hold for any ``CorpusResult``), then a stable sort by
    partition leaves each partition's lines in the reference's
    within-file order (``mr/worker.go:124-146``).  Everything is
    vectorized numpy — this sits inside the bench's timed window (~0.3 s
    of Python loop before, ~30 ms now at 137k unique words).
    """
    from dsi_tpu.utils.atomicio import atomic_write

    width = int(res.lens.max(initial=1))
    mat = res.byte_matrix(width)  # built once: hashes + spellings below
    part = res.ihashes(mat) % np.uint32(n_reduce)

    worder = np.lexsort(tuple(mat[:, j] for j in range(width - 1, -1, -1)))
    mat = mat[worder]
    part = part[worder]
    res = CorpusResult(res.buf, res.pos[worder], res.lens[worder],
                       res.cnt[worder])

    order = np.argsort(part, kind="stable")
    buf, ends = render_lines(mat[order], res.lens[order], res.cnt[order])
    starts = np.concatenate([[0], ends[:-1]]) if len(ends) else ends
    # Partition boundaries in the reordered row space.
    counts = np.bincount(part, minlength=n_reduce)
    row_bounds = np.concatenate([[0], np.cumsum(counts)])

    paths = []
    for r in range(n_reduce):
        lo, hi = int(row_bounds[r]), int(row_bounds[r + 1])
        lo_b = int(starts[lo]) if lo < hi else 0
        hi_b = int(ends[hi - 1]) if lo < hi else 0
        path = os.path.join(workdir, f"mr-out-{r}")
        with atomic_write(path, mode="wb") as f:
            f.write(buf[lo_b:hi_b].tobytes())
        paths.append(path)
    return paths
