"""Kernel-vs-oracle tests for the TPU word-count ops (CPU-mesh JAX).

Oracle: the host wc app semantics (``mrapps/wc.go:21-34`` — maximal letter
runs) via regex + Counter, and the reference ``ihash`` via the pure-Python
FNV in ``dsi_tpu.mr.worker``.
"""

from __future__ import annotations

import collections
import random
import string

import pytest

from dsi_tpu.apps.wc import tokenize
from dsi_tpu.mr.worker import ihash
from dsi_tpu.ops.wordcount import count_words_host_result, count_words_many


def oracle_counts(text: str):
    return collections.Counter(tokenize(text))


def check(text: str):
    res = count_words_host_result(text.encode("ascii"))
    assert res is not None
    expect = oracle_counts(text)
    got = {w: c for w, (c, _) in res.items()}
    assert got == dict(expect)
    for w, (_, h) in res.items():
        assert h == ihash(w), w


def test_simple():
    check("the quick brown fox jumps over the lazy dog the end")


def test_empty_and_no_letters():
    assert count_words_host_result(b"") == {}
    assert count_words_host_result(b"123 456 !!! \n\t 789") == {}


def test_edges():
    check("word")                      # single word, no separator
    check("a")                         # 1-byte word
    check("a b a b a")                 # minimal spacing (token-cap worst case)
    check("end-of-buffer-word trailing")
    check("Capital capital CAPITAL cApItAl")
    check("under_score split3split digits123mixed")


def test_long_words_retry_wider_kernel():
    # > 16 bytes forces the 64-byte kernel retry path.
    long_word = "supercalifragilisticexpialidocious"  # 34 letters
    check(f"short {long_word} short {long_word}")


def test_very_long_word_falls_back():
    # > 64 letters: exact handling requires the host path.
    assert count_words_host_result(b"x" * 100) is None


def test_non_ascii_falls_back():
    assert count_words_host_result("héllo world".encode("utf-8")) is None


def test_random_text():
    rng = random.Random(7)
    seps = " \n\t.,;:!?0123456789_"
    pieces = []
    for _ in range(5000):
        pieces.append("".join(rng.choice(string.ascii_letters)
                              for _ in range(rng.randint(1, 14))))
        pieces.append(rng.choice(seps) * rng.randint(1, 3))
    check("".join(pieces))


@pytest.mark.parametrize("size", [0, 1, 255, 256, 257, 4096])
def test_padding_boundaries(size):
    rng = random.Random(size)
    text = "".join(rng.choice("ab c") for _ in range(size))
    check(text)


def test_count_words_many_pipelined():
    """Pipelined multi-split path: same results as per-split calls,
    including per-split fallbacks and overflow retries."""
    datas = [
        b"alpha beta alpha",
        "héllo".encode("utf-8"),          # non-ASCII -> None
        b"abcdefghijklmnopqrstuvwx " * 40,      # 24-byte word -> wide retry
        b"a b c " * 300,                        # token-dense -> t_cap retry
        b"",
    ]
    many = count_words_many(datas)
    solo = [count_words_host_result(d) for d in datas]
    assert many == solo
    assert many[1] is None and many[0]["alpha"] == (2, many[0]["alpha"][1])


def test_zero_capacity_start_terminates():
    """A u_cap of 0 must widen through the retry ladder (floor of 1), not
    re-run the same zero-capacity kernel forever — in both entry points."""
    res = count_words_host_result(b"alpha beta alpha", u_cap=0)
    assert res is not None and res["alpha"][0] == 2 and res["beta"][0] == 1
    many = count_words_many([b"alpha beta alpha", b"beta"], u_cap=0)
    assert [m["beta"][0] for m in many] == [1, 1]


def test_pack_key_lanes_order_and_roundtrip():
    """Packed uint64 sort order must equal the unpacked lexicographic
    order, and unpack must invert pack — for even and odd lane counts,
    including PAD rows."""
    import jax.numpy as jnp
    import numpy as np

    from dsi_tpu.ops.wordcount import (_PAD_KEY, pack_key_lanes,
                                       unpack_key_lanes)
    from dsi_tpu.utils.jaxcompat import enable_x64

    rng = np.random.default_rng(3)
    for k in (1, 2, 3, 4, 16):
        n = 257
        cols_np = rng.integers(0, 0x7F7F7F80, size=(k, n), dtype=np.uint32)
        # sprinkle PAD rows (all lanes 0xFFFFFFFF), which must sort last
        pad_rows = rng.choice(n, size=16, replace=False)
        for j in range(k):
            cols_np[j, pad_rows] = _PAD_KEY
        cols = tuple(jnp.asarray(cols_np[j]) for j in range(k))

        # Eager u64 ops need the scope held across every op touching the
        # packed values (jaxcompat.x64_scoped rationale): outside it the
        # stack/asarray would silently truncate the high lanes to u32.
        with enable_x64(True):
            packed = pack_key_lanes(cols)
            assert len(packed) == (k + 1) // 2
            # roundtrip
            back = np.asarray(jnp.stack(unpack_key_lanes(packed, k), axis=1))
            packed_np = [np.asarray(p) for p in packed]
        assert np.array_equal(back, cols_np.T)
        # order: argsort by packed columns == lexsort by original lanes
        order_packed = np.lexsort(tuple(reversed(packed_np)))
        order_lanes = np.lexsort(tuple(reversed(cols_np)))
        assert np.array_equal(cols_np.T[order_packed],
                              cols_np.T[order_lanes])
        # PAD rows sort last under the packed order
        assert set(order_packed[-16:]) == set(pad_rows)


# ── grouping is by the word's bytes: hash collisions stay apart ────────


def _fnv1a(w: str) -> int:
    h = 0x811C9DC5
    for ch in w.encode():
        h = ((h ^ ch) * 0x01000193) & 0xFFFFFFFF
    return h


def _colliding_words(mask: int, count: int = 2):
    """Distinct lowercase words whose fnv1a hashes share their low bits
    and, modulo 10, their reduce partition."""
    seen: dict = {}
    import itertools

    for tup in itertools.product(string.ascii_lowercase, repeat=3):
        w = "".join(tup)
        h = _fnv1a(w)
        b = (h & mask, (h & 0x7FFFFFFF) % 10)
        seen.setdefault(b, []).append(w)
        if len(seen[b]) >= count:
            return seen[b][:count]
    raise AssertionError("no collision found")


def _collision_text(case: str) -> str:
    if case == "random":
        rng = random.Random(11)
        words = ["".join(rng.choices(string.ascii_lowercase,
                                     k=rng.randint(1, 12)))
                 for _ in range(400)]
        return " ".join(rng.choice(words) for _ in range(5000))
    w1, w2 = _colliding_words(1023)
    if case == "pair":
        return (f"{w1} {w2} " * 150 + f"{w1} filler words here").ljust(3000)
    # 600 tokens of the pair, 8 colliding words among them
    more = " ".join(_colliding_words(255, 8))
    return f"{w1} {w2} " * 300 + more


@pytest.mark.parametrize("case", ["pair", "many", "random"])
def test_words_that_collide_in_fnv_stay_distinct_rows(case):
    """Two words whose FNV-1a hashes share their low ten bits and their
    reduce partition, 600 tokens of such a pair beside eight words that
    share eight bits, and random text: the program's rows are the host
    ``Counter``'s, one row a word, and ``fnv_u`` puts each row in the
    partition ``mr.worker.ihash`` gives its word."""
    import numpy as np

    from dsi_tpu.ops.wordcount import (_pad_pow2, count_words_kernel,
                                       decode_packed)

    text = _collision_text(case)
    want = oracle_counts(text)
    (packed_u, len_u, cnt_u, fnv_u, n_unique, max_len, has_high,
     token_overflow) = count_words_kernel(
        _pad_pow2(text.encode()), max_word_len=16, u_cap=1024, t_cap_frac=4)
    assert not bool(has_high) and not bool(token_overflow)
    nu = int(n_unique)
    words = decode_packed(np.asarray(packed_u), np.asarray(len_u), nu)
    assert len(set(words)) == nu == len(want)
    assert dict(zip(words, np.asarray(cnt_u)[:nu].tolist())) == dict(want)
    parts = ((np.asarray(fnv_u)[:nu] & 0x7FFFFFFF) % 10).tolist()
    assert parts == [ihash(w) % 10 for w in words]
    if case != "random":
        w1, w2 = _colliding_words(1023)
        assert parts[words.index(w1)] == parts[words.index(w2)]
    check(text)


# ── compaction: one int32 helper, no scatter ───────────────────────────


def _mask(kind: str, m: int):
    import numpy as np

    if kind == "empty":
        return np.zeros(m, bool)
    if kind == "full":
        return np.ones(m, bool)
    if kind == "alternating":
        return np.arange(m) % 2 == 1
    return np.random.default_rng(m).random(m) < 0.3


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("fill", ["last", "zero"])
@pytest.mark.parametrize("size_is", ["below", "equal", "above"])
@pytest.mark.parametrize("kind", ["empty", "full", "alternating", "random"])
def test_compact_positions_equals_flatnonzero(kind, size_is, fill, x64):
    """``compact_positions`` against ``np.flatnonzero``: the first ``size``
    set positions, then the fill; ``m`` no power of two; int32 inside and
    outside the x64 scope its callers run in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dsi_tpu.ops.wordcount import compact_positions
    from dsi_tpu.utils.jaxcompat import enable_x64

    m = 1000
    mask = _mask(kind, m)
    hits = np.flatnonzero(mask)
    size = {"below": max(1, len(hits) - 7), "equal": max(1, len(hits)),
            "above": len(hits) + 5}[size_is]
    fill_value = m - 1 if fill == "last" else 0
    want = np.full(size, fill_value)
    want[:min(size, len(hits))] = hits[:size]
    with enable_x64(x64):
        got = jax.jit(compact_positions, static_argnums=(1, 2))(
            jnp.asarray(mask), size, fill_value)
        assert got.dtype == jnp.int32 and got.shape == (size,)
        np.testing.assert_array_equal(np.asarray(got), want)
        ref = jnp.nonzero(jnp.asarray(mask), size=size,
                          fill_value=fill_value)[0]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _scatters(fn, *args):
    """Names of the scatter primitives in ``fn``'s jaxpr, with repeats,
    traced under the x64 scope the word-count programs run in."""
    import jax

    from dsi_tpu.utils.jaxcompat import enable_x64

    def names(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    with enable_x64(True):
        return sorted(p for p in names(jax.make_jaxpr(fn)(*args).jaxpr)
                      if "scatter" in p)


def test_compact_positions_jaxpr_holds_no_scatter():
    """The mechanism, pinned where no device trace is at hand (after
    ``test_grep_step_jaxpr_holds_no_scatter``): the compaction is a sort,
    where ``jnp.nonzero(size=)`` lowers a 64-bit ``scatter-add``."""
    import jax.numpy as jnp

    from dsi_tpu.ops.wordcount import compact_positions

    mask = jnp.arange(1000) % 3 == 0
    assert _scatters(lambda x: compact_positions(x, 300, 999), mask) == []
    assert _scatters(
        lambda x: jnp.nonzero(x, size=300, fill_value=999)[0], mask) != []


def test_word_count_program_jaxpr_holds_no_scatter():
    """The word-count program scatters nowhere: ``group_sorted``'s totals
    are a prefix sum read at the run starts."""
    import functools

    import jax.numpy as jnp

    from dsi_tpu.ops.wordcount import tokenize_group_core

    fn = functools.partial(tokenize_group_core, u_cap=64)
    assert _scatters(fn, jnp.zeros(256, jnp.uint8)) == []


@pytest.mark.parametrize("program, n_dev, more, allowed", [
    ("corpus", 1, {}, []),
    # shuffle_rows' send-buffer placement, on one device as on four
    ("stream", 1, {}, ["scatter"]),
    ("stream", 4, {}, ["scatter"]),
    ("tfidf", 1, {}, ["scatter"]),
    ("idx", 1, {}, ["scatter"]),
    ("idx", 1, {"pack_docs": True}, ["scatter"]),
])
def test_whole_programs_scatter_only_where_named(program, n_dev, more,
                                                 allowed):
    """The programs the cells run, whole: the map scatters nowhere and
    adds nowhere, and the one scatter of a step or a wave is the
    placement ``test_shuffle_rows_jaxpr_holds_one_placement_scatter``
    pins."""
    import functools

    from tests.harness import word_count_program

    _, fn, args, static = word_count_program(program, n_dev, size=256,
                                             u_cap=64, **more)
    assert _scatters(functools.partial(fn, **static), *args) == allowed


@pytest.mark.parametrize("mesh_fold", [False, True])
def test_fold_programs_jaxpr_hold_no_scatter_add(mesh_fold):
    """The device table's fold programs over a one-device mesh: no
    ``scatter-add`` (``group_sorted``'s totals, the exchange's block
    starts).  The mesh fold keeps the exchange's one placement
    ``scatter``; the plain fold has no scatter at all."""
    import functools

    from dsi_tpu.device import table
    from dsi_tpu.parallel.shuffle import default_mesh

    mesh = default_mesh(1)
    cap, kk, rows = 64, 4, 32
    args = (*table._table_structs(1, cap, kk),
            *table._step_structs(1, rows, kk))
    if mesh_fold:
        fn = functools.partial(table._mesh_fold_impl, mesh=mesh, n_shards=1)
        args += (table._apply_struct(1),)
    else:
        fn = functools.partial(table._fold_impl, mesh=mesh)
    assert _scatters(fn, *args) == (["scatter"] if mesh_fold else [])


def test_shuffle_rows_jaxpr_holds_one_placement_scatter():
    """``shuffle_rows`` scatters once, the send buffer's placement
    (``sendbuf.at[flat].set``), and adds nowhere: the block starts are
    compare-and-sum reductions."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dsi_tpu.parallel.shuffle import AXIS, default_mesh, shuffle_rows
    from dsi_tpu.utils.jaxcompat import shard_map

    fn = shard_map(
        functools.partial(shuffle_rows, n_dev=1, u_cap=16, k=2),
        mesh=default_mesh(1), in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=P(AXIS, None))
    assert _scatters(fn, jnp.zeros((16, 5), jnp.uint32),
                     jnp.zeros((16,), jnp.int32)) == ["scatter"]


# ── group_sorted's totals: a prefix sum read at the run starts ─────────


def _sorted_runs(case: str, t: int, out_cap: int):
    """Run lengths of the valid rows (they sum to at most ``t``)."""
    import numpy as np

    rng = np.random.default_rng(len(case))
    if case == "no_valid":
        return []
    if case == "one_run":
        return [t]
    if case == "singletons":
        return [1] * t
    if case == "pads_after_first":
        return [1]
    n = out_cap if case == "fits_exactly" else out_cap + 9
    return rng.integers(1, 4, n).tolist()


_TOTALS_CASES = [
    (dtype, case, x64)
    for dtype in ("int32", "uint32", "uint64")
    for case in ("no_valid", "one_run", "singletons", "fits_exactly",
                 "overflows", "pads_after_first")
    for x64 in (False, True)
    if x64 or dtype != "uint64"]  # 64-bit arrays exist under the scope only


@pytest.mark.parametrize("dtype,case,x64", _TOTALS_CASES)
def test_group_sorted_totals_equal_segment_sum(dtype, case, x64):
    """``group_sorted``'s totals against the ``jax.ops.segment_sum`` they
    replace and against numpy.  The counts are large: 32-bit running sums
    pass 2^31 many times while a short run's total fits, 64-bit ones (near
    2^63) wrap at every other row, so the differences of the modular
    prefix sum must still be exact.  With more runs than ``out_cap`` the
    first ``out_cap`` come back whole."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dsi_tpu.ops.wordcount import group_sorted
    from dsi_tpu.utils.jaxcompat import enable_x64

    t = 128 if case != "singletons" else 40
    out_cap = 40
    runs = _sorted_runs(case, t, out_cap)
    n_valid = sum(runs)
    assert n_valid <= t
    rng = np.random.default_rng(7)
    rid = np.repeat(np.arange(len(runs)), runs)
    lanes = np.full((t, 2), 0xFFFFFFFF, np.uint32)
    lanes[:n_valid, 0] = rid >> 2
    lanes[:n_valid, 1] = (rid & 3) * 1000
    if dtype == "uint64":
        c = (np.uint64(1 << 63) - rng.integers(1, 1 << 40, t).astype(
            np.uint64))
    else:
        c = rng.integers(1 << 24, 1 << 27, t).astype(dtype)
    c[n_valid:] = 0
    starts = np.concatenate([[0], np.cumsum(runs)[:-1]]).astype(int)
    want = np.zeros(out_cap, dtype)
    k = min(len(runs), out_cap)
    with np.errstate(over="ignore"):
        if runs:
            want[:k] = np.add.reduceat(c[:n_valid], starts)[:k]

    with enable_x64(x64):
        counts = jnp.asarray(c)
        cols = (jnp.asarray(lanes[:, 0]), jnp.asarray(lanes[:, 1]))
        keys, totals, upos, ovalid, n_unique = jax.jit(
            group_sorted, static_argnums=2)(cols, counts, out_cap)
        assert totals.dtype == counts.dtype and totals.shape == (out_cap,)
        assert int(n_unique) == len(runs)
        np.testing.assert_array_equal(np.asarray(totals), want)
        np.testing.assert_array_equal(
            np.asarray(ovalid), np.arange(out_cap) < len(runs))
        np.testing.assert_array_equal(np.asarray(upos)[:k], starts[:k])
        assert (np.asarray(upos)[k:] == t - 1).all()
        uid = np.full(t, out_cap, np.int32)
        uid[:n_valid] = np.minimum(rid, out_cap)
        ref = jax.ops.segment_sum(
            counts, jnp.asarray(uid),
            num_segments=out_cap + 1, indices_are_sorted=True)[:out_cap]
        np.testing.assert_array_equal(np.asarray(totals), np.asarray(ref))


@pytest.mark.parametrize("dtype", ["int32", "uint32", "int64", "uint64"])
def test_running_sum_equals_numpy_cumsum(dtype):
    """``running_sum`` against ``np.cumsum`` in the same dtype (wrapping):
    the 64-bit form's three 32-bit scans carry from the low halves into
    the high ones at every wrap, and a zero addend carries nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dsi_tpu.ops.wordcount import running_sum
    from dsi_tpu.utils.jaxcompat import enable_x64

    rng = np.random.default_rng(3)
    info = np.iinfo(dtype)
    x = rng.integers(info.min, info.max, 1000, dtype=dtype, endpoint=True)
    x[rng.random(1000) < 0.3] = 0
    x[500:520] = info.max  # low halves all ones: a carry at every step
    with np.errstate(over="ignore"):
        want = np.cumsum(x, dtype=dtype)
    with enable_x64(True):
        got = jax.jit(running_sum)(jnp.asarray(x))
        assert got.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("case", ["empty_destination", "one_takes_all",
                                  "parked_only"])
def test_shuffle_rows_blocks_equal_numpy_routing(case, n_dev):
    """``shuffle_rows`` against a numpy routing: receiver ``r`` gets, per
    source in device order, that source's rows bound for ``r`` in their
    order, then pad rows to ``u_cap``; parked rows (``dest == n_dev``)
    leave nowhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from dsi_tpu.parallel.shuffle import AXIS, default_mesh, shuffle_rows
    from dsi_tpu.utils.jaxcompat import shard_map

    u_cap, k, p = 24, 2, 3
    rng = np.random.default_rng(n_dev)
    rows = rng.integers(0, 1 << 30, (n_dev, u_cap, k + p)).astype(np.uint32)
    if case == "parked_only":
        dest = np.full((n_dev, u_cap), n_dev)
    elif case == "one_takes_all":
        dest = np.zeros((n_dev, u_cap), int)
    else:  # the last destination gets nothing; a fifth of the rows park
        dest = rng.integers(0, max(n_dev - 1, 1), (n_dev, u_cap))
        dest[dest == n_dev - 1] = n_dev  # one device: its only destination
        dest[rng.random((n_dev, u_cap)) < 0.2] = n_dev
    pad_row = np.array([0xFFFFFFFF] * k + [0] * p, np.uint32)
    want = np.broadcast_to(pad_row, (n_dev, n_dev, u_cap, k + p)).copy()
    for r in range(n_dev):
        for s in range(n_dev):
            mine = rows[s][dest[s] == r]
            want[r, s, :len(mine)] = mine

    def body(rows, dest):
        return shuffle_rows(rows[0], dest[0], n_dev=n_dev, u_cap=u_cap,
                            k=k)[None]

    fn = jax.jit(shard_map(
        body, mesh=default_mesh(n_dev),
        in_specs=(P(AXIS, None, None), P(AXIS, None)),
        out_specs=P(AXIS, None, None)))
    got = fn(jnp.asarray(rows), jnp.asarray(dest.astype(np.int32)))
    np.testing.assert_array_equal(
        np.asarray(got), want.reshape(n_dev, n_dev * u_cap, k + p))


_N = 256  # one chunk; t_cap = _N // 4 + 1 = 65 tokens


def _boundary_chunk(case: str) -> bytes:
    t_cap = _N // 4 + 1
    body = {
        "t_cap_tokens": b"ab " * (t_cap - 1) + b"zz",
        "t_cap_plus_one": b"ab " * t_cap + b"zz",
        "no_tokens": b"12 34 !? \n" * 20,
        # every start is an end
        "single_letters": b" ".join(bytes([97 + i % 26]) for i in range(60)),
        # the chunk's last byte is a letter: no byte follows the last end
        "ends_in_letter": (b"tail words here " * 16)[:_N - 3] + b" xy",
    }[case]
    return body.ljust(_N, b"\0")


@pytest.mark.parametrize("case", ["t_cap_tokens", "t_cap_plus_one",
                                  "no_tokens", "single_letters",
                                  "ends_in_letter"])
def test_program_at_its_token_boundaries(case):
    """The program as a whole where its buffers end: exactly ``t_cap``
    tokens, one more (``token_overflow``, and the first ``t_cap`` tokens
    counted), none, single letters only, a letter in the last byte; each
    equal to the host reference."""
    import re

    import numpy as np

    from dsi_tpu.ops.wordcount import count_words_kernel, decode_packed

    chunk = _boundary_chunk(case)
    assert len(chunk) == _N
    t_cap = _N // 4 + 1
    tokens = re.findall(rb"[A-Za-z]+", chunk)
    (packed_u, len_u, cnt_u, fnv_u, n_unique, max_len, has_high,
     token_overflow) = count_words_kernel(
        np.frombuffer(chunk, np.uint8), max_word_len=16, u_cap=128,
        t_cap_frac=4)
    assert bool(token_overflow) == (len(tokens) > t_cap) \
        == (case == "t_cap_plus_one")
    assert not bool(has_high)
    want = collections.Counter(t.decode() for t in tokens[:t_cap])
    nu = int(n_unique)
    words = decode_packed(np.asarray(packed_u), np.asarray(len_u), nu)
    counts = np.asarray(cnt_u)[:nu].tolist()
    assert dict(zip(words, counts)) == dict(want) and len(words) == len(want)
    assert int(max_len) == max((len(t) for t in tokens[:t_cap]), default=0)
    hashes = (np.asarray(fnv_u)[:nu] & 0x7FFFFFFF).tolist()
    assert hashes == [ihash(w) for w in words]
    if not bool(token_overflow):
        check(chunk.rstrip(b"\0").decode())


# ── the lane movement: chunk positions -> token buffer rows (PR 46) ────


def _lanes_chunk(case: str) -> bytes:
    """The five boundary chunks and three more: a word of exactly
    ``max_word_len`` bytes, longer ones (17 and 40), and a pack of
    documents with an empty one and a separator as the last byte."""
    from dsi_tpu.ops.wordcount import DOC_SEP

    if case == "word_of_max_len":
        return (b"x " + b"q" * 16 + b" ab " + b"Z" * 16).ljust(_N, b"\0")
    if case == "word_longer":
        return (b"k" * 17 + b"," + b"m" * 40 + b" end").ljust(_N, b" ")
    if case == "doc_sep_pack":
        sep = bytes([DOC_SEP])
        docs = [b"one two one", b"", b"three", b"four five " * 20]
        return sep.join(docs)[:_N - 1].ljust(_N - 1, b"x") + sep
    return _boundary_chunk(case)


def _gathers_reference(chunk: bytes, k: int, t_cap: int, doc_sep):
    """The four-gather form the program held until PR 46, in numpy:
    ``b32[start + 4j]`` masked by the token's length, PAD rows behind."""
    import numpy as np

    a = np.frombuffer(chunk, np.uint8)
    n = len(a)
    letter = (((a | 32) >= 97) & ((a | 32) <= 122))
    starts = np.flatnonzero(letter & ~np.concatenate([[False], letter[:-1]]))
    ends = np.flatnonzero(letter & ~np.concatenate([letter[1:], [False]]))
    n_tokens = len(starts)
    starts, ends = starts[:t_cap], ends[:t_cap]
    nt = len(starts)
    z = np.concatenate([a, np.zeros(3, np.uint8)]).astype(np.uint32)
    b32 = (z[:n] << 24) | (z[1:n + 1] << 16) | (z[2:n + 2] << 8) | z[3:n + 3]
    lengths = np.zeros(t_cap, np.int32)
    lengths[:nt] = ends - starts + 1
    cols = []
    for j in range(k):
        keep = np.clip(lengths[:nt] - 4 * j, 0, 4)
        mask = (np.uint64(0xFFFFFFFF) << (8 * (4 - keep)).astype(np.uint64)
                ).astype(np.uint32)
        col = np.full(t_cap, 0xFFFFFFFF, np.uint32)
        col[:nt] = b32[np.minimum(starts + 4 * j, n - 1)] & mask
        cols.append(col)
    doc = None
    if doc_sep is not None:
        doc = np.full(t_cap, 0xFFFFFFFF, np.uint32)
        doc[:nt] = np.cumsum(a == doc_sep)[starts]
    pos = np.full(t_cap, n - 1, np.int32)
    pos[:nt] = starts
    return cols, lengths, doc, pos, n_tokens


_LANES_CASES = ["t_cap_tokens", "t_cap_plus_one", "no_tokens",
                "single_letters", "ends_in_letter", "word_of_max_len",
                "word_longer", "doc_sep_pack"]


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("frac", [4, 2])
@pytest.mark.parametrize("case", _LANES_CASES)
def test_token_lanes_equal_the_gathers_they_replace(case, frac, x64):
    """``token_lanes`` row for row against numpy's rendering of the
    gathers: the key lanes masked by the length, the lengths, the document
    lane, PAD rows included; with more tokens than ``t_cap`` the first
    ``t_cap``."""
    import jax
    import numpy as np

    from dsi_tpu.ops.wordcount import DOC_SEP, token_lanes
    from dsi_tpu.utils.jaxcompat import enable_x64

    chunk = _lanes_chunk(case)
    assert len(chunk) == _N
    doc_sep = DOC_SEP if case == "doc_sep_pack" else None
    k, t_cap = 4, _N // frac + 1
    cols, lengths, doc, pos, n_tokens = _gathers_reference(
        chunk, k, t_cap, doc_sep)
    if case == "t_cap_plus_one":
        assert n_tokens == _N // 4 + 2  # over the buffer at frac 4 alone
    with enable_x64(x64):
        got = jax.jit(token_lanes, static_argnames=(
            "max_word_len", "t_cap_frac", "doc_sep", "with_pos"))(
            np.frombuffer(chunk, np.uint8), max_word_len=4 * k,
            t_cap_frac=frac, doc_sep=doc_sep, with_pos=True)
    got_cols, got_len, got_n, got_doc, got_pos = got
    assert int(got_n) == n_tokens
    assert len(got_cols) == k
    for j in range(k):
        assert got_cols[j].dtype == np.uint32
        np.testing.assert_array_equal(np.asarray(got_cols[j]), cols[j])
    assert got_len.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got_len), lengths)
    nt = min(n_tokens, t_cap)
    np.testing.assert_array_equal(np.asarray(got_pos)[:nt], pos[:nt])
    if doc_sep is None:
        assert got_doc is None
    else:
        assert got_doc.dtype == np.uint32
        np.testing.assert_array_equal(np.asarray(got_doc), doc)


def _chunk_gathers(fn, n, *args):
    """How many ``gather``s of ``fn``'s jaxpr read an operand as long as
    the chunk or as its pairs of positions (``n`` or ``n // 2``)."""
    import jax

    from dsi_tpu.utils.jaxcompat import enable_x64

    def count(jaxpr):
        hits = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather" and any(
                    d in (n, n // 2) for d in eqn.invars[0].aval.shape):
                hits += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                hits += count(sub)
        return hits

    with enable_x64(True):
        return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("program, n_dev, more", [
    ("wc", 1, {}),
    ("corpus", 1, {}),
    ("stream", 1, {}),
    ("stream", 4, {}),
    ("idx", 1, {"pack_docs": True}),
])
def test_word_count_programs_gather_nowhere_from_the_chunk(program, n_dev,
                                                           more):
    """The mechanism of PR 46, pinned where no device trace is at hand:
    no gather reads an array of the chunk's length (the lanes, the
    lengths and the document lane move to the token buffer by shifted
    selects)."""
    import functools

    from tests.harness import word_count_program

    n = 1024  # n, n // 2, t_cap 257, u_cap 64 and 4 * 64: all distinct
    _, fn, args, static = word_count_program(program, n_dev, size=n,
                                             u_cap=64, **more)
    assert _chunk_gathers(functools.partial(fn, **static), n, *args) == 0


@pytest.mark.parametrize("doc_sep, extra", [(None, 0), (0x1E, 1)])
def test_the_gather_count_sees_the_form_it_replaced(doc_sep, extra):
    """The control of the test above: the form the program held until
    PR 46, kept in ``scripts/pack_micro.py`` as the micro-benchmark's
    reference, holds one chunk-long gather a lane and one more for the
    documents."""
    import importlib.util
    import os

    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        "pack_micro", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "scripts", "pack_micro.py"))
    micro = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(micro)
    n = 1024
    assert _chunk_gathers(
        lambda c: micro.gathers(c, n // 4 + 1, doc_sep), n,
        jnp.zeros(n, jnp.uint8)) == micro.K + extra
