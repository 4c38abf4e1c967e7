"""``ops/fieldsum.py``: the aggregation's map against plain Python, row by
row, over rows of every field length; the values 64 bits wide through the
group; every kind of bad row flagged at its place; and what the map must
not hold (a scatter, a 64-bit operation).  With it the word count's own
step: with the map parameter at its default it lowers to the text the
step had before there was one.
"""

import functools
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_agg  # noqa: E402

from dsi_tpu.ops import fieldsum, wordcount  # noqa: E402
from dsi_tpu.ops.fieldsum import FieldSum  # noqa: E402
from dsi_tpu.parallel import shuffle  # noqa: E402
from dsi_tpu.parallel.merge import PackedCounts  # noqa: E402
from dsi_tpu.utils.jaxcompat import enable_x64, shard_map  # noqa: E402

N = 8192
_KEY = bytes(b for b in range(0x20, 0x7F) if b != 0x7C)
_OTHER = bytes(b for b in range(1, 256) if b not in (0x0A, 0x7C))


def _value(rng) -> bytes:
    whole = str(rng.integers(0, 10 ** rng.integers(1, 4))).zfill(
        rng.integers(1, 4))[-3:]
    digits = int(rng.integers(0, 7))
    return (whole + ("." + "".join(str(d) for d in rng.integers(
        0, 10, digits)) if digits else "")).encode()


def _row(rng, key_len=None) -> bytes:
    pick = lambda alphabet, n: bytes(  # noqa: E731
        alphabet[i] for i in rng.integers(0, len(alphabet), n))
    fields = [pick(_KEY, key_len or rng.integers(1, 17)),
              pick(_OTHER, rng.integers(0, 20)),
              pick(_OTHER, rng.integers(0, 12)), _value(rng)]
    fields += [pick(_OTHER, rng.integers(0, 9))
               for _ in range(rng.integers(0, 6))]
    return b"|".join(fields)


def _chunk(rows, newline_after_last=True, n=N) -> np.ndarray:
    data = b"\n".join(rows) + (b"\n" if newline_after_last else b"")
    assert len(data) <= n
    out = np.zeros(n, np.uint8)
    out[:len(data)] = np.frombuffer(data, np.uint8)
    return out


def _rows_fn(spec, frac):
    @jax.jit
    def fn(chunk):
        return fieldsum.field_rows(chunk, spec=spec, max_word_len=16,
                                   t_cap_frac=frac)
    return fn


def _key_bytes(cols, i, length):
    return b"".join(int(c[i]).to_bytes(4, "big") for c in cols)[:length]


@pytest.mark.parametrize("prefix", [0, 7])
@pytest.mark.parametrize("seed,newline", [(0, True), (1, True), (2, False),
                                          (3, True), (4, False)])
def test_rows_against_plain_python(seed, newline, prefix):
    rng = np.random.default_rng(seed)
    rows = [_row(rng, key_len=k) for k in range(1, 17)]
    while sum(map(len, rows)) + len(rows) < N - 200:
        rows.append(_row(rng))
    cols, lens, values, n_rows, first_bad = _rows_fn(
        FieldSum(prefix=prefix), 4)(_chunk(rows, newline))
    assert int(n_rows) == len(rows) and int(first_bad) == N // 4 + 1
    for i, row in enumerate(rows):
        f = row.split(b"|")
        key = f[0][:prefix] if prefix else f[0]
        assert int(lens[i]) == len(key)
        assert _key_bytes(cols, i, 16) == key.ljust(16, b"\0")
        assert int(values[i]) == reference_agg.units(f[3]), f[3]
    assert not np.asarray(lens[len(rows):]).any()
    assert (np.asarray(cols[0][len(rows):]) == 0xFFFFFFFF).all()


def test_a_chunk_filled_to_its_last_byte():
    rng = np.random.default_rng(9)
    rows = [_row(rng) for _ in range(40)]
    size = sum(map(len, rows)) + len(rows) - 1
    pad = 4 - size % 4 if size % 4 else 0
    rows[-1] += b"|" + b"x" * (pad - 1) if pad else b""
    data = b"\n".join(rows)   # no newline, no zero tail
    chunk = np.frombuffer(data, np.uint8)
    out = jax.jit(functools.partial(
        fieldsum.field_rows, spec=FieldSum(), max_word_len=16,
        t_cap_frac=4))(chunk)
    assert int(out[3]) == len(rows) and int(out[4]) == len(chunk) // 4 + 1
    assert int(out[2][len(rows) - 1]) == reference_agg.units(
        rows[-1].split(b"|")[3])


BAD_ROWS = {
    "three fields": b"key|a|b",
    "one field": b"key",
    "empty row": b"",
    "empty key": b"|a|b|1.5",
    "key of 17 bytes": b"k" * 17 + b"|a|b|1.5",
    "key with a control byte": b"k\x1fy|a|b|1.5",
    "key with DEL": b"k\x7fy|a|b|1.5",
    "key with a high byte": b"k\x80y|a|b|1.5",
    "key with a NUL": b"k\x00y|a|b|1.5",
    "empty value": b"key|a|b||c",
    "four integer digits": b"key|a|b|1234",
    "no fraction digit": b"key|a|b|12.|c",
    "no integer digit": b"key|a|b|.5",
    "seven fraction digits": b"key|a|b|1.1234567",
    "a letter": b"key|a|b|1a",
    "two points": b"key|a|b|1.2.3",
    "a space": b"key|a|b| 1",
    "a sign": b"key|a|b|-1",
    "a carriage return": b"key|a|b|1.5\r",
    "an exponent": b"key|a|b|1e3",
}


@pytest.mark.parametrize("kind", sorted(BAD_ROWS))
def test_a_bad_row_is_flagged_at_its_place(kind):
    rng = np.random.default_rng(5)
    rows = [_row(rng) for _ in range(30)]
    rows[17] = BAD_ROWS[kind]
    *_, n_rows, first_bad = _rows_fn(FieldSum(), 4)(_chunk(rows))
    assert (int(n_rows), int(first_bad)) == (30, 17)
    with pytest.raises(ValueError):
        reference_agg.sums_of_rows(rows)


def test_sums_pass_32_bits_inside_a_step():
    hot = [b"hot|u|d|999.999999|x"] * 300     # 3e11 units, past 2^32
    cold = [b"cold%d|u|d|0.000001" % i for i in range(50)]
    rows = hot[:150] + cold + hot[150:]
    out = jax.jit(functools.partial(
        fieldsum.fieldsum_group_core, spec=FieldSum(), u_cap=64,
        t_cap_frac=4))(_chunk(rows))
    packed, lens, sums, fnv, n_unique, max_len, bad, over, n_rows, _ = out
    assert (int(n_unique), bool(bad), bool(over)) == (51, False, False)
    assert int(n_rows) == 350 and int(max_len) == 6
    got = {_key_bytes(np.asarray(packed).T, i, int(lens[i])):
           int(sums[i, 0]) | int(sums[i, 1]) << 32 for i in range(51)}
    assert got[b"hot"] == 300 * 999_999_999 > 1 << 32
    assert got == reference_agg.sums_of_rows(rows)
    assert [int(h) & 0x7FFFFFFF for h in fnv[:51]] == [
        reference_agg.ihash(_key_bytes(np.asarray(packed).T, i,
                                       int(lens[i]))) for i in range(51)]


def test_more_rows_than_the_buffer_says_so():
    rows = [b"k%d|||1" % i for i in range(400)]
    out = jax.jit(functools.partial(
        fieldsum.fieldsum_group_core, spec=FieldSum(), u_cap=512,
        t_cap_frac=64))(_chunk(rows))
    assert bool(out[7]) and int(out[8]) == 400   # 129 rows fit
    out = jax.jit(functools.partial(
        fieldsum.fieldsum_group_core, spec=FieldSum(), u_cap=512,
        t_cap_frac=4))(_chunk(rows))
    assert not bool(out[7]) and int(out[4]) == 400


@pytest.mark.parametrize("with_high", [False, True])
def test_wide_totals_against_uint64(with_high):
    rng = np.random.default_rng(3)
    keys = np.sort(rng.integers(0, 40, 1000).astype(np.uint32))
    keys[900:] = 0xFFFFFFFF  # pad rows
    lo = rng.integers(0, 1 << 32, 1000, dtype=np.uint64).astype(np.uint32)
    hi = rng.integers(0, 1 << 20, 1000, dtype=np.uint64).astype(np.uint32)
    counts = (lo, hi) if with_high else (lo, None)
    _, totals, _, ovalid, n_unique = jax.jit(
        lambda k, c: wordcount.group_sorted((k,), c, 64))(keys, counts)
    whole = lo.astype(np.uint64) | (
        hi.astype(np.uint64) << np.uint64(32) if with_high else 0)
    want = [int(whole[:900][keys[:900] == k].sum()) & (1 << 64) - 1
            for k in np.unique(keys[:900])]
    got = [int(t[0]) | int(t[1]) << 32 for t in np.asarray(totals)]
    assert got[:int(n_unique)] == want and not any(got[int(n_unique):])


def _dtypes_and_primitives(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen[0].add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            # a Python literal is a weakly typed scalar until the
            # operation it enters gives it its type: a constant, no op
            literal = getattr(v.aval, "weak_type", False) \
                and v.aval.shape == ()
            if hasattr(v.aval, "dtype") and not literal:
                seen[1].add(str(v.aval.dtype))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dtypes_and_primitives(inner, seen)


def test_the_map_holds_no_scatter_and_no_64_bit_operation():
    seen = (set(), set())
    with enable_x64(True):  # as the step traces it
        jaxpr = jax.make_jaxpr(functools.partial(
            fieldsum.fieldsum_group_core, spec=FieldSum(prefix=7),
            u_cap=1 << 12, t_cap_frac=64))(
            jax.ShapeDtypeStruct((1 << 16,), jnp.uint8))
    _dtypes_and_primitives(jaxpr.jaxpr, seen)
    assert "sort" in seen[0] and "gather" in seen[0]
    assert not [p for p in seen[0] if "scatter" in p]
    assert not [d for d in seen[1] if "64" in d], seen[1]


# ── the word count's step, as it was before it had a map parameter ──


def _device_step_before(chunk, *, n_dev, n_reduce, max_word_len, u_cap,
                        t_cap_frac):
    """``parallel/shuffle._device_step`` of the commit before PR 49."""
    k = max_word_len // 4
    chunk = chunk.reshape(-1)
    packed_u, len_u, cnt_u, part, dest, (
        n_unique, max_len, has_high, token_overflow) = shuffle.map_prologue(
        chunk, n_dev=n_dev, n_reduce=n_reduce, max_word_len=max_word_len,
        u_cap=u_cap, t_cap_frac=t_cap_frac)
    with jax.named_scope("shuffle"):
        rows = jnp.concatenate(
            [packed_u, len_u[:, None].astype(jnp.uint32),
             cnt_u[:, None].astype(jnp.uint32), part[:, None]], axis=1)
    recv = shuffle.shuffle_rows(rows, dest, n_dev=n_dev, u_cap=u_cap, k=k)
    out_cap = n_dev * u_cap
    with jax.named_scope("reduce"):
        *scols, mlen, mcnt, mpart = wordcount.lex_sort(
            tuple(recv[:, j] for j in range(k)),
            (recv[:, k], recv[:, k + 1], recv[:, k + 2]))
        mkeys, tot, upos, ovalid, m_unique = wordcount.group_sorted(
            tuple(scols), mcnt.astype(jnp.int32), out_cap)
        with jax.named_scope("group"):
            mlen = mlen.astype(jnp.int32)
            out_keys = jnp.where(ovalid[:, None], mkeys[upos],
                                 jnp.uint32(0))
            out_len = jnp.where(ovalid, mlen[upos], 0)
            out_part = jnp.where(ovalid, mpart[upos], 0)
    scalars = jnp.stack([m_unique, n_unique, max_len,
                         has_high.astype(jnp.int32),
                         token_overflow.astype(jnp.int32)])
    return (out_keys[None], out_len[None], tot[None], out_part[None],
            scalars[None])


def _mapreduce_step_impl(chunks, *, n_dev, n_reduce, max_word_len, u_cap,
                         mesh, t_cap_frac=4):
    """Named as the program's, so that the module's name is the same."""
    body = functools.partial(_device_step_before, n_dev=n_dev,
                             n_reduce=n_reduce, max_word_len=max_word_len,
                             u_cap=u_cap, t_cap_frac=t_cap_frac)
    return shard_map(
        body, mesh=mesh, in_specs=P(shuffle.AXIS, None),
        out_specs=(P(shuffle.AXIS, None, None), P(shuffle.AXIS, None),
                   P(shuffle.AXIS, None), P(shuffle.AXIS, None),
                   P(shuffle.AXIS, None)))(chunks)


@pytest.mark.parametrize("n_dev,u_cap,frac", [(1, 1 << 10, 4),
                                              (1, 1 << 12, 2),
                                              (2, 1 << 10, 4)])
def test_the_word_count_step_lowers_to_the_text_it_had(n_dev, u_cap, frac):
    mesh = shuffle.default_mesh(n_dev)
    kw = dict(n_dev=n_dev, n_reduce=10, max_word_len=16, u_cap=u_cap,
              mesh=mesh, t_cap_frac=frac)
    chunks = jax.ShapeDtypeStruct((n_dev, 1 << 14), jnp.uint8)
    with enable_x64(True):
        before = jax.jit(_mapreduce_step_impl, static_argnames=tuple(
            kw)).lower(chunks, **kw).as_text()
        now = jax.jit(shuffle._mapreduce_step_impl,
                      static_argnames=shuffle._STEP_STATICS).lower(
            chunks, **kw).as_text()
        mapped = jax.jit(shuffle._mapreduce_step_impl,
                         static_argnames=shuffle._STEP_STATICS).lower(
            chunks, map=FieldSum(), **kw).as_text()
    assert now == before
    assert mapped != before


def test_the_pack_lowers_to_the_text_it_had_and_takes_two_lanes():
    sds = jax.ShapeDtypeStruct
    args = (sds((1, 256, 4), jnp.uint32), sds((1, 256), jnp.int32),
            sds((1, 256), jnp.int32), sds((1, 256), jnp.uint32))

    @functools.partial(jax.jit, static_argnames=("mp",))
    def _slice_pack(keys, lens, cnts, parts, *, mp):
        with jax.named_scope("pack"):
            return jnp.concatenate(
                [keys[:, :mp], lens[:, :mp, None].astype(jnp.uint32),
                 cnts[:, :mp, None].astype(jnp.uint32),
                 parts[:, :mp, None].astype(jnp.uint32)], axis=2)

    assert shuffle._slice_pack.lower(*args, mp=64).as_text() \
        == _slice_pack.lower(*args, mp=64).as_text()
    wide = shuffle._slice_pack(
        np.ones((1, 8, 4), np.uint32), np.ones((1, 8), np.int32),
        np.full((1, 8, 2), 7, np.uint32), np.ones((1, 8), np.uint32), mp=4)
    assert wide.shape == (1, 4, 8) and (np.asarray(wide)[0, :, 5:7] == 7).all()


# ── the host's half: two lanes in, decimals out ──


def _lanes(key: bytes) -> np.ndarray:
    return np.frombuffer(key.ljust(16, b"\0"), ">u4").astype(np.uint32)


def test_a_packed_step_of_two_lanes_and_the_rendered_decimals():
    totals = {b"10.0.0.1": (1 << 40) + 123_456, b"9.9.9.9": 7,
              b"a": 12_500_000}
    keys = sorted(totals)
    packed = np.zeros((1, 4, 8), np.uint32)
    for i, key in enumerate(keys):
        packed[0, i, :4] = _lanes(key)
        packed[0, i, 4] = len(key)
        packed[0, i, 5] = totals[key] & 0xFFFFFFFF
        packed[0, i, 6] = totals[key] >> 32
        packed[0, i, 7] = i % 2
    acc = PackedCounts(decimals=6)
    acc.add_packed_step(packed, [3], 4)
    acc.add_packed_step(packed, [3], 4)
    result = acc.finalize()
    assert result.cnts.tolist() == [2 * totals[k] for k in keys]
    want = [reference_agg.line(k, 2 * totals[k]) + "\n" for k in keys]
    assert result.render_partition(0).decode() == want[0] + want[2]
    assert result.render_partition(1).decode() == want[1]
    assert want[0] == "10.0.0.1 2199023.502464\n"
    assert want[1] == "9.9.9.9 0.000014\n"


def test_counts_render_as_they_did():
    acc = PackedCounts()
    acc.add(np.stack([_lanes(b"a"), _lanes(b"b")]), [1, 1], [5, 1234567],
            [0, 0])
    assert acc.finalize().render_partition(0) == b"a 5\nb 1234567\n"


def test_running_sum_pair_against_uint64():
    rng = np.random.default_rng(8)
    lo = rng.integers(0, 1 << 32, 5000, dtype=np.uint64)
    hi = rng.integers(0, 1 << 31, 5000, dtype=np.uint64)
    got_lo, got_hi = jax.jit(wordcount.running_sum_pair)(
        lo.astype(np.uint32), hi.astype(np.uint32))
    want = np.cumsum(lo | hi << np.uint64(32))
    got = np.asarray(got_lo).astype(np.uint64) | (
        np.asarray(got_hi).astype(np.uint64) << np.uint64(32))
    assert (got == want).all()
    only_lo = jax.jit(lambda x: wordcount.running_sum_pair(x, None))(
        lo.astype(np.uint32))
    assert (np.asarray(only_lo[0]).astype(np.uint64) | (np.asarray(
        only_lo[1]).astype(np.uint64) << np.uint64(32))
        == np.cumsum(lo)).all()


# ── the aggregation's map, as it was before the join's maps shared it ──


def _field_rows_before(chunk, *, spec, max_word_len, t_cap_frac):
    """``ops/fieldsum.field_rows`` of the commit before PR 55: one
    function, before its three scopes became functions of their own."""
    n = chunk.shape[0]
    k = max_word_len // 4
    t_cap = n // t_cap_frac + 1
    last_field = max(spec.key_field, spec.value_field)
    pos = jnp.arange(n, dtype=jnp.int32)

    with jax.named_scope("fields"):
        content = jnp.max(jnp.where(chunk != 0, pos, -1)) + 1
        is_end = (chunk == 10) | (pos >= content)
        is_start = (pos < content) & jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), is_end[:-1]])
        n_rows = jnp.sum(is_start, dtype=jnp.int32)
        # Every position's next terminator, and in the low bit whether it
        # ends the row (1) or only a field (0: a delimiter), so that one
        # gather tells both; position n ends whatever is open there (a
        # full chunk's last row).
        is_delim = chunk == jnp.uint8(spec.delim)
        nxt = jnp.concatenate([
            jax.lax.cummin(jnp.where(
                is_end | is_delim, 2 * pos + (~is_delim).astype(jnp.int32),
                jnp.int32(2 * n + 1)), reverse=True),
            jnp.full((1,), 2 * n + 1, jnp.int32)])
        valid = jnp.arange(t_cap, dtype=jnp.int32) < n_rows
        begin = fieldsum._row_starts(is_start, t_cap)
        fields_ok = valid
        starts, ends = [], []
        for f in range(last_field + 1):
            code = nxt[begin]
            starts.append(begin)
            ends.append(code >> 1)
            if f < last_field:  # a delimiter, not the row's end, behind it
                fields_ok &= (code & 1) == 0
                begin = jnp.minimum((code >> 1) + 1, n)

    words = fieldsum._words(chunk)

    with jax.named_scope("key_lanes"):
        at = starts[spec.key_field]
        field_len = ends[spec.key_field] - at
        lanes = [words[jnp.minimum(at + 4 * j, n)] for j in range(k)]
        printable = valid
        for p, b in enumerate(fieldsum._bytes_of(lanes, 4 * k)):
            printable &= (p >= field_len) | ((b >= 0x20) & (b <= 0x7E))
        key_ok = (field_len >= 1) & (field_len <= 4 * k) & printable
        key_lens = jnp.where(
            valid, jnp.minimum(field_len, spec.prefix) if spec.prefix
            else field_len, 0)
        key_cols = tuple(
            jnp.where(valid,
                      lanes[j] & fieldsum._byte_mask(jnp.clip(key_lens - 4 * j, 0, 4)),
                      jnp.uint32(fieldsum._PAD_KEY))
            for j in range(k))

    with jax.named_scope("decimal"):
        at = starts[spec.value_field]
        length = ends[spec.value_field] - at
        window = fieldsum._bytes_of(
            [words[jnp.minimum(at + 4 * j, n)] for j in range(3)],
            fieldsum._VALUE_BYTES)
        digits = [b - jnp.uint32(0x30) for b in window]
        # the dot, if any, stands at byte 1, 2 or 3; without one the
        # value is its integer digits, and the dot's place is its length
        dot = length
        for p in (3, 2, 1):
            dot = jnp.where((window[p] == 0x2E) & (p < length), p, dot)
        value = jnp.zeros((t_cap,), jnp.uint32)
        all_digits = valid
        for d in (1, 2, 3):
            total = jnp.zeros((t_cap,), jnp.uint32)
            for p in range(min(d + 1 + fieldsum.DECIMALS, fieldsum._VALUE_BYTES)):
                if p == d:
                    continue
                weight = 10 ** (fieldsum.DECIMALS + d - 1 - p if p < d
                                else fieldsum.DECIMALS - (p - d))
                total += jnp.where(p < length, digits[p] * jnp.uint32(weight),
                                   jnp.uint32(0))
            value = jnp.where(dot == d, total, value)
        for p in range(fieldsum._VALUE_BYTES):
            all_digits &= (p >= length) | (p == dot) | (digits[p] <= 9)
        fraction = length - dot - 1  # -1 without a dot
        value_ok = (all_digits & (dot >= 1) & (dot <= 3)
                    & (fraction != 0) & (fraction <= fieldsum.DECIMALS))
        values = jnp.where(valid, value, jnp.uint32(0))

    with jax.named_scope("fields"):
        bad = valid & ~(fields_ok & key_ok & value_ok)
        first_bad = jnp.min(jnp.where(
            bad, jnp.arange(t_cap, dtype=jnp.int32), jnp.int32(t_cap)))
    return key_cols, key_lens, values, n_rows, first_bad


@pytest.mark.parametrize("n,frac,prefix", [(1 << 14, 64, 0), (1 << 14, 4, 7),
                                           (1 << 12, 64, 0)])
def test_the_factored_field_rows_lowers_to_the_text_it_had(n, frac, prefix):
    chunk = jax.ShapeDtypeStruct((n,), jnp.uint8)
    kw = dict(spec=FieldSum(prefix=prefix), max_word_len=16, t_cap_frac=frac)

    def field_rows(c):  # named as the program's, whichever it traces
        return fn(c, **kw)

    texts = []
    for fn in (_field_rows_before, fieldsum.field_rows):
        texts.append(jax.jit(field_rows).lower(chunk).as_text())
    assert texts[0] == texts[1]


def test_group_sorted_takes_several_sums_a_key():
    rng = np.random.default_rng(4)
    keys = np.sort(rng.integers(0, 40, 3000).astype(np.uint32))
    a, b = (rng.integers(0, 1 << 32, 3000, dtype=np.uint64).astype(np.uint32)
            for _ in range(2))
    ones = np.ones(3000, np.uint32)
    _, totals, _, ovalid, n_unique = jax.jit(
        lambda k, x, y, z: wordcount.group_sorted(
            (k,), [(x, None), (y, None), (z, None)], 64))(keys, a, b, ones)
    totals = np.asarray(totals).astype(np.uint64)
    assert totals.shape == (64, 6) and int(n_unique) == len(set(keys))
    got = totals[:, 0::2] | (totals[:, 1::2] << np.uint64(32))
    for i, key in enumerate(sorted(set(keys.tolist()))):
        rows = keys == key
        assert got[i].tolist() == [int(a[rows].astype(np.uint64).sum()),
                                   int(b[rows].astype(np.uint64).sum()),
                                   int(rows.sum())]
    assert not got[int(n_unique):].any() and ovalid.sum() == n_unique
    # one pair in a tuple is what it was
    _, one, *_ = jax.jit(lambda k, x: wordcount.group_sorted(
        (k,), (x, None), 64))(keys, a)
    assert (np.asarray(one) == np.asarray(totals[:, :2])).all()


def test_a_packed_step_of_three_sums_and_the_rendered_mean():
    groups = {b"10.0.0.1": ((1 << 40) + 5, (1 << 33) + 1, 3),
              b"9.9.9.9": (7, 2, 3), b"a": (12_500_000, 4, 3)}
    keys = sorted(groups)
    packed = np.zeros((1, 4, 12), np.uint32)
    for i, key in enumerate(keys):
        packed[0, i, :4] = _lanes(key)
        packed[0, i, 4] = len(key)
        for j, value in enumerate(groups[key]):
            packed[0, i, 5 + 2 * j] = value & 0xFFFFFFFF
            packed[0, i, 6 + 2 * j] = value >> 32
        packed[0, i, 11] = i % 2
    acc = PackedCounts(decimals=6, compact_rows=4)
    for _ in range(3):  # through a window's merge and into the table
        acc.add_packed_step(packed, [3], 4)
    result = acc.finalize()
    assert result.cnts.tolist() == [[3 * v for v in groups[k]] for k in keys]
    assert result.parts.tolist() == [0, 1, 0]
    want = {k: f"{k.decode()} {3 * r // 10 ** 6}.{3 * r % 10 ** 6:06d} "
               f"{s * 10 ** 6 // n // 10 ** 6}.{s * 10 ** 6 // n % 10 ** 6:06d}\n"
            for k, (r, s, n) in groups.items()}
    assert result.render_partition(0).decode() == want[keys[0]] + want[keys[2]]
    assert result.render_partition(1).decode() == want[keys[1]]
    assert want[b"9.9.9.9"] == "9.9.9.9 0.000021 0.666666\n"
    assert result[ "a"] == ([37_500_000, 12, 9], 0)
