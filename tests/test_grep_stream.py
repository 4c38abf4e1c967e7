"""Streaming grep / indexer engines (parallel/grepstream.py) and the
on-device top-k/histogram service (device/topk.py).

Oracle discipline as everywhere else: every engine path — depth x
device_accumulate x short lines x forced top-k widen — must
agree BIT-FOR-BIT with the depth=1 host-merge path and with a
pure-Python oracle over the same bytes (including per-word posting
order for the indexer), so any divergence is an engine/service bug,
never a tolerance.
"""

import functools
import re

import pytest

jax = pytest.importorskip("jax")

import numpy as np

from dsi_tpu.parallel.grepstream import (
    GrepStreamResult,
    batch_lines,
    grep_host_oracle,
    grep_streaming,
    indexer_streaming,
    write_indexer_output,
    _grep_step_device,
    _LineTooLong,
    _top_positions,
)
from dsi_tpu.parallel.shuffle import default_mesh
from dsi_tpu.utils.jaxcompat import enable_x64

WORDS = re.compile(r"[A-Za-z]+")


def _mesh():
    return default_mesh(8)


def _letters(i: int) -> str:
    return "".join(chr(97 + (i // 26 ** j) % 26) for j in range(3))


VOCAB = [_letters(i) for i in range(600)]


# ── batch_lines ────────────────────────────────────────────────────────


def test_batch_lines_cuts_only_at_newlines():
    blocks = [b"alpha\nbeta\n", b"gam", b"ma\ndelta\nepsilon"]
    batches = list(batch_lines(blocks, n_dev=2, chunk_bytes=8))
    text = b""
    total_lines = 0
    for batch, lens, row_lines in batches:
        for d in range(2):
            row = bytes(batch[d, :lens[d]])
            assert not batch[d, lens[d]:].any()  # zero tail
            # no line straddles a row: every non-final row ends in \n
            text += row
            total_lines += int(row_lines[d])
    assert text == b"".join(blocks)
    # 5 lines, the last unterminated
    assert total_lines == 5


def test_batch_lines_line_wider_than_chunk_raises():
    with pytest.raises(_LineTooLong):
        list(batch_lines([b"x" * 100], n_dev=2, chunk_bytes=16))


def test_batch_lines_exact_chunk_final_line_fits():
    # A final unterminated line of exactly chunk_bytes must NOT raise.
    batches = list(batch_lines([b"y" * 16], n_dev=1, chunk_bytes=16))
    assert len(batches) == 1
    batch, lens, row_lines = batches[0]
    assert int(lens[0]) == 16 and int(row_lines[0]) == 1


# The cut against a plain reference written here: join the blocks, cut
# greedily behind the last newline that fits, a row while more than a
# chunk is left and the rest as the last row.

_CUT_CHUNK = 32

_CUT_BLOCK_SIZES = (1, 7, _CUT_CHUNK - 1, _CUT_CHUNK, _CUT_CHUNK + 1,
                    4 * _CUT_CHUNK, "random")


def _cut_text(kind: str) -> bytes:
    rng = np.random.default_rng(sum(kind.encode()))
    lines = [b"w" * int(n) for n in rng.integers(0, _CUT_CHUNK - 1, 40)]
    if kind == "empty_stream":
        return b""
    if kind == "no_final_newline":
        return b"\n".join(lines) + b"\ntail without its newline"
    if kind == "empty_line_runs":
        for at in (3, 11, 12, 30):
            lines[at:at] = [b""] * (2 * _CUT_CHUNK + at)
    if kind == "exact_chunk_line":
        # a line that fills a row to its last byte, three times
        for at in (0, 9, 17):
            lines[at:at] = [b"x" * (_CUT_CHUNK - 1)]
        lines.append(b"x" * (_CUT_CHUNK - 1))
    return b"\n".join(lines) + b"\n"


def _cut_blocks(data: bytes, size, gaps: bool = False):
    rng = np.random.default_rng(len(data))
    blocks, pos = [], 0
    while pos < len(data):
        n = int(rng.integers(1, 5 * _CUT_CHUNK)) if size == "random" else size
        blocks.append(data[pos:pos + n])
        pos += n
    if gaps:  # empty blocks between the full ones, and at both ends
        blocks = [b for full in blocks for b in (b"", full, b"")]
        # ... in the other forms a block may take
        blocks = [bytearray(b) if i % 3 == 1 else
                  memoryview(b) if i % 3 == 2 else b
                  for i, b in enumerate(blocks)]
    return blocks


def _reference_cut(data: bytes, n_dev: int, chunk: int):
    """``(rows, offsets)``: the rows, and the stream offset behind every
    ``n_dev`` of them and behind the last."""
    rows, pos = [], 0
    while len(data) - pos > chunk:
        cut = data.rfind(b"\n", pos, pos + chunk) + 1
        if not cut:
            raise _LineTooLong
        rows.append(data[pos:cut])
        pos = cut
    if pos < len(data):
        rows.append(data[pos:])
    ends = np.cumsum([len(r) for r in rows]).tolist()
    return rows, ends[n_dev - 1::n_dev] + (ends[-1:] if len(rows) % n_dev
                                           else [])


def _cut_rows(blocks, n_dev: int, chunk: int, pool=None):
    """``batch_lines``' rows, lengths and line counts, flat, the unused
    rows of the last batch dropped once they are seen to be zero, and its
    offsets."""
    offsets, rows, lens, lines = [], [], [], []
    for batch, blens, row_lines in batch_lines(iter(blocks), n_dev, chunk,
                                               pool=pool, offsets=offsets):
        assert batch.shape == (n_dev, chunk)
        for d in range(n_dev):
            assert not batch[d, blens[d]:].any()  # zero tail, stale or not
            if blens[d] == 0:
                assert row_lines[d] == 0 and not blens[d:].any()
                continue
            rows.append(bytes(batch[d, :blens[d]]))
            lens.append(int(blens[d]))
            lines.append(int(row_lines[d]))
        if pool is not None:
            batch[:] = 0xFF  # what a confirmed step leaves is stale
            pool.give(batch)
    return rows, lens, lines, offsets


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("size", _CUT_BLOCK_SIZES)
@pytest.mark.parametrize("kind", ["no_final_newline", "empty_line_runs",
                                  "exact_chunk_line", "empty_blocks_between",
                                  "empty_stream"])
def test_batch_lines_matches_the_plain_cut(kind, size, n_dev):
    data = _cut_text(kind)
    blocks = _cut_blocks(data, size, gaps=kind == "empty_blocks_between")
    want_rows, want_offsets = _reference_cut(data, n_dev, _CUT_CHUNK)
    rows, lens, lines, offsets = _cut_rows(blocks, n_dev, _CUT_CHUNK)
    assert rows == want_rows
    assert lens == [len(r) for r in want_rows]
    assert lines == [r.count(b"\n") + (not r.endswith(b"\n"))
                     for r in want_rows]
    assert offsets == want_offsets
    if kind == "exact_chunk_line":
        assert lens.count(_CUT_CHUNK) >= 4


@pytest.mark.parametrize("size", _CUT_BLOCK_SIZES)
def test_batch_lines_line_of_a_chunk_and_a_byte_raises(size):
    data = (_cut_text("exact_chunk_line") + b"y" * _CUT_CHUNK + b"\n"
            + b"after\n")
    before, _ = _reference_cut(data[:data.index(b"y")], 1, _CUT_CHUNK)
    got = []
    with pytest.raises(_LineTooLong):
        for batch, lens, _lines in batch_lines(
                iter(_cut_blocks(data, size)), 1, _CUT_CHUNK):
            got.append(bytes(batch[0, :lens[0]]))
    assert got == before  # raised only once every row before it was cut
    with pytest.raises(_LineTooLong):
        _reference_cut(data, 1, _CUT_CHUNK)


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("size", [7, _CUT_CHUNK + 1, 4 * _CUT_CHUNK])
def test_batch_lines_zeroes_what_a_recycled_buffer_held(size, n_dev):
    from dsi_tpu.parallel.pipeline import BufferPool

    pool = BufferPool((n_dev, _CUT_CHUNK), retain=4)
    stale = [pool.take() for _ in range(3)]
    for buf in stale:
        buf[:] = 0xFF
        pool.give(buf)
    data = _cut_text("no_final_newline")
    want_rows, want_offsets = _reference_cut(data, n_dev, _CUT_CHUNK)
    # _cut_rows asserts every row's tail and the last batch's unused rows
    rows, _lens, _lines, offsets = _cut_rows(_cut_blocks(data, size), n_dev,
                                             _CUT_CHUNK, pool=pool)
    assert rows == want_rows and offsets == want_offsets
    assert len(want_rows) % 4 and pool.allocs == 3  # a partial last batch


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("size", [1, _CUT_CHUNK, "random"])
def test_batch_lines_resumes_behind_any_offset(size, n_dev):
    """The checkpoint cursor's contract: the stream behind ``offsets[i]``
    gives the batches from ``i + 1`` on."""
    from dsi_tpu.ckpt import skip_stream

    data = _cut_text("empty_line_runs")
    blocks = _cut_blocks(data, size)
    rows, lens, lines, offsets = _cut_rows(blocks, n_dev, _CUT_CHUNK)
    assert len(offsets) > 4
    for i in range(len(offsets) - 1):
        again = _cut_rows(skip_stream(iter(blocks), offsets[i]), n_dev,
                          _CUT_CHUNK)
        at = (i + 1) * n_dev
        assert again == (rows[at:], lens[at:], lines[at:],
                         [o - offsets[i] for o in offsets[i + 1:]])


def test_row_batches_are_batch_lines_rows_without_the_counts():
    from dsi_tpu.parallel.streaming import _row_batches

    data = _cut_text("empty_line_runs")
    blocks = _cut_blocks(data, 4 * _CUT_CHUNK)
    offsets, want_offsets = [], []
    got = [b.copy() for b in _row_batches(iter(blocks), 2, _CUT_CHUNK,
                                          offsets=offsets)]
    want = [b.copy() for b, _l, _n in batch_lines(
        iter(blocks), 2, _CUT_CHUNK, offsets=want_offsets)]
    assert offsets == want_offsets and len(got) == len(want)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


# ── grep: oracle + host path ───────────────────────────────────────────


def _grep_blocks(seed: int, n_blocks: int = 8):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_blocks):
        words = [VOCAB[j] for j in rng.integers(0, 400, 120)]
        lines = []
        cur = []
        for w in words:
            cur.append(w)
            if rng.random() < 0.2:
                lines.append(" ".join(cur))
                cur = []
        lines.append(" ".join(cur))
        out.append(("\n".join(lines) + "\n").encode())
    return out


def test_grep_host_path_matches_oracle():
    blocks = _grep_blocks(1)
    want = grep_host_oracle(list(blocks), "aba")
    st: dict = {}
    res = grep_streaming(list(blocks), "aba", mesh=_mesh(),
                         chunk_bytes=1 << 11, depth=2, pipeline_stats=st)
    assert res == want
    assert isinstance(res, GrepStreamResult)
    assert st["step_pulls"] >= 1 and st["sync_pulls"] == 0
    assert sum(res.hist) == res.lines  # every line lands in one bucket


def test_grep_overlapping_occurrences_counted():
    # 'aa' in 'aaaa' occurs 3 times (overlapping) — engine and oracle
    # must agree on the overlap rule.
    blocks = [b"aaaa\naa\nxx\n"]
    want = grep_host_oracle(list(blocks), "aa")
    assert want.occurrences == 4 and want.matched == 2
    res = grep_streaming(list(blocks), "aa", mesh=_mesh(),
                         chunk_bytes=1 << 11, depth=1)
    assert res == want


def test_grep_host_path_rejections():
    mesh = _mesh()
    # non-literal pattern: the regex tiers' job, not this engine's
    assert grep_streaming([b"x\n"], "th.e", mesh=mesh,
                          chunk_bytes=1 << 11) is None
    # a line wider than the chunk: host path
    assert grep_streaming([b"z" * 5000], "z", mesh=mesh,
                          chunk_bytes=1 << 11) is None
    # empty stream: zeros, not None
    res = grep_streaming([], "the", mesh=mesh, chunk_bytes=1 << 11)
    assert res.lines == 0 and res.matched == 0 and res.topk == ()


# ── grep: the step body against a plain per-line count ────────────────

_STEP_N, _STEP_BINS, _STEP_K = 256, 8, 4
_STEP_BASE = (7 << 32) + 5  # global line numbers need both key lanes


def _run_step(data: bytes, pat: bytes, emit: bool = False):
    """The step body on one zero-padded chunk, outside ``shard_map``:
    ``(fn, args, results)``."""
    chunk = np.zeros((1, _STEP_N), np.uint8)
    chunk[0, :len(data)] = np.frombuffer(data, np.uint8)
    args = (chunk, np.frombuffer(pat, np.uint8)[None],
            np.array([len(data)], np.int32),
            np.array([_STEP_BASE], np.uint64))
    fn = functools.partial(_grep_step_device, bins=_STEP_BINS, k=_STEP_K,
                           emit=emit)
    with enable_x64(True):
        return fn, args, [np.asarray(x) for x in jax.jit(fn)(*args)]


_STEP_CASES = {
    "dlen_0": (b"", b"the"),
    "no_trailing_newline": (b"xthe\nnone\nthe the", b"the"),
    "only_newlines": (b"\n" * 20, b"the"),
    "match_on_every_line": (b"the\n" * 10, b"the"),
    "more_than_bins_on_a_line": (b"a\n" + b"a" * 12 + b"\naaa\n", b"aa"),
    "fewer_matched_than_k": (b"x\nthe\ny\nz the the\nw\n", b"the"),
    "ties_across_the_topk_edge":
        (b"ab ab\n" * 3 + b"ab ab ab\nq\n" + b"ab ab\n" * 3
         + b"ab ab ab\nab\n", b"ab"),
    # 41 lines in 256 bytes: more than an eighth of the chunk
    "n_lines_above_l_cap": (b"a\n" * 20 + b"aaa\nb\n" * 10 + b"aa", b"a"),
    "pattern_ends_at_last_valid_byte": (b"xx\nab the\nabthe", b"the"),
    "full_chunk_one_open_line": (b"the " * (_STEP_N // 4), b"the"),
    "full_chunk_newline_last":
        (b"aba" * 5 + b"\n" + b"b" * (_STEP_N - 32) + b"\n"
         + b"ababa" * 2 + b"abab\n", b"aba"),
}


@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_grep_step_body_matches_plain_count(case):
    """The step program's three results against the host oracle (split
    at newlines, overlapping occurrences per line) over the same bytes:
    the histogram row, the scalars, and the candidate rows in (count
    desc, line asc) order with ties to the earlier line.  Lane 2 of the
    scalar row is reserved and 0 whatever the line count: the counts
    pass through no line buffer."""
    data, pat = _STEP_CASES[case]
    assert len(data) <= _STEP_N
    _, _, (hist_ext, cand, scal) = _run_step(data, pat)
    n_lines, matched, occurrences, hist, top = grep_host_oracle(
        [data], pat.decode(), bins=_STEP_BINS, topk=_STEP_K)
    assert hist_ext.shape == (1, _STEP_BINS + 3)
    assert cand.shape == (1, _STEP_K, 5) and scal.shape == (1, 5)
    assert hist_ext[0].tolist() == [*hist, n_lines, matched, occurrences]
    assert scal[0].tolist() == [len(top), n_lines, 0, matched,
                                occurrences]
    rows = cand[0].astype(np.int64)
    got = [(int((r[0] << 32) | r[1]) - _STEP_BASE, int(r[3]))
           for r in rows[:len(top)]]
    assert got == list(top)  # (line, occurrences)
    assert (rows[:len(top), 2] == 8).all() and (rows[:, 4] == 0).all()
    assert not rows[len(top):].any()  # dead candidate rows are zero


@pytest.mark.parametrize("n", [100, 1024, 3000, 4096])
def test_top_positions_exact_with_ties_to_the_lower_position(n):
    """The two-stage top-k against a stable numpy sort: few distinct
    values, so ties cross the rows of the ``[n / 1024, 1024]`` view and
    the edge of the top k; ``n`` below, at and off a multiple of the
    row width."""
    vals = np.random.default_rng(n).integers(0, 4, n).astype(np.int32)
    vals[n // 2] = vals[n - 1] = 9
    for k in (1, 16, 40):
        want = np.argsort(-vals, kind="stable")[:k]
        got_val, got_pos = jax.jit(_top_positions, static_argnums=1)(vals, k)
        assert np.asarray(got_pos).tolist() == want.tolist()
        assert np.asarray(got_val).tolist() == vals[want].tolist()


@pytest.mark.parametrize("emit", [False, True])
def test_grep_step_jaxpr_holds_no_scatter(emit):
    """The mechanism, pinned where no device trace is at hand: per-line
    statistics come from scans read at the line ends, so neither step
    variant may lower a scatter (``segment_sum`` and ``.at[].add`` are
    both ``scatter-add``)."""
    fn, args, _ = _run_step(b"the\nx\n", b"the", emit=emit)

    def names(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    with enable_x64(True):
        prims = set(names(jax.make_jaxpr(fn)(*args).jaxpr))
    assert {"cumsum", "cummax"} <= prims
    assert not [p for p in prims if "scatter" in p], sorted(prims)


# ── grep: the parity grid ──────────────────────────────────────────────


def test_grep_parity_grid_depth_x_device_accumulate():
    """depth x device_accumulate x K bit-identical to the depth=1
    host-merge path (and to the oracle)."""
    blocks = _grep_blocks(7)
    mesh = _mesh()
    want = grep_host_oracle(list(blocks), "aba")
    base = grep_streaming(list(blocks), "aba", mesh=mesh,
                          chunk_bytes=1 << 11, depth=1)
    assert base == want
    for depth in (1, 3):
        for dacc, k in ((False, None), (True, 1), (True, 4)):
            st: dict = {}
            res = grep_streaming(list(blocks), "aba", mesh=mesh,
                                 chunk_bytes=1 << 11, depth=depth,
                                 device_accumulate=dacc, sync_every=k,
                                 pipeline_stats=st)
            assert res == base, (depth, dacc, k)
            if dacc:
                assert st["step_pulls"] == 0


def _grep_step_programs(n_dev: int, chunk_bytes: int, m: int):
    """Names of the compiled ``grep_stream_*`` programs this process
    holds for one shape (``backends/aotcache``'s memo)."""
    from dsi_tpu.backends import aotcache

    head = f"grep_stream_d{n_dev}_c{chunk_bytes}_m{m}_"
    return sorted({key[0] for key in aotcache._memo
                   if key[0].startswith(head)})


def test_grep_short_lines_exact_no_replay():
    """One-byte lines, far more of them than an eighth of the chunk:
    the oracle's result on the host-merge and the device-service path,
    nothing replayed."""
    blocks = [b"a\n" * 2000, b"aba\nx\n" * 500, b"a\n" * 2000]
    mesh = _mesh()
    want = grep_host_oracle(list(blocks), "aba")
    st: dict = {}
    res = grep_streaming(list(blocks), "aba", mesh=mesh,
                         chunk_bytes=1 << 11, depth=2, pipeline_stats=st)
    assert res == want
    assert st["replays"] == 0 and st["replay_s"] == 0.0
    assert st["steps"] >= 1 and "l_cap" not in st
    # same stream through the device services, same answer
    st2: dict = {}
    res2 = grep_streaming(list(blocks), "aba", mesh=mesh,
                          chunk_bytes=1 << 11, depth=2,
                          device_accumulate=True, sync_every=2,
                          pipeline_stats=st2)
    assert res2 == want
    assert st2["replays"] == 0 and st2["step_pulls"] == 0


def test_grep_short_lines_leave_one_cache_entry():
    """After a short-line stream the process holds exactly one
    ``grep_stream_*`` executable for the shape (a chunk size no other
    test of this file uses, so the entry is this stream's)."""
    mesh = _mesh()
    n_dev, chunk_bytes = mesh.devices.size, 3 << 9
    assert _grep_step_programs(n_dev, chunk_bytes, 1) == []
    blocks = [b"a\n" * 2000] * 3
    st: dict = {}
    res = grep_streaming(list(blocks), "a", mesh=mesh,
                         chunk_bytes=chunk_bytes, depth=2,
                         pipeline_stats=st)
    assert res == grep_host_oracle(list(blocks), "a")
    assert st["replays"] == 0 and st["steps"] >= 1
    assert _grep_step_programs(n_dev, chunk_bytes, 1) == [
        f"grep_stream_d{n_dev}_c{chunk_bytes}_m1_b8_t16"]


def test_grep_forced_topk_widen_never_drops(monkeypatch):
    """A candidate table forced to a tiny rung overflows mid-stream:
    the fold no-ops, the service drains + widens + re-folds, and the
    final top-k is still bit-identical — overflow surfaces a widen
    signal, it never drops candidates."""
    monkeypatch.setenv("DSI_DEVICE_TOPK_CAP", "32")
    blocks = [(" aba x" * 8 + "\n").encode() * 30] * 60
    mesh = _mesh()
    want = grep_host_oracle(list(blocks), "aba")
    st: dict = {}
    res = grep_streaming(list(blocks), "aba", mesh=mesh,
                         chunk_bytes=1 << 11, depth=2,
                         device_accumulate=True, sync_every=3,
                         pipeline_stats=st)
    assert res == want
    assert st["widens"] >= 1 and st["fold_overflows"] >= 1
    assert st["step_pulls"] == 0
    assert st["table_cap"] > 32  # the rung actually moved


def test_grep_sync_accounting_windows_plus_close():
    """Device path accounting: zero per-step pulls; one snapshot+hist
    pull bundle per K confirmed folds plus the close drain — the
    ceil(steps/K)+widens amortization the service exists for."""
    line = (" ".join(VOCAB[:30]) + " aba\n").encode() * 6
    blocks = [line] * 400  # ~290 KB -> ~18 steps of 8 x 2 KiB
    mesh = _mesh()
    for k in (3, 8):
        st: dict = {}
        res = grep_streaming(list(blocks), "aba", mesh=mesh,
                             chunk_bytes=1 << 11, depth=2,
                             device_accumulate=True, sync_every=k,
                             pipeline_stats=st)
        assert res is not None and res.matched > 0
        assert st["step_pulls"] == 0 and st["widens"] == 0
        windows = st["folds"] // k
        assert st["folds"] == st["steps"] >= k
        assert st["sync_pulls"] == windows + 1  # windows + close drain
        assert st["hist_pulls"] == windows + 1
        assert st["topk_snapshots"] == windows


# ── one transfer each way per step (PR 29) ─────────────────────────────


def _prefetch_blocks():
    # ~115 KB of short lines: 8 steps of 8 x 2 KiB, matches in every one
    return _grep_blocks(31, n_blocks=240)


def _run_prefetch_path(path, depth, tmp_path, monkeypatch):
    """One stream through one of the four paths whose ``finish_one``
    converts different arrays on the host; returns (result, relay bytes
    or None, stats of the run that finished the stream)."""
    from dsi_tpu.ckpt import FaultInjected, reset_faults
    from dsi_tpu.device.relay import HostRelay
    from dsi_tpu.parallel.grepstream import GrepStep

    blocks, mesh, st = _prefetch_blocks(), _mesh(), {}
    kw = dict(mesh=mesh, chunk_bytes=1 << 11, depth=depth,
              pipeline_stats=st)
    if path == "emit":
        relay = HostRelay()
        res = GrepStep(list(blocks), "aba", line_sink=relay, **kw).close()
        return res, b"".join(relay.blocks()), st
    if path == "resume":
        ck = str(tmp_path / "ck")
        monkeypatch.setenv("DSI_FAULT_MODE", "raise")
        monkeypatch.setenv("DSI_FAULT_POINT", "mid-fold")
        monkeypatch.setenv("DSI_FAULT_STEP", "3")
        reset_faults()
        with pytest.raises(FaultInjected):
            grep_streaming(list(blocks), "aba", checkpoint_dir=ck,
                           checkpoint_every=1, **kw)
        for k in ("DSI_FAULT_MODE", "DSI_FAULT_POINT", "DSI_FAULT_STEP"):
            monkeypatch.delenv(k)
        reset_faults()
        st.clear()
        res = grep_streaming(list(blocks), "aba", checkpoint_dir=ck,
                             checkpoint_every=1, resume=True, **kw)
        assert 0 < st["resume_cursor"] < sum(map(len, blocks))
        return res, None, st
    res = grep_streaming(list(blocks), "aba", sync_every=2,
                         device_accumulate=(path == "device_accumulate"),
                         **kw)
    return res, None, st


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("path", ["host_accumulate", "device_accumulate",
                                  "emit", "resume"])
def test_grep_prefetched_results_equal_the_oracle(path, depth, tmp_path,
                                                  monkeypatch):
    """The copies that start at dispatch change no answer, whichever
    arrays the path converts on the host, at depth 1 (read at once) and
    2 (read one pump later)."""
    res, kept, st = _run_prefetch_path(path, depth, tmp_path, monkeypatch)
    assert res == grep_host_oracle(_prefetch_blocks(), "aba")
    if kept is not None:
        lines = b"".join(_prefetch_blocks()).split(b"\n")
        assert kept == b"".join(ln + b"\n" for ln in lines if b"aba" in ln)
    assert 0 <= st["results_ready"] <= st["steps"] and st["steps"] >= 4


@pytest.mark.parametrize("path,per_step", [
    ("host_accumulate", [(8, 5), (8, 11), (8, 16, 5)]),
    ("device_accumulate", [(8, 5)]),
    ("emit", [(8, 5), (8, 11), (8, 16, 5), (8,)]),
])
def test_grep_host_copies_start_only_for_what_finish_converts(
        path, per_step, tmp_path, monkeypatch):
    """What ``step_call`` hands the helper, by shape: the scalar row
    always; the histogram and candidate rows only where the host
    accumulates (with ``device_accumulate`` they stay on the device and
    no copy of them starts); the kept counts with ``emit``.  The helper
    here also waits for the array, so every step's reads are ready by
    construction and ``results_ready`` must count them all."""
    from dsi_tpu.parallel import grepstream

    started = []

    def start_and_wait(arr):
        started.append(tuple(arr.shape))
        arr.block_until_ready()

    monkeypatch.setattr(grepstream, "_copy_to_host_async", start_and_wait)
    res, _, st = _run_prefetch_path(path, 2, tmp_path, monkeypatch)
    assert res == grep_host_oracle(_prefetch_blocks(), "aba")
    assert started == per_step * st["steps"]
    assert st["results_ready"] == st["steps"]


def test_grep_step_puts_its_inputs_in_one_call(monkeypatch):
    """A step's inputs go up in one ``device_put`` (the pattern goes up
    once a stream): the chunk and ONE small array, uint64 so that the
    line bases stay whole, holding each row's byte count and line base."""
    puts = []
    real_put = jax.device_put

    def counting_put(x, *a, **kw):
        out = real_put(x, *a, **kw)
        puts.append(out)
        return out

    monkeypatch.setattr(jax, "device_put", counting_put)
    st: dict = {}
    res = grep_streaming(_prefetch_blocks(), "aba", mesh=_mesh(),
                         chunk_bytes=1 << 11, depth=2, pipeline_stats=st)
    assert res == grep_host_oracle(_prefetch_blocks(), "aba")
    pattern, steps = puts[0], puts[1:]
    assert pattern.shape == (8, 3)
    assert len(steps) == st["steps"] >= 4
    all_bases = []
    for chunks, meta in steps:
        assert chunks.shape == (8, 1 << 11) and chunks.dtype == np.uint8
        assert meta.shape == (8, 2) and meta.dtype == np.uint64
        lens, bases = np.asarray(meta).T
        assert lens.max() <= 1 << 11
        all_bases += bases.tolist()
    # each row's first line number: from 0, never backwards, within the total
    assert all_bases[0] == 0 and all_bases == sorted(all_bases)
    assert all_bases[-1] <= res.lines


def test_grep_property_random_streams():
    """Property: random streams x random K x both paths, equal to the
    oracle and to each other."""
    mesh = _mesh()
    for seed in (11, 29):
        rng = np.random.default_rng(seed)
        blocks = _grep_blocks(seed, n_blocks=int(rng.integers(3, 7)))
        pat = ["aba", "ab", "aaa"][int(rng.integers(0, 3))]
        k = int(rng.integers(1, 6))
        want = grep_host_oracle(list(blocks), pat)
        res = grep_streaming(list(blocks), pat, mesh=mesh,
                             chunk_bytes=1 << 11, depth=2,
                             device_accumulate=True, sync_every=k)
        assert res == want, (seed, pat, k)


# ── indexer ────────────────────────────────────────────────────────────


def _idx_docs(n_docs: int, seed: int):
    rng = np.random.default_rng(seed)
    return [(" ".join(VOCAB[j] for j in
                      rng.integers(0, 180, int(rng.integers(30, 120))))
             + "\n").encode() for _ in range(n_docs)]


def _idx_oracle(docs):
    """{word: sorted doc list} + {word: df} from the host tokenizer."""
    posts: dict = {}
    for d, doc in enumerate(docs):
        for w in sorted(set(WORDS.findall(doc.decode()))):
            posts.setdefault(w, []).append(d)
    return posts


def test_indexer_matches_oracle_and_posting_order():
    mesh = _mesh()
    docs = _idx_docs(13, seed=5)
    want = _idx_oracle(docs)
    st: dict = {}
    base = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                             depth=1, stats=st)
    assert base is not None
    postings, top = base
    assert set(postings) == set(want)
    for w, docs_w in want.items():
        # doc SETS match the oracle; ORDER is the wave order, stable
        assert sorted(postings[w][1]) == docs_w, w
    # df top-k: count desc, word asc, exact
    df = {w: len(ds) for w, ds in want.items()}
    want_top = tuple(sorted(((c, w) for w, c in df.items()),
                            key=lambda r: (-r[0], r[1]))[:16])
    assert top == want_top
    assert st["step_pulls"] >= 1


def test_indexer_parity_grid_bit_identical():
    """depth x device_accumulate x K: identical postings (per-word doc
    ORDER included) and identical df top-k."""
    mesh = _mesh()
    docs = _idx_docs(21, seed=9)
    base = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                             depth=1)
    assert base is not None
    for depth in (1, 3):
        for dacc, k in ((False, None), (True, 2), (True, 7)):
            st: dict = {}
            res = indexer_streaming(docs, mesh=mesh, n_reduce=10,
                                    u_cap=1 << 9, depth=depth,
                                    device_accumulate=dacc, sync_every=k,
                                    stats=st)
            assert res is not None
            assert res == base, (depth, dacc, k)
            if dacc:
                assert st["step_pulls"] == 0
                assert st["appends"] >= 1 and st["folds"] >= 1


def test_indexer_forced_topk_widen(monkeypatch):
    """The df table forced below the vocabulary widens mid-walk and the
    result is still bit-identical — same acceptance as the stream's
    fold table."""
    monkeypatch.setenv("DSI_DEVICE_TOPK_CAP", "32")
    mesh = _mesh()
    docs = _idx_docs(16, seed=3)
    base = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                             depth=1)
    st: dict = {}
    res = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                            depth=2, device_accumulate=True, sync_every=2,
                            stats=st)
    assert base is not None and res is not None
    assert res == base
    assert st["widens"] >= 1 and st["fold_overflows"] >= 1
    assert st["step_pulls"] == 0


def test_indexer_forced_postings_overflow(monkeypatch):
    """A postings buffer trimmed below the window drains early (the
    sticky-dirty order-preserving recovery) while the df folds ride the
    same confirmations — nothing lost, nothing doubled, order intact."""
    monkeypatch.setenv("DSI_DEVICE_POSTINGS_CAP", "256")
    mesh = _mesh()
    docs = _idx_docs(40, seed=13)
    base = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                             depth=1)
    st: dict = {}
    res = indexer_streaming(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                            depth=2, device_accumulate=True,
                            sync_every=10_000, stats=st)
    assert base is not None and res is not None
    assert res == base
    assert st["append_overflows"] >= 1


def test_indexer_host_path_rejections():
    mesh = _mesh()
    # non-ASCII: the host app's job
    assert indexer_streaming(["caf\xe9".encode("utf-8")], mesh=mesh,
                             n_reduce=10, u_cap=1 << 9) is None
    # a word wider than 64 bytes: host path
    assert indexer_streaming([b"x" * 80 + b" y"], mesh=mesh, n_reduce=10,
                             u_cap=1 << 9) is None


def test_write_indexer_output_matches_host_app_format(tmp_path):
    """mr-out-* files byte-identical to the sequential indexer app over
    the same documents."""
    from dsi_tpu.apps import indexer as app
    from dsi_tpu.mr.sequential import run_sequential
    from tests.harness import merged_output

    docs = _idx_docs(6, seed=21)
    names = []
    for i, doc in enumerate(docs):
        p = tmp_path / f"doc-{i}.txt"
        p.write_bytes(doc)
        names.append(str(p))
    res = indexer_streaming(docs, mesh=_mesh(), n_reduce=10, u_cap=1 << 9)
    assert res is not None
    wd = tmp_path / "out"
    wd.mkdir()
    write_indexer_output(res, names, 10, str(wd))
    oracle_out = tmp_path / "mr-correct.txt"
    run_sequential(app.Map, app.Reduce, names, str(oracle_out))
    with open(oracle_out, encoding="utf-8") as f:
        want = sorted(l for l in f if l.strip())
    assert merged_output(str(wd)) == want


# ── warm ladder / AOT coverage ─────────────────────────────────────────


def test_grep_warm_covers_everything(tmp_path, monkeypatch):
    """warm_grepstream_aot(device_accumulate=True) must pre-compile
    every program a device-accumulated aot run then executes — the one
    step program, the top-k fold/pack/snapshot shapes, the histogram
    fold — so a chip run is loads, never compiles."""
    from dsi_tpu.backends import aotcache
    from dsi_tpu.parallel.grepstream import warm_grepstream_aot

    mesh = default_mesh(1)
    warm_grepstream_aot(mesh=mesh, chunk_bytes=1 << 14,
                        device_accumulate=True)
    assert _grep_step_programs(1, 1 << 14, 3) == [
        "grep_stream_d1_c16384_m3_b8_t16"]
    compiles_after_warm = aotcache.stats["compiles"]
    blocks = [b"the quick fox\nthe end\n" * 200] * 3
    want = grep_host_oracle(list(blocks), "the")
    st: dict = {}
    res = grep_streaming(list(blocks), "the", mesh=mesh,
                         chunk_bytes=1 << 14, depth=2, aot=True,
                         device_accumulate=True, sync_every=2,
                         pipeline_stats=st)
    assert res == want
    assert st["folds"] >= 1 and st["step_pulls"] == 0
    assert aotcache.stats["compiles"] == compiles_after_warm


# ── CLI ────────────────────────────────────────────────────────────────


def test_grepstream_cli_check_against_oracle(tmp_path):
    """The engine is reachable without importing internals: grepstream
    --check end-to-end (device-accumulated) vs the host oracle."""
    from dsi_tpu.cli import grepstream as cli
    from dsi_tpu.utils.corpus import ensure_corpus

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=20_000)
    rc = cli.main(["--pattern", "the", "--chunk-bytes", "4096",
                   "--check", "--device-accumulate", "--sync-every", "4",
                   "--topk", "8"] + files)
    assert rc == 0  # --check exits 2 on a parity failure
