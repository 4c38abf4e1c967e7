"""Plan-layer tests (``dsi_tpu/plan`` + ``device/relay.py``).

What they pin, per the ISSUE-14 contract:

* the relay pack program is byte-exact (device concat == host concat),
  seals at capacity, spills under a budget, and round-trips through
  ``capture``/``restore``;
* a grep → wordcount chain on the device path is BIT-IDENTICAL to the
  staged baseline (host materialization between the stages) across
  depth × device-accumulate × mesh-shards × forced widen inside stage
  2, and moves ZERO intermediate bytes through the host;
* the indexer → df-top-k → postings-join chain matches its staged twin
  in both device-accumulate and host-merge modes, including the
  widen-residue fallback;
* stage commits make the chain resume at the last COMPLETED stage for
  every inter-stage fault point, and a torn stage manifest falls back
  to re-running that stage from its upstream's commit.
"""

import glob
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import NamedSharding, PartitionSpec as P

from dsi_tpu.ckpt.fault import FaultInjected, reset_faults
from dsi_tpu.device.relay import DeviceRelay, HostRelay
from dsi_tpu.obs import get_registry
from dsi_tpu.parallel.shuffle import AXIS, default_mesh
from dsi_tpu.plan import (Plan, PlanError, Stage, grep_wordcount_plan,
                          indexer_join_plan, run_plan)

MESH = None


def mesh():
    global MESH
    if MESH is None:
        MESH = default_mesh(8)
    return MESH


def corpus(n=420, wide_vocab=False, short_lines=False):
    """Matching lines carry 'the' plus a vocabulary; fillers don't."""
    lines = []
    for i in range(n):
        if i % 3 == 0:
            if wide_vocab:
                lines.append("the " + " ".join(
                    f"w{chr(97 + (i * 7 + j) % 26)}"
                    f"{chr(97 + (i * 3 + j) % 26)}q" for j in range(12)))
            elif short_lines:
                lines.append(f"the a{i % 9}")
            else:
                lines.append(f"the quick w{i % 29} fox likes the pond")
        else:
            lines.append("x" if short_lines else
                         f"unrelated filler row{i} content")
    return ("\n".join(lines) + "\n").encode()


def gw_plan(data, **kw):
    kw.setdefault("chunk_bytes", 1 << 9)
    return grep_wordcount_plan("the", data=data, **kw)


# ── relay units ───────────────────────────────────────────────────────


def _dev_chunk(rows, cap):
    """[n_dev, cap] device buffer from per-row byte strings."""
    buf = np.zeros((len(rows), cap), np.uint8)
    kept = np.zeros(len(rows), np.int64)
    for r, b in enumerate(rows):
        buf[r, :len(b)] = np.frombuffer(b, np.uint8)
        kept[r] = len(b)
    sh = NamedSharding(mesh(), P(AXIS, None))
    return jax.device_put(buf, sh), kept


def _drain_rows(relay, n_dev, cap):
    got = [bytearray() for _ in range(n_dev)]
    for b in relay.batches():
        arr = np.asarray(b)
        for r in range(n_dev):
            row = arr[r]
            nz = np.flatnonzero(row)
            end = int(nz[-1]) + 1 if nz.size else 0
            got[r].extend(row[:end].tobytes())
    return [bytes(g) for g in got]


def test_relay_pack_byte_exact_and_seals():
    cap = 64
    n_dev = 8
    stats = {}
    relay = DeviceRelay(mesh(), cap=cap, stats=stats)
    want = [bytearray() for _ in range(n_dev)]
    rng = np.random.default_rng(3)
    for step in range(7):
        rows = []
        for r in range(n_dev):
            n = int(rng.integers(0, 30))
            rows.append(bytes((rng.integers(1, 255, n)).astype(np.uint8)))
            want[r].extend(rows[-1])
        comp, kept = _dev_chunk(rows, cap)
        relay.append(comp, kept)
    assert relay.total_bytes == sum(len(w) for w in want)
    got = _drain_rows(relay, n_dev, cap)
    # Nonzero test bytes → zero-trim reconstruction is exact.
    assert got == [bytes(w) for w in want]
    assert stats["plan_intermediate_bytes"] == 0


def test_relay_spill_budget_counts_and_preserves():
    cap = 32
    n_dev = 8
    stats = {}
    relay = DeviceRelay(mesh(), cap=cap, stats=stats,
                        spill_bytes=n_dev * cap)  # one resident buffer
    want = [bytearray() for _ in range(n_dev)]
    for step in range(6):
        rows = [bytes([65 + step] * 20) for _ in range(n_dev)]
        for r in range(n_dev):
            want[r].extend(rows[r])
        comp, kept = _dev_chunk(rows, cap)
        relay.append(comp, kept)
    assert stats["plan_spilled_bytes"] > 0
    assert stats["plan_intermediate_bytes"] == stats["plan_spilled_bytes"]
    assert _drain_rows(relay, n_dev, cap) == [bytes(w) for w in want]


def test_relay_capture_restore_round_trip():
    cap = 48
    stats = {}
    relay = DeviceRelay(mesh(), cap=cap, stats=stats)
    rows = [b"hello world\n"] * 8
    comp, kept = _dev_chunk(rows, cap)
    relay.append(comp, kept)
    arrays = relay.capture()
    restored = DeviceRelay.restore(mesh(), arrays, cap=cap, stats={})
    assert _drain_rows(restored, 8, cap) == list(rows)
    # The original relay still serves its consumer after the capture.
    assert _drain_rows(relay, 8, cap) == list(rows)


def test_plan_graph_validation():
    p = Plan("t")
    p.add(Stage("a", "grep", pattern="x"))
    with pytest.raises(PlanError):
        p.add(Stage("a", "grep", pattern="x"))  # duplicate
    with pytest.raises(PlanError):
        p.add(Stage("b", "wordcount", deps=["nope"]))  # unknown dep
    with pytest.raises(PlanError):
        Stage("c", "sort")  # unknown kind
    sig = gw_plan(b"abc\n").signature()
    assert sig == gw_plan(b"abc\n").signature()
    assert sig != gw_plan(b"xyz\n").signature()  # data CRC in identity


# ── grep → wordcount parity grid ──────────────────────────────────────


@pytest.mark.parametrize("depth,dacc,shards", [
    (1, False, 0),
    (2, True, 0),
    (2, True, 8),
])
def test_grep_wc_chain_parity(depth, dacc, shards):
    data = corpus()
    kw = dict(depth=depth, device_accumulate=dacc, mesh_shards=shards)
    st_c, st_s = {}, {}
    chained = run_plan(gw_plan(data, **kw), mesh=mesh(), stats=st_c)
    staged = run_plan(gw_plan(data, **kw), mesh=mesh(), staged=True,
                      stats=st_s)
    assert chained.results["grep"] == staged.results["grep"]
    assert chained.final == staged.final
    assert len(chained.final) > 0
    # THE acceptance bar: the device-resident handoff moves zero
    # intermediate bytes through the host; the staged baseline moves
    # the full matching-line materialization.
    assert st_c["plan_intermediate_bytes"] == 0
    assert st_s["plan_intermediate_bytes"] > 0
    assert st_c["plan_handoff"] == "device"
    assert st_s["plan_handoff"] == "host"


def test_grep_wc_forced_widen_inside_stage2(monkeypatch):
    # A tiny device-table rung + a wide matching-line vocabulary force
    # the wordcount stage's widen protocol mid-chain.
    monkeypatch.setenv("DSI_DEVICE_TABLE_CAP", "32")
    data = corpus(wide_vocab=True)
    kw = dict(device_accumulate=True, sync_every=3)
    chained = run_plan(gw_plan(data, **kw), mesh=mesh())
    staged = run_plan(gw_plan(data, **kw), mesh=mesh(), staged=True)
    assert chained.final == staged.final
    assert get_registry().phases("stream").get("widens", 0) >= 1


def test_grep_wc_short_lines_no_replay():
    # Dense short lines, more of them than an eighth of a chunk: stage 1
    # runs its one emit program, replays nothing, and the emitted bytes
    # stay exact.
    data = corpus(short_lines=True)
    chained = run_plan(gw_plan(data), mesh=mesh())
    staged = run_plan(gw_plan(data), mesh=mesh(), staged=True)
    assert chained.final == staged.final
    assert len(chained.final) > 0
    grep = get_registry().phases("grep")
    assert grep["steps"] >= 1 and grep["replays"] == 0


def _kept_on_host(data: bytes, pat: bytes) -> bytes:
    """The bytes of the lines that hold ``pat``, each with its newline
    where it has one."""
    return b"".join(line for line in data.splitlines(keepends=True)
                    if pat in line)


_EMIT_CHUNK = 1 << 9
_EMIT_CASES = {
    # rows of exactly one line each: a terminated one, a filler, and an
    # open final line, all as wide as the chunk
    "line_spans_the_whole_chunk":
        b"the" + b"x" * (_EMIT_CHUNK - 4) + b"\n"
        + b"y" * (_EMIT_CHUNK - 1) + b"\n"
        + b"z" * (_EMIT_CHUNK - 3) + b"the",
    "last_line_open_and_matching":
        b"a the b\nfiller\nthe end has the no newline",
    "last_line_open_not_matching":
        b"filler\nthe the\n\nan open tail that does not match",
}


@pytest.mark.parametrize("case", sorted(_EMIT_CASES))
def test_grep_emit_keeps_what_the_host_keeps(case):
    # The emit step carries "my line matched" back from a line's end
    # over its bytes with one reverse scan; its edges are a line that
    # fills the chunk and a last line without a newline.
    from dsi_tpu.parallel.grepstream import GrepStep, grep_host_oracle

    data = _EMIT_CASES[case]
    relay = HostRelay()
    res = GrepStep([data], "the", mesh=mesh(), chunk_bytes=_EMIT_CHUNK,
                   line_sink=relay).close()
    assert res == grep_host_oracle([data], "the")
    assert b"".join(relay.blocks()) == _kept_on_host(data, b"the")


# ── stage-boundary crash/resume state machine ─────────────────────────


@pytest.mark.parametrize("point,step,resumed", [
    ("plan-stage0-advance", 2, 0),   # mid-stage-1: nothing committed
    ("plan-stage1-advance", 1, 1),   # stage-2 entry: stage 1 committed
    ("plan-stage1-advance", 3, 1),   # mid-stage-2
    ("post-stage-commit", 1, 1),     # right after stage 1's manifest
])
def test_chain_crash_resume_every_fault_point(tmp_path, monkeypatch,
                                              point, step, resumed):
    data = corpus()
    ck = str(tmp_path / "ck")
    want = run_plan(gw_plan(data), mesh=mesh()).final
    monkeypatch.setenv("DSI_FAULT_POINT", point)
    monkeypatch.setenv("DSI_FAULT_STEP", str(step))
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    reset_faults()
    with pytest.raises(FaultInjected):
        run_plan(gw_plan(data), mesh=mesh(), checkpoint_dir=ck)
    monkeypatch.delenv("DSI_FAULT_POINT")
    monkeypatch.delenv("DSI_FAULT_STEP")
    monkeypatch.delenv("DSI_FAULT_MODE")
    st: dict = {}
    res = run_plan(gw_plan(data), mesh=mesh(), checkpoint_dir=ck,
                   resume=True, stats=st)
    assert st["plan_resumed_stages"] == resumed
    assert res.final == want


def test_torn_stage_manifest_falls_back(tmp_path):
    data = corpus()
    ck = str(tmp_path / "ck")
    want = run_plan(gw_plan(data), mesh=mesh()).final
    reset_faults()
    run_plan(gw_plan(data), mesh=mesh(), checkpoint_dir=ck)
    # Tear the FINAL stage's manifest: resume must fall back to the
    # stage-1 commit and re-run only stage 2.
    m = sorted(glob.glob(os.path.join(ck, "stage01-wc",
                                      "manifest-*.json")))[-1]
    with open(m, "r+b") as f:
        f.write(b"GARBAGE")
    st: dict = {}
    res = run_plan(gw_plan(data), mesh=mesh(), checkpoint_dir=ck,
                   resume=True, stats=st)
    assert st["plan_resumed_stages"] == 1
    assert res.final == want


def test_resume_refuses_other_plan(tmp_path):
    from dsi_tpu.ckpt import CheckpointMismatch

    ck = str(tmp_path / "ck")
    run_plan(gw_plan(corpus()), mesh=mesh(), checkpoint_dir=ck)
    with pytest.raises(CheckpointMismatch):
        run_plan(gw_plan(corpus(n=99)), mesh=mesh(), checkpoint_dir=ck,
                 resume=True)


# ── indexer → df-top-k → postings join ────────────────────────────────


DOCS = [f"alpha beta w{i % 7} gamma shared doc{i % 3} tail".encode()
        for i in range(13)]


@pytest.mark.parametrize("dacc", [False, True])
def test_indexer_chain_parity(dacc):
    kw = dict(topk=5, device_accumulate=dacc, u_cap=1 << 8)
    chained = run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh())
    staged = run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh(),
                      staged=True)
    assert chained.results["dftopk"] == staged.results["dftopk"]
    assert chained.final == staged.final
    assert len(chained.final) == 5


def test_indexer_chain_forced_topk_widen_fallback(monkeypatch):
    # A tiny df-table rung forces mid-walk widens whose drains land in
    # the host accumulator: the df-top-k stage must take the exact
    # drain fallback (snapshot alone would miss the host residue).
    monkeypatch.setenv("DSI_DEVICE_TOPK_CAP", "16")
    kw = dict(topk=5, device_accumulate=True, u_cap=1 << 8)
    chained = run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh())
    monkeypatch.delenv("DSI_DEVICE_TOPK_CAP")
    staged = run_plan(indexer_join_plan(DOCS, topk=5, u_cap=1 << 8),
                      mesh=mesh(), staged=True)
    assert chained.results["dftopk"] == staged.results["dftopk"]
    assert chained.final == staged.final


def test_indexer_chain_crash_resume(tmp_path, monkeypatch):
    ck = str(tmp_path / "ck")
    kw = dict(topk=5, device_accumulate=True, u_cap=1 << 8)
    want = run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh())
    monkeypatch.setenv("DSI_FAULT_POINT", "plan-stage1-advance")
    monkeypatch.setenv("DSI_FAULT_MODE", "raise")
    reset_faults()
    with pytest.raises(FaultInjected):
        run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh(),
                 checkpoint_dir=ck)
    monkeypatch.delenv("DSI_FAULT_POINT")
    monkeypatch.delenv("DSI_FAULT_MODE")
    st: dict = {}
    res = run_plan(indexer_join_plan(DOCS, **kw), mesh=mesh(),
                   checkpoint_dir=ck, resume=True, stats=st)
    assert st["plan_resumed_stages"] == 1
    assert res.results["dftopk"] == want.results["dftopk"]
    assert res.final == want.final


# ── handoff-hook guards ───────────────────────────────────────────────


def test_device_batches_refuses_checkpoint_dir(tmp_path):
    from dsi_tpu.parallel.streaming import WordcountStep

    with pytest.raises(ValueError):
        WordcountStep([], mesh=mesh(), device_batches=iter(()),
                      checkpoint_dir=str(tmp_path / "ck"))


def test_line_sink_refuses_checkpoint_dir(tmp_path):
    from dsi_tpu.parallel.grepstream import GrepStep

    with pytest.raises(ValueError):
        GrepStep([b"x\n"], "x", mesh=mesh(), line_sink=HostRelay(),
                 checkpoint_dir=str(tmp_path / "ck"))
