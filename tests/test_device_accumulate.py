"""Device-resident accumulator service (dsi_tpu/device/).

Oracle discipline as everywhere else: the device-accumulated paths must
agree BIT-FOR-BIT with the depth=1 host-merge paths and with a host
Counter over the Go tokenizer semantics — folds consume exactly the
confirmed per-step tables the host merge would, so any divergence is a
service bug, never a tolerance.
"""

import collections
import math
import re

import pytest

jax = pytest.importorskip("jax")

import numpy as np

from dsi_tpu.device import DeviceTable, SyncPolicy, sync_every_default
from dsi_tpu.parallel.merge import PackedCounts
from dsi_tpu.parallel.shuffle import (
    default_mesh,
    mapreduce_step,
    _slice_pack,
)
from dsi_tpu.parallel.streaming import wordcount_streaming

WORDS = re.compile(r"[A-Za-z]+")


def _mesh():
    return default_mesh(8)


def _letters(i: int) -> str:
    return "".join(chr(97 + (i // 26 ** j) % 26) for j in range(3))


VOCAB = [_letters(i) for i in range(800)]


def _counts(res):
    return {w: c for w, (c, _) in res.items()}


# ── SyncPolicy ─────────────────────────────────────────────────────────


def test_sync_policy_cadence_and_env_default(monkeypatch):
    p = SyncPolicy(3)
    for _ in range(2):
        p.note_fold()
        assert not p.due()
    p.note_fold()
    assert p.due()
    p.reset()
    assert not p.due()
    monkeypatch.setenv("DSI_STREAM_SYNC_EVERY", "5")
    assert sync_every_default() == 5
    assert sync_every_default(2) == 2  # explicit wins
    monkeypatch.setenv("DSI_STREAM_SYNC_EVERY", "junk")
    assert sync_every_default() == 8
    assert sync_every_default(0) == 1  # floored at the degenerate cadence


# ── DeviceTable unit: fold + widen against a hand-driven host merge ───


def _run_step(mesh, text: bytes, u_cap: int = 64):
    """One mapreduce_step over identical per-device chunks, packed the
    way the streaming engine hands steps to the fold."""
    n_dev = mesh.devices.size
    chunks_np = np.zeros((n_dev, 512), np.uint8)
    for d in range(n_dev):
        t = text[:512]
        chunks_np[d, :len(t)] = np.frombuffer(t, np.uint8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dsi_tpu.parallel.shuffle import AXIS

    chunks = jax.device_put(chunks_np, NamedSharding(mesh, P(AXIS, None)))
    keys, lens, cnts, parts, scal = mapreduce_step(
        chunks, n_dev=n_dev, n_reduce=10, max_word_len=16, u_cap=u_cap,
        mesh=mesh, t_cap_frac=4)
    packed = _slice_pack(keys, lens, cnts, parts, mp=keys.shape[1])
    return packed, scal, np.asarray(scal)


def _host_merge(steps, kk=4):
    acc = PackedCounts()
    for packed, _, scal_np in steps:
        pn = np.asarray(packed)
        for d in range(pn.shape[0]):
            nu = int(scal_np[d, 0])
            r = pn[d, :nu]
            acc.add(r[:, :kk], r[:, kk], r[:, kk + 1], r[:, kk + 2])
    return acc.finalize()


def test_device_table_fold_matches_host_merge():
    mesh = _mesh()
    steps = [_run_step(mesh, (" ".join(VOCAB[o:o + 20]) + " ").encode())
             for o in (0, 10, 40)]
    stats: dict = {}
    acc = PackedCounts()
    tab = DeviceTable(mesh, kk=4, cap=8 * 64, acc=acc, lag=1, stats=stats)
    for p, s, snp in steps:
        tab.fold(p, s, snp)
    tab.close()
    assert acc.finalize() == _host_merge(steps)
    assert stats["folds"] == 3 and stats["widens"] == 0
    assert stats["sync_pulls"] == 1  # the close() drain, nothing else


def test_device_table_widen_never_drops_keys():
    """A rung-0 capacity far below the vocabulary: every fold overflows,
    the service drains + widens + re-folds, and the final counts still
    match the host merge exactly — overflow surfaces a widen signal, it
    never silently drops keys."""
    mesh = _mesh()
    steps = [_run_step(mesh, (" ".join(VOCAB[o:o + 20]) + " ").encode())
             for o in (0, 20, 40)]
    stats: dict = {}
    acc = PackedCounts()
    tab = DeviceTable(mesh, kk=4, cap=2, acc=acc, lag=2, stats=stats)
    for p, s, snp in steps:
        tab.fold(p, s, snp)
    tab.close()
    got = acc.finalize()
    assert got == _host_merge(steps)
    assert len(got) == 60
    assert stats["widens"] >= 1 and stats["fold_overflows"] >= 1
    assert stats["table_cap"] > 2  # the rung actually moved


# ── streaming integration ─────────────────────────────────────────────


def test_stream_sync_accounting_exactly_ceil_steps_over_k():
    """K-step sync accounting: with every step non-empty and no widens,
    host pulls == ceil(folds / K) — the amortization the subsystem
    exists for (vs one pull per step on the host-merge path)."""
    line = (" ".join(VOCAB[:40]) + "\n").encode() * 4
    blocks = [line] * 480  # ~300 KB -> ~19 steps of 8 x 2 KiB
    mesh = _mesh()
    for k in (3, 8):
        st: dict = {}
        res = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                                  chunk_bytes=1 << 11, u_cap=64, depth=2,
                                  device_accumulate=True, sync_every=k,
                                  pipeline_stats=st)
        assert res is not None
        want = {w: c for w, c in collections.Counter(
            WORDS.findall((line * 480).decode())).items()}
        assert _counts(res) == want
        assert st["folds"] == st["steps"] >= 2 * k  # every step folded
        assert st["widens"] == 0 and st["step_pulls"] == 0
        assert st["sync_pulls"] == math.ceil(st["folds"] / k)


def test_stream_device_accumulate_bit_identical_to_host_merge():
    """depth x K parity grid against the depth=1 synchronous host-merge
    path: identical result DICTS (counts and partitions both)."""
    rng = np.random.default_rng(11)
    blocks = [(" ".join(VOCAB[j] for j in rng.integers(0, 300, 350))
               + "\n").encode() for _ in range(10)]
    text = b"".join(blocks)
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    mesh = _mesh()
    base = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                               chunk_bytes=1 << 11, u_cap=64, depth=1)
    assert base is not None and _counts(base) == want
    for depth in (1, 3):
        for k in (1, 4):
            st: dict = {}
            res = wordcount_streaming(
                list(blocks), mesh=mesh, n_reduce=10, chunk_bytes=1 << 11,
                u_cap=64, depth=depth, device_accumulate=True,
                sync_every=k, pipeline_stats=st)
            assert res is not None
            assert res == base, (depth, k)  # bit-identical, partitions too
            assert st["step_pulls"] == 0


def test_stream_fold_parity_random_with_forced_widen(monkeypatch):
    """Property test: random streams x random K, with the table forced
    to start at a tiny capacity rung (DSI_DEVICE_TABLE_CAP) so the vocab
    crosses it mid-stream — every run must widen at least once and still
    match the host-merge path bit-for-bit."""
    monkeypatch.setenv("DSI_DEVICE_TABLE_CAP", "32")
    mesh = _mesh()
    widens = 0
    for seed in (7, 23):
        rng = np.random.default_rng(seed)
        k = int(rng.integers(1, 7))
        blocks = [(" ".join(VOCAB[j] for j in rng.integers(0, 500, 300))
                   + "\n").encode()
                  for _ in range(int(rng.integers(6, 12)))]
        text = b"".join(blocks)
        want = dict(collections.Counter(WORDS.findall(text.decode())))
        base = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                                   chunk_bytes=1 << 11, u_cap=64, depth=1)
        st: dict = {}
        res = wordcount_streaming(
            list(blocks), mesh=mesh, n_reduce=10, chunk_bytes=1 << 11,
            u_cap=64, depth=2, device_accumulate=True, sync_every=k,
            pipeline_stats=st)
        assert base is not None and res is not None
        assert _counts(res) == want
        assert res == base, (seed, k)
        widens += st["widens"]
        # Widen drains are extra pulls, but bounded by the acceptance
        # formula: pulls <= ceil(folds/K) + widens.
        assert st["sync_pulls"] <= math.ceil(st["folds"] / k)
        assert st["step_pulls"] == 0
    assert widens >= 1  # the tiny rung actually forced the widen path


def test_stream_replayed_step_folds_exact_output():
    """A mid-stream capacity overflow replays through the ladder; with
    device accumulation the REPLAYED (exact) output folds on device —
    still zero per-step pulls, still bit-identical to depth=1."""
    rng = np.random.default_rng(23)
    small = ["aa", "bb", "cc", "dd"]
    blocks = []
    for i in range(12):
        vocab = small if i < 6 else VOCAB[:700]
        picks = rng.integers(0, len(vocab), 400)
        blocks.append((" ".join(vocab[j] for j in picks) + "\n").encode())
    text = b"".join(blocks)
    want = dict(collections.Counter(WORDS.findall(text.decode())))
    mesh = _mesh()
    base = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                               chunk_bytes=1 << 11, u_cap=64, depth=1)
    st: dict = {}
    res = wordcount_streaming(list(blocks), mesh=mesh, n_reduce=10,
                              chunk_bytes=1 << 11, u_cap=64, depth=3,
                              device_accumulate=True, sync_every=8,
                              pipeline_stats=st)
    assert base is not None and res is not None
    assert _counts(res) == want
    assert res == base
    assert st["replays"] >= 1   # the deferred check actually fired
    assert st["step_pulls"] == 0  # the replay folded, it did not pull


def test_wcstream_cli_device_accumulate_matches_oracle(tmp_path):
    """The service is reachable without importing internals: wcstream
    --device-accumulate end-to-end vs the sequential oracle."""
    from dsi_tpu.cli import wcstream
    from dsi_tpu.utils.corpus import ensure_corpus
    from tests.harness import merged_output, oracle_output

    files = ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                          file_size=20_000)
    want = oracle_output("wc", files, str(tmp_path))
    wd = tmp_path / "out"
    wd.mkdir()
    rc = wcstream.main(["--nreduce", "10", "--chunk-bytes", "4096",
                        "--check", "--device-accumulate", "--sync-every",
                        "4", "--stats", "--workdir", str(wd)] + files)
    assert rc == 0  # --check exits 2 on a parity failure
    assert merged_output(str(wd)) == want


def test_stream_device_accumulate_aot_warm_covers_everything(tmp_path,
                                                             monkeypatch):
    """The bench/chip configuration: aot=True + device_accumulate on a
    single-device mesh.  warm_stream_aot(device_accumulate=True) must
    pre-compile every program the stream then executes — step, pack,
    fold, clear, table pack — so the chip run is loads, never compiles;
    and the result must still match the Counter oracle."""
    from dsi_tpu.backends import aotcache
    from dsi_tpu.parallel.streaming import warm_stream_aot

    mesh = default_mesh(1)
    warm_stream_aot(mesh=mesh, chunk_bytes=1 << 14, caps=(1 << 10,),
                    device_accumulate=True)
    compiles_after_warm = aotcache.stats["compiles"]
    text = ("device resident accumulate " * 900).encode()
    st: dict = {}
    res = wordcount_streaming([text], mesh=mesh, n_reduce=10,
                              chunk_bytes=1 << 14, u_cap=1 << 10, aot=True,
                              device_accumulate=True, sync_every=8,
                              pipeline_stats=st)
    assert res is not None
    want = collections.Counter(WORDS.findall(text.decode()))
    assert _counts(res) == dict(want)
    assert st["folds"] >= 1 and st["step_pulls"] == 0
    assert aotcache.stats["compiles"] == compiles_after_warm


# ── TF-IDF wave walk integration ──────────────────────────────────────


def _tfidf_docs(n_docs: int, seed: int):
    rng = np.random.default_rng(seed)
    return [(" ".join(VOCAB[j] for j in
                      rng.integers(0, 200, int(rng.integers(30, 250))))
             + "\n").encode() for _ in range(n_docs)]


def test_tfidf_device_accumulate_matches_per_wave_pulls():
    from dsi_tpu.parallel.tfidf import tfidf_sharded

    mesh = _mesh()
    docs = _tfidf_docs(20, seed=5)
    base = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9)
    st: dict = {}
    dev = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                        device_accumulate=True, sync_every=2,
                        wave_stats=st)
    assert base is not None and dev is not None
    assert dev == base  # same postings, same per-word order
    assert st["appends"] >= 1 and st["sync_pulls"] >= 1
    assert st["step_pulls"] == 0


def test_tfidf_device_accumulate_overflow_drains_early(monkeypatch):
    """A buffer trimmed below the window's postings overflows once a few
    waves accumulate: the append no-ops, the walk drains and retries,
    and nothing is lost or doubled."""
    from dsi_tpu.parallel.tfidf import tfidf_sharded

    monkeypatch.setenv("DSI_DEVICE_POSTINGS_CAP", "512")
    mesh = _mesh()
    docs = _tfidf_docs(48, seed=9)
    base = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9)
    st: dict = {}
    # sync_every far beyond the wave count: only overflow can drain
    # before the end-of-walk sync.
    dev = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                        device_accumulate=True, sync_every=10_000,
                        wave_stats=st)
    assert base is not None and dev is not None
    assert dev == base
    assert st["append_overflows"] >= 1  # the early-sync path actually ran


def test_tfidf_device_accumulate_partition_slice():
    from dsi_tpu.parallel.tfidf import tfidf_sharded

    mesh = _mesh()
    docs = _tfidf_docs(12, seed=3)
    base = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9)
    sl = tfidf_sharded(docs, mesh=mesh, n_reduce=10, u_cap=1 << 9,
                       partitions={0, 1, 2}, device_accumulate=True,
                       sync_every=3)
    assert base is not None and sl is not None
    assert sl == {w: v for w, v in base.items() if v[0] in (0, 1, 2)}
