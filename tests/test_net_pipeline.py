"""Overlapped shuffle (ISSUE 18): pipelined reduce-side fetches,
streaming decode/merge, and net-served plan relays.

Layers, cheapest first:

* keep-alive transport units — :class:`rpc.StreamConn` multi-fetch
  reuse, poisoning after an error, the per-dialer :class:`ConnPool`
  redial-once on a stale cached connection;
* fetch-failure taxonomy units (satellite) — an unknown wirecodec flag
  and a torn LOCAL spool read both surface as :class:`FetchFailure`
  and both count in ``net_fetch_failures``;
* pipeline units — the parity grid (window 1/4/8 × wordcount/indexer
  reduce → byte-identical ``mr-out-*``), first-failure-wins with
  in-flight peers drained, and the slow-peer overlap attribution
  (``net_overlap_s`` > 0 pipelined, absent serial);
* journal × net units (satellite) — a coordinator killed between map
  commit and reduce dispatch replays the partition location registry
  from the journal, and reduce-output locations survive the same way;
* stage-payload codec units — ``pack_commit``/``unpack_commit``
  round-trip;
* the differential harness — ``mrrun --net --journal`` (accepted and
  parity-gated now), the off-loopback HMAC smoke
  (``DSI_NET_BIND=127.0.0.2`` + ``DSI_MR_SECRET`` — the CI auth-path
  exercise, plus the no-secret refusal), and ``planrun --hosts
  --check``: net-served plan relays, share-nothing audited, parity
  against the in-process chain.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dsi_tpu.config import JobConfig
from dsi_tpu.mr import rpc
from dsi_tpu.mr.coordinator import Coordinator
from dsi_tpu.mr.types import TaskStatus
from dsi_tpu.net import ConnPool, FetchPipeline, PartitionServer
from dsi_tpu.net.fetch import (FetchFailure, fetch_partition,
                               fetch_window_from_env,
                               run_reduce_task_net)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ── keep-alive transport ───────────────────────────────────────────────


def test_stream_conn_multi_fetch_reuse():
    served = []
    srv = rpc.StreamServer(
        "tcp:127.0.0.1:0",
        {"Blob": lambda args: served.append(args["N"]) or
                              b"payload-%d" % args["N"]})
    srv.start()
    try:
        with rpc.StreamConn(srv.address, timeout=10.0) as conn:
            for n in range(3):
                assert conn.fetch("Blob", {"N": n}) == b"payload-%d" % n
            assert conn.fetches == 3
        assert served == [0, 1, 2]
    finally:
        srv.close()


def test_stream_conn_poisoned_after_error():
    srv = rpc.StreamServer("tcp:127.0.0.1:0",
                           {"Blob": lambda args: b"ok"})
    srv.start()
    try:
        conn = rpc.StreamConn(srv.address, timeout=10.0)
        try:
            with pytest.raises(rpc.StreamError, match="no such method"):
                conn.fetch("Nope")
            # the server closed its end on the error response; the conn
            # must refuse reuse rather than read a desynchronized stream
            with pytest.raises(rpc.StreamError, match="already failed"):
                conn.fetch("Blob")
        finally:
            conn.close()
    finally:
        srv.close()


def test_conn_pool_redials_stale_keepalive(tmp_path):
    ps = PartitionServer(str(tmp_path / "spool"))
    ps.start()
    try:
        ps.put("mr-0-0", b"bytes one\n")
        with ConnPool(timeout=10.0) as pool:
            assert fetch_partition(ps.address, "mr-0-0",
                                   pool=pool) == b"bytes one\n"
            # sever the cached connection under the pool (the server's
            # idle timeout in real fleets); the next fetch must redial
            # once and succeed, not surface the stale socket's error
            pool._conns[ps.address]._sock.close()
            assert fetch_partition(ps.address, "mr-0-0",
                                   pool=pool) == b"bytes one\n"
    finally:
        ps.close()


# ── fetch-failure taxonomy (satellite) ─────────────────────────────────


def test_unknown_codec_flag_is_fetch_failure_and_counted():
    # a producer shipping a flag byte this consumer does not know is a
    # curable fetch failure (re-fetch from a replacement), NOT a bare
    # StreamError escaping into the reduce loop
    srv = rpc.StreamServer("tcp:127.0.0.1:0",
                           {"Fetch": lambda args: b"Xcorrupt"})
    srv.start()
    try:
        stats: dict = {}
        with pytest.raises(FetchFailure) as ei:
            fetch_partition(srv.address, "mr-0-0", stats=stats,
                            timeout=10.0)
        assert isinstance(ei.value.cause, rpc.StreamError)
        assert "unknown codec flag" in str(ei.value.cause)
        assert stats["net_fetch_failures"] == 1
    finally:
        srv.close()


def test_local_read_oserror_is_fetch_failure_and_counted(tmp_path):
    # the locality short-circuit's failure mode: our own advertised
    # address but the spool entry is unreadable (here: a directory) —
    # wrapped and counted exactly like a remote failure
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "mr-3-1"))
    stats: dict = {}
    with pytest.raises(FetchFailure) as ei:
        fetch_partition("tcp:127.0.0.1:9", "mr-3-1", stats=stats,
                        own_addr="tcp:127.0.0.1:9", local_root=root)
    assert isinstance(ei.value.cause, OSError)
    assert stats["net_fetch_failures"] == 1


# ── the prefetch pipeline ──────────────────────────────────────────────


def test_fetch_window_from_env(monkeypatch):
    monkeypatch.delenv("DSI_NET_FETCH_WINDOW", raising=False)
    assert fetch_window_from_env() == 4
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW", "8")
    assert fetch_window_from_env() == 8
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW", "0")
    assert fetch_window_from_env() == 1  # clamped: 0 would deadlock
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW", "garbage")
    assert fetch_window_from_env() == 4


def _spool_partitions(tmp_path, n_maps, reduce_task=0):
    """n_maps producers, each serving one KV partition for one reduce
    task; returns ``map_locs`` and the servers."""
    servers, map_locs = [], {}
    for m in range(n_maps):
        srv = PartitionServer(str(tmp_path / f"spool-{m}"))
        srv.start()
        servers.append(srv)
        lines = [json.dumps({"Key": f"w{(m * 7 + i) % 11:02d}",
                             "Value": "1"})
                 for i in range(120)]
        srv.put(f"mr-{m}-{reduce_task}",
                ("\n".join(lines) + "\n").encode())
        map_locs[str(m)] = srv.address
    return map_locs, servers


@pytest.mark.parametrize("app", ["wc", "indexer"])
def test_parity_grid_windows_are_byte_identical(tmp_path, app):
    # the tentpole's determinism claim: mr-out-<r> bytes are identical
    # at ANY window — window 1 being the literal pre-pipeline serial
    # loop, so 4 and 8 are bit-identical to it by transitivity
    from dsi_tpu.mr.plugin import load_plugin

    _mapf, reducef = load_plugin(app)
    map_locs, servers = _spool_partitions(tmp_path, n_maps=6)
    try:
        outs = {}
        for window in (1, 4, 8):
            wd = str(tmp_path / f"out-w{window}")
            os.makedirs(wd)
            stats: dict = {}
            name = run_reduce_task_net(reducef, 0, map_locs,
                                       workdir=wd, stats=stats,
                                       window=window)
            assert stats["net_prefetch_window"] == window
            if window == 1:
                assert "net_overlap_s" not in stats  # serial: none
            with open(os.path.join(wd, name), "rb") as f:
                outs[window] = f.read()
        assert outs[1] == outs[4] == outs[8]
        assert outs[1]  # the grid compared real content
    finally:
        for srv in servers:
            srv.close()


def test_pipeline_first_failure_wins_and_drains(tmp_path):
    map_locs, servers = _spool_partitions(tmp_path, n_maps=5)
    try:
        items = [(m, map_locs[str(m)],
                  f"mr-{m}-0" if m != 2 else "mr-missing-0")
                 for m in range(5)]
        pipe = FetchPipeline(items, window=3)
        got = []
        with pytest.raises(FetchFailure) as ei:
            for task, raw in pipe:
                got.append(task)
        # the failure is attributed to the producer whose bytes were
        # lost, with the original cause chained
        assert ei.value.task == 2
        assert ei.value.name == "mr-missing-0"
        # submission order up to the failure — the consumer stops
        # waiting the moment ANY dialer errors, so how far it got
        # before the (fast) failure landed is a race; the ORDER is not
        assert got == [0, 1][:len(got)]
        # in-flight peers were drained: no dialer thread survives
        assert not any(t.is_alive() for t in pipe._threads)
    finally:
        for srv in servers:
            srv.close()


def test_slow_peer_overlap_attribution(tmp_path):
    # a fake slow peer (injected per-chunk serve latency): the pipeline
    # hides its wire time behind the consumer (net_overlap_s > 0); the
    # serial path cannot, by construction, and reports none
    from dsi_tpu.mr.plugin import load_plugin

    _mapf, reducef = load_plugin("wc")
    map_locs, servers = _spool_partitions(tmp_path, n_maps=4)
    for srv in servers:
        srv._chunk_sleep_s = 0.05
    try:
        serial: dict = {}
        wd1 = str(tmp_path / "serial")
        os.makedirs(wd1)
        run_reduce_task_net(reducef, 0, map_locs, workdir=wd1,
                            stats=serial, window=1)
        piped: dict = {}
        wd4 = str(tmp_path / "piped")
        os.makedirs(wd4)
        run_reduce_task_net(reducef, 0, map_locs, workdir=wd4,
                            stats=piped, window=4)
        assert "net_overlap_s" not in serial
        assert piped["net_overlap_s"] > 0
        assert piped["net_fetch_wait_s"] >= 0
        assert piped["net_prefetch_window"] == 4
        with open(os.path.join(wd1, "mr-out-0"), "rb") as a, \
                open(os.path.join(wd4, "mr-out-0"), "rb") as b:
            assert a.read() == b.read()
    finally:
        for srv in servers:
            srv.close()


# ── adaptive fetch window (ISSUE 19 satellite) ─────────────────────────


def test_fetch_window_max_from_env(monkeypatch):
    from dsi_tpu.net.fetch import fetch_window_max_from_env

    monkeypatch.delenv("DSI_NET_FETCH_WINDOW_MAX", raising=False)
    assert fetch_window_max_from_env(4) == 4      # unset: widening off
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW_MAX", "16")
    assert fetch_window_max_from_env(4) == 16
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW_MAX", "2")
    assert fetch_window_max_from_env(4) == 4      # clamped >= window
    monkeypatch.setenv("DSI_NET_FETCH_WINDOW_MAX", "garbage")
    assert fetch_window_max_from_env(4) == 4      # malformed: off


def test_adaptive_window_widens_on_slow_peers(tmp_path):
    # slow producers (injected per-chunk serve latency) starve the
    # consumer → the wait-dominated pipeline widens toward the ceiling,
    # attributes the final width, and the bytes stay identical to the
    # window-1 serial loop (the parity-grid transitivity claim extends
    # to ANY widening schedule, because decode order is submission
    # order regardless of width)
    from dsi_tpu.mr.plugin import load_plugin

    _mapf, reducef = load_plugin("wc")
    map_locs, servers = _spool_partitions(tmp_path, n_maps=8)
    for srv in servers:
        srv._chunk_sleep_s = 0.05
    try:
        wd1 = str(tmp_path / "serial")
        os.makedirs(wd1)
        serial: dict = {}
        run_reduce_task_net(reducef, 0, map_locs, workdir=wd1,
                            stats=serial, window=1)
        assert serial["net_prefetch_window"] == 1
        wda = str(tmp_path / "adaptive")
        os.makedirs(wda)
        adaptive: dict = {}
        run_reduce_task_net(reducef, 0, map_locs, workdir=wda,
                            stats=adaptive, window=2, max_window=8)
        assert adaptive["net_prefetch_window"] > 2    # it widened
        assert adaptive["net_prefetch_window"] <= 8   # bounded
        with open(os.path.join(wd1, "mr-out-0"), "rb") as a, \
                open(os.path.join(wda, "mr-out-0"), "rb") as b:
            assert a.read() == b.read()
    finally:
        for srv in servers:
            srv.close()


def test_adaptive_window_off_at_ceiling_and_serial(tmp_path):
    # max_window == window → no widening no matter how slow the peers;
    # window 1 ignores any ceiling (serial stays the literal serial
    # loop, the parity grid's anchor)
    map_locs, servers = _spool_partitions(tmp_path, n_maps=4)
    for srv in servers:
        srv._chunk_sleep_s = 0.05
    try:
        items = [(m, map_locs[str(m)], f"mr-{m}-0") for m in range(4)]
        pipe = FetchPipeline(items, window=2, max_window=2)
        list(pipe)
        assert pipe.window_effective == 2
        stats: dict = {}
        pipe1 = FetchPipeline(items, window=1, max_window=8,
                              stats=stats)
        list(pipe1)
        assert pipe1.window_effective == 1
        assert stats["net_prefetch_window"] == 1
    finally:
        for srv in servers:
            srv.close()


# ── journal × net (satellite): replayed location registry ──────────────


def _drive_maps(c, addr_of):
    tasks = []
    while True:
        r = c.request_task({"WorkerId": "w", "Addr": addr_of(0)})
        if r["TaskStatus"] != TaskStatus.MAP:
            break
        tasks.append(r["CMap"])
    for m in tasks:
        c.map_complete({"TaskNumber": m, "Addr": addr_of(m),
                        "PartSizes": [100 * (m + 1)] * c.n_reduce})


def test_journal_replay_restores_map_locations(tmp_path):
    # the exact crash window the satellite names: every map committed
    # (and journaled), coordinator dies BEFORE any reduce dispatch —
    # the successor must re-learn where the partitions live or every
    # reducer starves
    jpath = str(tmp_path / "journal")
    cfg = JobConfig(n_reduce=1, net_shuffle=True, journal_path=jpath,
                    workdir=str(tmp_path))
    c1 = Coordinator(["in-0", "in-1"], 1, cfg)
    _drive_maps(c1, lambda m: f"tcp:10.0.0.{m}:5000")
    c1.close()

    c2 = Coordinator(["in-0", "in-1"], 1, cfg)
    try:
        r = c2.request_task({"WorkerId": "w2", "Addr": "tcp:10.0.0.9:1"})
        assert r["TaskStatus"] == TaskStatus.REDUCE and r["Net"] is True
        assert r["MapLocs"] == {"0": "tcp:10.0.0.0:5000",
                                "1": "tcp:10.0.0.1:5000"}
    finally:
        c2.close()


def test_journal_replay_restores_output_locations(tmp_path):
    jpath = str(tmp_path / "journal")
    cfg = JobConfig(n_reduce=1, net_shuffle=True, journal_path=jpath,
                    workdir=str(tmp_path))
    c1 = Coordinator(["in-0"], 1, cfg)
    _drive_maps(c1, lambda m: "tcp:h:1")
    r = c1.request_task({"WorkerId": "w", "Addr": "tcp:h:1"})
    c1.reduce_complete({"TaskNumber": r["CReduce"], "Addr": "tcp:h:1",
                        "Name": "mr-out-0", "Crc": 42})
    assert c1.done()
    c1.close()

    c2 = Coordinator(["in-0"], 1, cfg)
    try:
        assert c2.done()
        assert c2.output_locations() == {0: ("tcp:h:1", "mr-out-0", 42)}
        # and the replayed registry is only ADVISORY: a fetch failure
        # still resets the producer for re-execution (§3.4 convergence)
        assert c2.refetch_reduce(0) is True
        assert not c2.done()
    finally:
        c2.close()


# ── stage-payload codec (net-served plan relays) ───────────────────────


def test_pack_unpack_commit_round_trip():
    from dsi_tpu.plan.stagehost import pack_commit, unpack_commit

    arrays = {"a": np.arange(12, dtype=np.int64).reshape(3, 4),
              "b": np.array([1.5, -2.25])}
    meta = {"kind": "wordcount", "n": 3, "nested": {"k": [1, 2]}}
    blob = pack_commit(arrays, meta)
    got_arrays, got_meta = unpack_commit(blob)
    assert got_meta == meta
    assert sorted(got_arrays) == ["a", "b"]
    assert np.array_equal(got_arrays["a"], arrays["a"])
    assert np.array_equal(got_arrays["b"], arrays["b"])
    with pytest.raises(ValueError, match="not a stage payload"):
        unpack_commit(b"JUNK" + blob[4:])


# ── differential harness ───────────────────────────────────────────────


def _write_corpus(path, lines=1200, seed=7):
    import random

    rnd = random.Random(seed)
    vocab = ["".join(rnd.choice("abcdefgh") for _ in range(4))
             for _ in range(50)]
    with open(path, "w") as f:
        for _ in range(lines):
            f.write(" ".join(rnd.choice(vocab) for _ in range(8)) + "\n")


def _env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra or {})
    return env


def test_mrrun_net_journal_parity(tmp_path):
    # the satellite's headline: --net + --journal is a supported combo
    # now (the location registry is journaled), parity-gated end to end
    corpora = []
    for i in range(2):
        path = str(tmp_path / f"corpus-{i}.txt")
        _write_corpus(path, lines=800, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    os.makedirs(wd)
    stats_json = str(tmp_path / "stats.json")
    jpath = str(tmp_path / "journal")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", "2", "--nreduce", "3", "--workdir", wd,
           "--net", "--journal", jpath,
           "--check", "--stats-json", stats_json, "wc"] + corpora
    r = subprocess.run(cmd, env=_env(), cwd=REPO, capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    # the journal really carries the net location registry
    from dsi_tpu.mr.journal import Journal

    j = Journal(jpath, corpora, 3)
    done_maps, done_reduces = j.replay()
    assert sorted(done_maps) == [0, 1]
    assert sorted(done_reduces) == [0, 1, 2]
    assert set(j.map_locations) == {0, 1}
    assert all(a.startswith("tcp:") for a in j.map_locations.values())
    assert set(j.out_locations) == {0, 1, 2}


def test_partition_server_off_loopback_refused_without_secret(
        tmp_path, monkeypatch):
    monkeypatch.delenv("DSI_MR_SECRET", raising=False)
    with pytest.raises(ValueError, match="refusing to bind"):
        PartitionServer(str(tmp_path / "spool"),
                        bind="tcp:127.0.0.2:0")


def test_mrrun_net_off_loopback_with_hmac(tmp_path):
    # the CI auth-path exercise: a non-loopback bind (127.0.0.2 is off
    # the loopback allowlist but still locally routable) forces the
    # HMAC challenge on EVERY partition fetch — so the auth path runs
    # in tier-1, not only on multi-host fleets
    corpora = []
    for i in range(2):
        path = str(tmp_path / f"corpus-{i}.txt")
        _write_corpus(path, lines=800, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    os.makedirs(wd)
    stats_json = str(tmp_path / "stats.json")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", "2", "--nreduce", "3", "--workdir", wd,
           "--net", "--check", "--stats-json", stats_json,
           "wc"] + corpora
    r = subprocess.run(
        cmd, cwd=REPO, capture_output=True, text=True, timeout=240,
        env=_env({"DSI_NET_BIND": "tcp:127.0.0.2:0",
                  "DSI_MR_SECRET": "tier1-ci-secret"}))
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    # Every mr-out-* the driver collected came over the authenticated
    # wire (--check passed on them).  Whether the SHUFFLE crossed it is
    # a race (a worker that won both maps reads its own spool), so that
    # is asserted where every fetch is remote, below.
    assert s["net_fetches"] + s["net_local_reads"] > 0
    assert s["net_fetch_failures"] == 0


def test_net_remote_shuffle_off_loopback_with_hmac(tmp_path, monkeypatch):
    # the reducer's worker ran none of the maps, both servers bind off
    # the loopback allowlist: all 2 x 3 shuffle fetches answer the HMAC
    # challenge, and the same server turns a wrong secret away
    from tests.harness import net_job_split_roles, oracle_output

    monkeypatch.setenv("DSI_MR_SECRET", "tier1-ci-secret")
    corpora = []
    for i in range(2):
        path = str(tmp_path / f"corpus-{i}.txt")
        _write_corpus(path, lines=800, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    os.makedirs(wd)
    with net_job_split_roles("wc", corpora, wd, n_reduce=3,
                             bind="tcp:127.0.0.2:0") as job:
        assert job.producer.address.startswith("tcp:127.0.0.2:")
        assert job.consumer.address.startswith("tcp:127.0.0.2:")
        assert job.lines == oracle_output("wc", corpora, wd)
        assert job.stats["net_fetches"] == job.producer.served == 2 * 3
        assert job.stats["net_local_reads"] == 0
        assert job.stats["net_fetch_failures"] == 0
        with pytest.raises(rpc.AuthError):
            rpc.stream_fetch(job.producer.address, "Fetch",
                             {"Name": "mr-0-0"}, secret="wrong",
                             timeout=10.0)
        assert job.producer.served == 2 * 3  # nothing served unauthenticated


def test_planrun_hosts_parity_and_share_nothing_audit(tmp_path):
    corpus = str(tmp_path / "corpus.txt")
    _write_corpus(corpus, lines=2000)
    wd = str(tmp_path / "wd")
    stats_json = str(tmp_path / "stats.json")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.planrun",
           "--chain", "wc-topk", "--topk", "8", "--workdir", wd,
           "--hosts", "--check", "--stats-json", stats_json, corpus]
    r = subprocess.run(cmd, env=_env(), cwd=REPO, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK (hosts vs chained)" in r.stderr
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    assert s["plan_handoff"] == "net"
    # the inter-stage intermediate really crossed TCP, attributed
    assert s["plan_intermediate_bytes"] > 0
    assert s["net_fetches"] > 0
    # share-nothing: stage dirs cleaned up, no payload in the shared
    # workdir — only the report artifact remains
    left = sorted(os.listdir(wd))
    assert not [n for n in left if n.startswith("stage-")]
    assert not [n for n in left
                if n.startswith("plan-") and n[5:6].isdigit()]
    assert "plan-topk.json" in left
