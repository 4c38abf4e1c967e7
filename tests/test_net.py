"""Network data plane (ISSUE 17): worker-served shuffle over TCP.

Layers, cheapest first:

* stream-transport units — chunked fetch round-trip with CRC trailers,
  the eager-hello version gate (``ProtocolMismatch``, distinct from
  connection-refused), auth, the open-bind refusal;
* KV line-codec units — ``pack_kv``/``unpack_kv`` round-trip on every
  edge shape, and real compression on shuffle-shaped payloads;
* partition-server units — spool hygiene at boot (``reap_spool``),
  basename-only fetch surface, put/fetch round-trip through the codec
  flag, local-read short-circuit, attribution;
* coordinator units — the §3.1 location registry forwarded to
  reducers, locality-aware placement (biggest byte share wins,
  ``locality_hits``), §3.4 map re-execution on ``FetchFailed``, and the
  driver-side ``refetch_reduce``/``refetch_shard`` surface;
* the differential harness — real ``mrrun --net`` / ``shardrun
  --hosts`` fleets with per-process PRIVATE workdirs over localhost
  TCP, byte-identical to the sequential oracle; a job whose every
  shuffle fetch is remote by construction (``tests/harness.py``), for
  what only a fetch that crossed the wire can show; and the fetch-failure
  chaos arm: a real ``os._exit`` while SERVING (mid-serve) — the
  producer is re-executed, every shard still commits exactly once
  (zero duplicate commits), and parity holds.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from dsi_tpu.config import JobConfig
from dsi_tpu.mr import rpc
from dsi_tpu.mr.coordinator import Coordinator
from dsi_tpu.mr.types import TaskStatus
from dsi_tpu.net import PartitionServer
from dsi_tpu.net.fetch import FetchFailure, fetch_partition
from dsi_tpu.net.partsrv import CODEC_KV, CODEC_RAW, reap_spool
from dsi_tpu.ops.wirecodec import pack_kv, unpack_kv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def kv_corpus(rows=200) -> bytes:
    # shuffle-shaped: few distinct lines, many repeats — the case the
    # line-dictionary codec exists for
    lines = [b'{"Key":"alpha","Value":"1"}', b'{"Key":"beta","Value":"1"}',
             b'{"Key":"gamma","Value":"1"}']
    return b"\n".join(lines[i % 3] for i in range(rows)) + b"\n"


# ── stream transport ───────────────────────────────────────────────────


def test_stream_fetch_roundtrip_multichunk():
    payload = os.urandom(300_000)  # > default chunk, incompressible
    srv = rpc.StreamServer("tcp:127.0.0.1:0",
                           {"Blob": lambda args: payload},
                           chunk_size=4096)
    srv.start()
    try:
        got = rpc.stream_fetch(srv.address, "Blob", timeout=10.0)
        assert got == payload
    finally:
        srv.close()


def test_stream_fetch_server_side_error_is_stream_error():
    def boom(args):
        raise FileNotFoundError("no such partition")

    srv = rpc.StreamServer("tcp:127.0.0.1:0", {"Fetch": boom})
    srv.start()
    try:
        with pytest.raises(rpc.StreamError, match="no such partition"):
            rpc.stream_fetch(srv.address, "Fetch", timeout=10.0)
        with pytest.raises(rpc.StreamError, match="no such method"):
            rpc.stream_fetch(srv.address, "Nope", timeout=10.0)
    finally:
        srv.close()


def test_connection_refused_is_not_protocol_mismatch():
    # distinct failure taxonomy (satellite): a dead server reads as
    # CoordinatorGone (re-fetch from a replacement), NEVER as the fatal
    # mixed-version diagnosis
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()  # nothing listens here now
    with pytest.raises(rpc.CoordinatorGone) as ei:
        rpc.stream_fetch(f"tcp:127.0.0.1:{port}", "Fetch", timeout=2.0)
    assert not isinstance(ei.value, rpc.ProtocolMismatch)


def _one_shot_hello_server(hello: bytes):
    """A fake peer that sends ``hello`` and closes — the mixed-version
    / not-a-stream-server cases."""
    ls = socket.socket()
    ls.bind(("127.0.0.1", 0))
    ls.listen(1)

    def serve():
        conn, _ = ls.accept()
        conn.sendall(hello)
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return ls, ls.getsockname()[1]


def test_version_mismatch_is_loud():
    wrong = bytes((rpc.PROTOCOL_VERSION + 1,))
    ls, port = _one_shot_hello_server(b"DSN" + wrong)
    try:
        with pytest.raises(rpc.ProtocolMismatch, match="upgrade in "
                                                       "lockstep"):
            rpc.stream_fetch(f"tcp:127.0.0.1:{port}", "Fetch",
                             timeout=5.0)
    finally:
        ls.close()


def test_non_stream_peer_is_protocol_mismatch():
    ls, port = _one_shot_hello_server(b"HTTP")
    try:
        with pytest.raises(rpc.ProtocolMismatch):
            rpc.stream_fetch(f"tcp:127.0.0.1:{port}", "Fetch",
                             timeout=5.0)
    finally:
        ls.close()


def test_stream_auth_round_trip_and_rejection():
    srv = rpc.StreamServer("tcp:127.0.0.1:0",
                           {"Blob": lambda args: b"payload"},
                           secret="hunter2")
    srv.start()
    try:
        assert rpc.stream_fetch(srv.address, "Blob", secret="hunter2",
                                timeout=10.0) == b"payload"
        with pytest.raises(rpc.AuthError):
            rpc.stream_fetch(srv.address, "Blob", secret="wrong",
                             timeout=10.0)
    finally:
        srv.close()


def test_open_bind_without_secret_refused():
    with pytest.raises(ValueError, match="refusing to bind"):
        rpc.StreamServer("tcp:0.0.0.0:0", {"Blob": lambda a: b""})


# ── KV line codec ──────────────────────────────────────────────────────


@pytest.mark.parametrize("raw", [
    b"",
    b"\n",
    b"one line no newline",
    b"one line\n",
    b"a\nb\na\nb\na\n",
    b"trailing\nblank\n\n\nlines\n",
    kv_corpus(64),
    "unicodé line\n".encode(),
])
def test_pack_kv_round_trips(raw):
    assert unpack_kv(pack_kv(raw)) == raw


def test_pack_kv_compresses_shuffle_shape():
    raw = kv_corpus(rows=2000)
    packed = pack_kv(raw)
    assert len(packed) < len(raw) / 2  # 3 distinct lines, 2000 rows
    assert unpack_kv(packed) == raw


# ── partition server ───────────────────────────────────────────────────


def test_reap_spool_boot_hygiene(tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    with open(os.path.join(spool, ".tmp-orphan"), "wb") as f:
        f.write(b"torn write")
    old = os.path.join(spool, "mr-0-0")
    with open(old, "wb") as f:
        f.write(b"dead task's bytes")
    past = time.time() - 7200
    os.utime(old, (past, past))
    with open(os.path.join(spool, "mr-1-0"), "wb") as f:
        f.write(b"live bytes")
    reaped, aged = reap_spool(spool, retention_s=3600.0)
    assert (reaped, aged) == (1, 1)
    assert sorted(os.listdir(spool)) == ["mr-1-0"]


def test_path_of_rejects_escapes(tmp_path):
    ps = PartitionServer(str(tmp_path / "spool"))
    for bad in ("", "../etc/passwd", "a/b", ".tmp-x", ".hidden"):
        with pytest.raises(ValueError):
            ps.path_of(bad)


def test_put_fetch_round_trip_with_attribution(tmp_path):
    ps = PartitionServer(str(tmp_path / "spool"))
    ps.start()
    try:
        raw = kv_corpus(rows=500)
        ps.put("mr-0-1", raw)
        stats: dict = {}
        got = fetch_partition(ps.address, "mr-0-1", stats=stats,
                              timeout=10.0)
        assert got == raw
        assert stats["net_fetches"] == 1
        assert stats["net_bytes_raw"] == len(raw)
        # shuffle-shaped payload really crossed the wire packed
        assert stats["net_bytes_wire"] < len(raw)
        assert stats["net_ratio"] > 1.5
    finally:
        ps.close()


def test_incompressible_payload_ships_raw_flag(tmp_path):
    ps = PartitionServer(str(tmp_path / "spool"))
    ps.start()
    try:
        raw = os.urandom(4096)
        ps.put("blob", raw)
        assert fetch_partition(ps.address, "blob", timeout=10.0) == raw
        # server-side codec decision: packed only when smaller
        assert ps._fetch({"Name": "blob"})[:1] == CODEC_RAW
        ps.put("kv", kv_corpus())
        assert ps._fetch({"Name": "kv"})[:1] == CODEC_KV
    finally:
        ps.close()


def test_local_read_short_circuit(tmp_path):
    spool = str(tmp_path / "spool")
    ps = PartitionServer(spool)  # never started: a socket would fail
    raw = b"my own bytes\n"
    ps.put("mr-2-3", raw)
    stats: dict = {}
    got = fetch_partition(ps.address, "mr-2-3", stats=stats,
                          own_addr=ps.address, local_root=spool)
    assert got == raw
    assert stats == {"net_local_reads": 1}


def test_missing_partition_is_fetch_failure(tmp_path):
    ps = PartitionServer(str(tmp_path / "spool"))
    ps.start()
    try:
        stats: dict = {}
        with pytest.raises(FetchFailure):
            fetch_partition(ps.address, "mr-9-9", stats=stats,
                            timeout=5.0)
        assert stats["net_fetch_failures"] == 1
    finally:
        ps.close()


# ── coordinator: locations, locality, re-execution ─────────────────────


def mk_net(files=2, n_reduce=2):
    return Coordinator([f"in-{i}" for i in range(files)], n_reduce,
                       JobConfig(n_reduce=n_reduce, net_shuffle=True))


def run_maps(c, addr_of):
    """Drive every map to completion WITHOUT consuming a reduce
    assignment; ``addr_of(m)`` is the serving address for map m."""
    tasks = []
    while True:
        r = c.request_task({"WorkerId": "w", "Addr": addr_of(0)})
        if r["TaskStatus"] != TaskStatus.MAP:
            break  # WAITING: every map assigned, none complete yet
        tasks.append(r["CMap"])
    for m in tasks:
        c.map_complete({"TaskNumber": m, "Addr": addr_of(m),
                        "PartSizes": [100] * c.n_reduce})


def test_map_locations_forwarded_to_reducers():
    # §3.1: "the master ... forwards these locations to the reduce
    # workers" — the reduce assignment carries the full registry
    c = mk_net(files=2, n_reduce=1)
    run_maps(c, lambda m: f"tcp:10.0.0.{m}:5000")
    r = c.request_task({"WorkerId": "w", "Addr": "tcp:10.0.0.9:5000"})
    assert r["TaskStatus"] == TaskStatus.REDUCE and r["Net"] is True
    assert r["MapLocs"] == {"0": "tcp:10.0.0.0:5000",
                            "1": "tcp:10.0.0.1:5000"}


def test_locality_placement_prefers_biggest_share():
    c = mk_net(files=2, n_reduce=2)
    a, b = "tcp:hostA:1", "tcp:hostB:1"
    r0 = c.request_task({"WorkerId": "wa", "Addr": a})
    r1 = c.request_task({"WorkerId": "wb", "Addr": b})
    assert {r0["CMap"], r1["CMap"]} == {0, 1}
    # map0 (on A) holds almost all of reduce 1; map1 (on B) almost all
    # of reduce 0 — each host should be handed ITS big partition
    sizes = {r0["CMap"]: [10, 9000], r1["CMap"]: [9000, 10]}
    c.map_complete({"TaskNumber": r0["CMap"], "Addr": a,
                    "PartSizes": sizes[r0["CMap"]]})
    c.map_complete({"TaskNumber": r1["CMap"], "Addr": b,
                    "PartSizes": sizes[r1["CMap"]]})
    got_b = c.request_task({"WorkerId": "wb", "Addr": b})
    got_a = c.request_task({"WorkerId": "wa", "Addr": a})
    assert got_b["TaskStatus"] == got_a["TaskStatus"] == TaskStatus.REDUCE
    assert got_b["CReduce"] == 0 and got_a["CReduce"] == 1
    assert c.net_stats()["locality_hits"] == 2


def test_fetch_failed_reexecutes_map():
    # §3.4: "map tasks executed by the failed worker are re-executed
    # ... since their output is stored on the local disk"
    c = mk_net(files=2, n_reduce=1)
    run_maps(c, lambda m: f"tcp:10.0.0.{m}:5000")
    r = c.request_task({"WorkerId": "wr", "Addr": "tcp:10.0.0.7:1"})
    assert r["TaskStatus"] == TaskStatus.REDUCE
    out = c.fetch_failed({"Map": 0, "Reduce": r["CReduce"],
                          "WorkerId": "wr", "Addr": "tcp:10.0.0.0:5000"})
    assert out["Requeued"] is True
    # barrier re-engaged: the next request is map 0 again, not WAITING
    nxt = c.request_task({"WorkerId": "wx", "Addr": "tcp:10.0.0.8:1"})
    assert nxt["TaskStatus"] == TaskStatus.MAP and nxt["CMap"] == 0
    c.map_complete({"TaskNumber": 0, "Addr": "tcp:10.0.0.8:1",
                    "PartSizes": [100]})
    again = c.request_task({"WorkerId": "wr", "Addr": "tcp:10.0.0.7:1"})
    assert again["TaskStatus"] == TaskStatus.REDUCE
    # the replacement's address replaced the dead one in the registry
    assert again["MapLocs"]["0"] == "tcp:10.0.0.8:1"
    s = c.net_stats()
    assert s["net_refetches"] == 1 and s["net_fetch_failures"] == 1


def test_refetch_reduce_forgets_completion():
    c = mk_net(files=1, n_reduce=1)
    run_maps(c, lambda m: "tcp:h:1")
    r = c.request_task({"WorkerId": "w", "Addr": "tcp:h:1"})
    c.reduce_complete({"TaskNumber": r["CReduce"], "Addr": "tcp:h:1",
                       "Name": "mr-out-0", "Crc": 7})
    assert c.done()
    assert c.output_locations() == {0: ("tcp:h:1", "mr-out-0", 7)}
    assert c.refetch_reduce(0) is True
    assert not c.done() and c.output_locations() == {}
    assert c.refetch_reduce(0) is False  # no longer completed


# ── the differential harness (real fleets, private workdirs) ───────────


def write_corpus(path, lines=3000, seed=7):
    import random

    rnd = random.Random(seed)
    vocab = ["".join(rnd.choice("abcdefgh") for _ in range(4))
             for _ in range(50)]
    with open(path, "w") as f:
        for _ in range(lines):
            f.write(" ".join(rnd.choice(vocab) for _ in range(8)) + "\n")


def _env(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def test_mrrun_net_parity(tmp_path):
    # Which worker wins which map is a race: when one wins all three,
    # locality placement hands it every reduce and the whole shuffle is
    # local reads.  So this fleet is held to parity and to having
    # shuffled at all; what a remote fetch alone shows is asserted where
    # every fetch is remote (test_net_remote_shuffle_is_packed).
    corpora = []
    for i in range(3):
        path = str(tmp_path / f"corpus-{i}.txt")
        write_corpus(path, lines=1500, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    os.makedirs(wd)
    stats_json = str(tmp_path / "stats.json")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", "2", "--nreduce", "4", "--workdir", wd,
           "--net", "--check", "--stats-json", stats_json,
           "wc"] + corpora
    r = subprocess.run(cmd, env=_env(tmp_path), cwd=REPO,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    assert s["net_fetches"] + s["net_local_reads"] > 0
    assert s["net_fetch_failures"] == 0 and s["net_refetches"] == 0
    # share-nothing: private spools were cleaned up, only outputs stay
    left = sorted(os.listdir(wd))
    assert not [n for n in left if n.startswith("worker-")]
    assert not [n for n in left
                if n.startswith("mr-")
                and not n.startswith(("mr-out-", "mr-correct"))]


def test_net_remote_shuffle_is_packed(tmp_path):
    # the reducer's worker ran none of the maps: all 3 x 4 partitions
    # cross the wire, through the KV codec
    from tests.harness import net_job_split_roles, oracle_output

    corpora = []
    for i in range(3):
        path = str(tmp_path / f"corpus-{i}.txt")
        write_corpus(path, lines=1500, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    os.makedirs(wd)
    with net_job_split_roles("wc", corpora, wd, n_reduce=4) as job:
        s = job.stats
        assert job.lines == oracle_output("wc", corpora, wd)
        assert s["net_fetches"] == job.producer.served == 3 * 4
        assert s["net_local_reads"] == 0
        assert s["net_bytes_raw"] > s["net_bytes_wire"] > 0
        assert s["net_ratio"] > 1.5  # shuffle crossed the wire packed
        assert s["net_fetch_failures"] == 0 and s["net_refetches"] == 0


def test_shardrun_hosts_parity(tmp_path):
    corpus = str(tmp_path / "corpus.txt")
    write_corpus(corpus)
    wd = str(tmp_path / "wd")
    stats_json = str(tmp_path / "stats.json")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.shardrun",
           "--engine", "wordcount", "--workers", "2", "--shards", "4",
           "--workdir", wd, "--hosts", "--progress-s", "0.1",
           "--shard-timeout", "5",
           "--check", "--stats-json", stats_json, corpus]
    r = subprocess.run(cmd, env=_env(tmp_path), cwd=REPO,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    assert s["commits"] == s["shards"] == 4
    assert s["duplicate_commits"] == 0
    assert s["net_fetches"] == 4  # the driver pulled every shard output
    assert s["net_fetch_failures"] == 0
    # share-nothing: no worker artifact in the shared dir, spools reaped
    left = sorted(os.listdir(wd))
    assert not [n for n in left if n.startswith("worker-")]
    assert not [n for n in left if n.endswith(".part") or n == ".shards"]


def test_fetch_failure_chaos_reexecutes_producer(tmp_path):
    """The satellite chaos arm: worker 0 takes a REAL ``os._exit``
    while serving its first committed output (mid-serve, half the
    payload on the wire).  The driver's fetch fails, the coordinator
    forgets the commit and a replacement re-executes the producer —
    exactly one WINNING attempt per shard, zero duplicate commits, and
    the merged output is still byte-identical to the oracle.  Runs with
    an explicit prefetch window of 4 (ISSUE 18): the chaos converges
    under the pipeline too, with the same exactly-once guarantees."""
    corpus = str(tmp_path / "corpus.txt")
    write_corpus(corpus)
    wd = str(tmp_path / "wd")
    stats_json = str(tmp_path / "stats.json")
    cmd = [sys.executable, "-m", "dsi_tpu.cli.shardrun",
           "--engine", "wordcount", "--workers", "2", "--shards", "4",
           "--workdir", wd, "--hosts", "--progress-s", "0.1",
           "--shard-timeout", "5",
           "--fault-worker", "0:mid-serve",
           "--check", "--stats-json", stats_json, corpus]
    env = _env(tmp_path)
    env["DSI_NET_FETCH_WINDOW"] = "4"
    r = subprocess.run(cmd, env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    assert "FAULT: injected crash at mid-serve" in r.stderr
    assert "re-executing" in r.stderr  # the refetch path, loudly
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    assert s["net_fetch_failures"] >= 1
    assert s["net_refetches"] >= 1
    assert s["net_prefetch_window"] == 4  # the pipeline really ran
    assert s["duplicate_commits"] == 0
    # re-execution, not duplication: each shard has exactly one WINNER
    assert s["committed"] == s["shards"] == 4
    assert len(s["winning_attempts"]) == 4
    # total commits may exceed shards (the lost copy was re-committed)
    assert s["commits"] >= 4


@pytest.mark.slow
def test_mrrun_net_chaos_every_worker_dies_serving(tmp_path):
    """Classic-plane chaos: EVERY initial worker dies the first time it
    serves a partition (deterministic mid-serve fault).  Reducers hit
    FetchFailure, the coordinator re-executes the producer maps on
    clean respawns, and parity still holds."""
    corpora = []
    for i in range(3):
        path = str(tmp_path / f"corpus-{i}.txt")
        write_corpus(path, lines=1500, seed=i)
        corpora.append(path)
    wd = str(tmp_path / "wd")
    stats_json = str(tmp_path / "stats.json")
    env = _env(tmp_path)
    env["DSI_FAULT_POINT"] = "mid-serve"
    cmd = [sys.executable, "-m", "dsi_tpu.cli.mrrun",
           "--workers", "2", "--nreduce", "4", "--workdir", wd,
           "--net", "--check", "--stats-json", stats_json,
           "wc"] + corpora
    r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stderr[-3000:]}"
    assert "parity OK" in r.stderr
    assert "FAULT: injected crash at mid-serve" in r.stderr
    assert "re-executing map" in r.stderr
    with open(stats_json, encoding="utf-8") as f:
        s = json.load(f)
    assert s["net_fetch_failures"] >= 1 and s["net_refetches"] >= 1
    assert s["workers_spawned"] > 2  # replacements really spawned
