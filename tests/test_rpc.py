"""RPC layer: framed-JSON over Unix socket, dial-per-call semantics."""

import os

import pytest

from dsi_tpu.mr import rpc


def test_roundtrip(tmp_path):
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Echo": lambda a: {"got": a}})
    srv.start()
    try:
        ok, reply = rpc.call(sock, "Echo", {"x": 1})
        assert ok and reply == {"got": {"x": 1}}
    finally:
        srv.close()


def test_unknown_method_returns_not_ok(tmp_path):
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {})
    srv.start()
    try:
        ok, reply = rpc.call(sock, "Nope", {})
        assert not ok and reply is None
    finally:
        srv.close()


def test_dial_failure_raises_coordinator_gone(tmp_path):
    # Reference worker log.Fatals when the coordinator socket is gone
    # (mr/worker.go:176-178); we surface it as an exception the loop
    # treats as job-over.
    with pytest.raises(rpc.CoordinatorGone):
        rpc.call(str(tmp_path / "missing"), "X", {})


def test_stale_socket_file_is_replaced(tmp_path):
    sock = str(tmp_path / "s")
    open(sock, "w").close()  # stale file; server must os.remove it first
    srv = rpc.RpcServer(sock, {"M": lambda a: {}})
    srv.start()
    try:
        ok, _ = rpc.call(sock, "M", {})
        assert ok
    finally:
        srv.close()


def test_concurrent_calls(tmp_path):
    import threading
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Inc": lambda a: {"v": a["v"] + 1}})
    srv.start()
    errs = []

    def hit(i):
        ok, r = rpc.call(sock, "Inc", {"v": i})
        if not ok or r["v"] != i + 1:
            errs.append(i)

    try:
        ts = [threading.Thread(target=hit, args=(i,)) for i in range(32)]
        for t in ts: t.start()
        for t in ts: t.join()
        assert not errs
    finally:
        srv.close()


def test_tcp_roundtrip():
    """TCP transport: the reference's commented-out multi-host variant
    (mr/coordinator.go:124, mr/worker.go:173) as a first-class address."""
    calls = []
    srv = rpc.RpcServer("tcp:127.0.0.1:0",
                        {"Echo": lambda a: (calls.append(a) or a)})
    srv.start()
    try:
        addr = srv.address
        assert addr.startswith("tcp:127.0.0.1:")
        ok, reply = rpc.call(addr, "Echo", {"x": 42})
        assert ok and reply == {"x": 42} and calls == [{"x": 42}]
        ok, reply = rpc.call(addr, "NoSuch", {})
        assert not ok
    finally:
        srv.close()


def test_tcp_dead_port_raises_coordinator_gone():
    import pytest as _pytest

    with _pytest.raises(rpc.CoordinatorGone):
        rpc.call("tcp:127.0.0.1:1", "Echo", {})


def test_tcp_end_to_end_job(tmp_path):
    """Full distributed job with the control plane on TCP."""
    import os as _os

    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.coordinator import make_coordinator
    from dsi_tpu.mr.plugin import load_plugin
    from dsi_tpu.mr.worker import worker_loop
    from dsi_tpu.utils.corpus import ensure_corpus
    from tests.harness import merged_output, oracle_output
    import threading
    import time as _time

    wd = str(tmp_path)
    files = ensure_corpus(_os.path.join(wd, "inputs"), n_files=3,
                          file_size=40_000)
    want = oracle_output("wc", files, wd)
    cfg = JobConfig(n_reduce=5, workdir=wd, socket_path="tcp:127.0.0.1:0",
                    wait_sleep_s=0.02)
    c = make_coordinator(files, 5, cfg)
    worker_cfg = JobConfig(n_reduce=5, workdir=wd,
                           socket_path=c.address(), wait_sleep_s=0.02)
    mapf, reducef = load_plugin("wc")
    try:
        ws = [threading.Thread(target=worker_loop,
                               args=(mapf, reducef, worker_cfg), daemon=True)
              for _ in range(2)]
        for w in ws:
            w.start()
        deadline = _time.time() + 60
        while not c.done():
            assert _time.time() < deadline
            _time.sleep(0.05)
        for w in ws:
            w.join(timeout=10)
    finally:
        c.close()
    assert merged_output(wd) == want


def test_malformed_tcp_address_is_coordinator_gone():
    import pytest as _pytest

    with _pytest.raises(rpc.CoordinatorGone):
        rpc.call("tcp:myhost", "Echo", {})  # operator typo: no port
    with _pytest.raises(ValueError):
        rpc.parse_address("tcp:")


def test_wildcard_bind_advertises_reachable_host():
    srv = rpc.RpcServer("tcp:0.0.0.0:0", {"Ping": lambda a: {}}, secret="t")
    srv.start()
    try:
        host = srv.address[4:].rpartition(":")[0]
        assert host not in ("0.0.0.0", "", "::")
        ok, _ = rpc.call(srv.address, "Ping", {}, secret="t")
        assert ok
    finally:
        srv.close()


def test_advertise_override(monkeypatch):
    monkeypatch.setenv("DSI_MR_ADVERTISE", "coord.example.net")
    srv = rpc.RpcServer("tcp:0.0.0.0:0", {"Ping": lambda a: {}}, secret="t")
    try:
        assert srv.address.startswith("tcp:coord.example.net:")
    finally:
        srv.close()


def test_tcp_wildcard_without_secret_refused(monkeypatch):
    """An open TCP listener accepts task-completion reports, so binding a
    non-loopback interface without DSI_MR_SECRET must fail loudly."""
    monkeypatch.delenv("DSI_MR_SECRET", raising=False)
    with pytest.raises(ValueError, match="DSI_MR_SECRET"):
        rpc.RpcServer("tcp:0.0.0.0:0", {"Ping": lambda a: {}})


def test_auth_token_enforced(tmp_path):
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Ping": lambda a: {"pong": 1}}, secret="hunter2")
    srv.start()
    try:
        # A rejected token is LOUD (AuthError), not a silent not-ok: a
        # misconfigured worker must not exit looking like end-of-job.
        with pytest.raises(rpc.AuthError):
            rpc.call(sock, "Ping", {}, secret="")  # no token
        with pytest.raises(rpc.AuthError):
            rpc.call(sock, "Ping", {}, secret="wrong")
        ok, reply = rpc.call(sock, "Ping", {}, secret="hunter2")
        assert ok and reply == {"pong": 1}
    finally:
        srv.close()


def test_auth_non_ascii_secret(tmp_path):
    """compare_digest(str, str) TypeErrors on non-ASCII; the comparison must
    be over utf-8 bytes so a passphrase secret can't crash the handler."""
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Ping": lambda a: {}}, secret="pässwörd")
    srv.start()
    try:
        ok, _ = rpc.call(sock, "Ping", {}, secret="pässwörd")
        assert ok
        with pytest.raises(rpc.AuthError):
            rpc.call(sock, "Ping", {}, secret="pässwörd2")
    finally:
        srv.close()


def test_auth_secret_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("DSI_MR_SECRET", "s3cret")
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Ping": lambda a: {}})  # picks up env
    srv.start()
    try:
        ok, _ = rpc.call(sock, "Ping", {})  # client picks up env too
        assert ok
        with pytest.raises(rpc.AuthError):
            rpc.call(sock, "Ping", {}, secret="wrong")
    finally:
        srv.close()


def test_auth_replay_rejected(tmp_path):
    """A captured authenticated frame re-sent verbatim must be rejected
   : the nonce is single-use inside the window."""
    import socket as _socket

    sock = str(tmp_path / "s")
    hits = []
    srv = rpc.RpcServer(sock, {"Ping": lambda a: hits.append(1) or {}},
                        secret="hunter2")
    srv.start()
    try:
        # Build one valid frame by hand, then send the identical bytes twice.
        body = rpc._canonical_body("Ping", {})
        nonce = "aa" * 16
        ts = repr(__import__("time").time())
        frame = {"method": "Ping", "args": {},
                 "auth": {"nonce": nonce, "ts": ts,
                          "mac": rpc._auth_mac("hunter2", nonce, ts, body)}}

        def send_raw():
            s = _socket.socket(_socket.AF_UNIX)
            s.connect(sock)
            try:
                rpc._send_frame(s, frame)
                return rpc._recv_frame(s)
            finally:
                s.close()

        first = send_raw()
        assert first["ok"] and hits == [1]
        replay = send_raw()
        assert not replay["ok"] and replay["error"] == "auth failed"
        assert hits == [1]  # the handler never ran for the replay
    finally:
        srv.close()


def test_auth_stale_timestamp_rejected(tmp_path):
    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Ping": lambda a: {}}, secret="hunter2")
    srv.start()
    try:
        import socket as _socket
        import time as _time

        body = rpc._canonical_body("Ping", {})
        nonce = "bb" * 16
        ts = repr(_time.time() - 3600)  # far outside the 300 s window
        frame = {"method": "Ping", "args": {},
                 "auth": {"nonce": nonce, "ts": ts,
                          "mac": rpc._auth_mac("hunter2", nonce, ts, body)}}
        s = _socket.socket(_socket.AF_UNIX)
        s.connect(sock)
        try:
            rpc._send_frame(s, frame)
            resp = rpc._recv_frame(s)
        finally:
            s.close()
        assert not resp["ok"] and resp["error"] == "auth failed"
    finally:
        srv.close()


def test_dial_retry_survives_late_listener(tmp_path):
    """A transient ECONNREFUSED (listener mid-restart) must be retried, not
    mistaken for a dead coordinator — losing a worker to a transient dial
    error silently shrinks the fleet."""
    import socket as _socket
    import threading as _threading

    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    addr = f"tcp:127.0.0.1:{port}"
    holder = {}

    def late_start():
        import time as _time
        _time.sleep(0.2)
        holder["srv"] = rpc.RpcServer(addr, {"Ping": lambda a: {"ok": 1}})
        holder["srv"].start()

    t = _threading.Thread(target=late_start)
    t.start()
    try:
        # One outer retry: on a heavily loaded box the late_start thread can
        # itself be delayed past the ~1.6 s dial-retry budget; the property
        # under test is that call() rides out ECONNREFUSED, not the exact
        # size of the budget.
        try:
            ok, reply = rpc.call(addr, "Ping", {})
        except rpc.CoordinatorGone:
            t.join()
            ok, reply = rpc.call(addr, "Ping", {})
        assert ok and reply == {"ok": 1}
    finally:
        t.join()
        srv = holder.get("srv")
        if srv is not None:
            srv.close()


def test_high_contention_soak(tmp_path):
    """32 threads x 50 dial-per-call RPCs against one server: with the Go-
    parity 128 listener backlog and transient-dial retry, not one call may
    die with CoordinatorGone (the round-1 stress test tripped exactly this
    with backlog 5 and no retry)."""
    import threading

    sock = str(tmp_path / "s")
    srv = rpc.RpcServer(sock, {"Inc": lambda a: {"v": a["v"] + 1}})
    srv.start()
    errs: list = []

    def hammer(tid):
        try:
            for i in range(50):
                ok, r = rpc.call(sock, "Inc", {"v": i})
                if not ok or r["v"] != i + 1:
                    errs.append((tid, i, "bad reply"))
        except Exception as e:  # noqa: BLE001 — any escape is the failure
            errs.append((tid, repr(e)))

    try:
        ts = [threading.Thread(target=hammer, args=(t,)) for t in range(32)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs[:5]
    finally:
        srv.close()


def test_silent_peer_does_not_pin_handler_threads():
    """A connected-but-mute TCP peer must be timed out server-side."""
    import socket as _socket
    import threading as _threading

    srv = rpc.RpcServer("tcp:127.0.0.1:0", {"Ping": lambda a: {}})
    srv.start()
    try:
        mute = _socket.create_connection(
            tuple(rpc.parse_address(srv.address)[1]))
        # server still serves real clients while the mute peer idles
        ok, _ = rpc.call(srv.address, "Ping", {})
        assert ok
        mute.close()
        before = _threading.active_count()
        assert before < 50  # no thread pile-up
    finally:
        srv.close()


def test_non_object_response_frame_is_rpc_failure():
    """A server answering with a JSON array (corrupt or hostile) must yield
    (False, None) — the reference's ok=false path (worker.go:186-188) — not
    an AttributeError that kills the worker loop."""
    import socket
    import struct
    import threading

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def serve_one():
        conn, _ = srv.accept()
        with conn:
            payload = b"[1, 2, 3]"
            conn.recv(1 << 16)  # drain the request
            conn.sendall(struct.pack(">I", len(payload)) + payload)

    t = threading.Thread(target=serve_one, daemon=True)
    t.start()
    try:
        ok, reply = rpc.call(f"tcp:127.0.0.1:{port}", "Echo", {})
        assert (ok, reply) == (False, None)
    finally:
        t.join(timeout=5)
        srv.close()
