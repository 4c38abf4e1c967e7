"""Serving-QoS tests (ISSUE 19): priority admission, load shedding,
rate limits, tail-driven eviction, and the packed grep lanes.

Two layers, the qos.py discipline:

* deterministic units — injected clocks, injected histograms, stubbed
  residents, monkeypatched RPC — no daemon scheduler, no sleeps, no
  wall-clock races;
* end-to-end integration on the in-process daemon — priority ordering
  observable in ``done_ts``, packed-grep byte parity vs the host
  oracle (literal, non-literal/hostpath, short-line, and evict/resume
  arms), the packing evidence in ``grep_packer.stats``;
* the ``slow``-marked soak — ``scripts/serve_soak.run_soak(1000)``,
  the acceptance bar's thousands-of-tenants churn.
"""

import json
import os
import sys
import tempfile

import pytest

jax = pytest.importorskip("jax")

from dsi_tpu.serve import client, qos
from dsi_tpu.serve.daemon import ServeDaemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def short_sock() -> str:
    # AF_UNIX paths cap at ~108 bytes; pytest tmp dirs can exceed it.
    return os.path.join(tempfile.mkdtemp(prefix="dsi-qos-"), "s.sock")


def grep_oracle_bytes(path: str, pattern: str) -> bytes:
    """grep.json ground truth: grep_host_oracle serialized exactly as
    ServeDaemon._write_grep_result spells it."""
    from dsi_tpu.parallel.grepstream import grep_host_oracle

    with open(path, "rb") as f:
        r = grep_host_oracle([f.read()], pattern)
    return json.dumps(
        {"lines": r.lines, "matched": r.matched,
         "occurrences": r.occurrences, "hist": list(r.hist),
         "topk": [list(t) for t in r.topk]},
        sort_keys=True).encode("utf-8")


def grep_corpus(path: str, pat: str, n_lines: int = 400,
                line_fill: str = " fill") -> str:
    with open(path, "w") as f:
        for j in range(n_lines):
            f.write((pat + " ") * (j % 4) + f"x{j % 13}{line_fill}\n")
    return path


# ── units: the policy objects, injected clocks ──


def test_priority_queue_strict_order_and_lanes():
    q = qos.PriorityQueue()
    q.push("b1", 2)
    q.push("d1", 1)
    q.push("a1", 0)
    q.push("d2", 1)
    q.push("a2", 0)
    assert len(q) == 5 and "d2" in q
    assert q.depths() == (2, 2, 1)
    assert list(q) == ["a1", "a2", "d1", "d2", "b1"]
    # push_front re-queues at the head of the job's OWN lane only —
    # a parked batch job must not cut ahead of the interactive lane.
    q.push_front("b0", 2)
    assert list(q) == ["a1", "a2", "d1", "d2", "b0", "b1"]
    assert [q.pop() for _ in range(6)] == \
        ["a1", "a2", "d1", "d2", "b0", "b1"]
    assert q.pop() is None
    q.push("x", 7)       # out-of-range priorities clamp, never KeyError
    q.push("y", -3)
    assert q.depths() == (1, 0, 1)
    assert q.remove("x") and not q.remove("x")


def test_token_bucket_injected_clock():
    now = [100.0]
    b = qos.TokenBucket(rate=2.0, burst=2, clock=lambda: now[0])
    assert b.take() == 0.0 and b.take() == 0.0   # burst admits
    hint = b.take()                              # empty: a real hint
    assert hint == pytest.approx(0.5, abs=0.01)  # 1 token / 2 per s
    now[0] += 0.5                                # one token accrues
    assert b.take() == 0.0
    assert b.take() > 0.0
    shut = qos.TokenBucket(rate=0.0, burst=1, clock=lambda: now[0])
    assert shut.take() == 0.0                    # the burst token
    assert shut.take() == 60.0                   # rate 0: long hint
    rep = qos.backpressure_reply("full", hint)
    assert rep["error_type"] == "backpressure" and rep["retryable"]
    assert rep["retry_after_s"] == pytest.approx(hint, abs=0.001)


def test_submit_shed_at_queue_bound(tmp_path):
    """max_queue=1 on a never-started daemon: the second submission is
    SHED with the typed reply and no journal entry."""
    corpus = grep_corpus(str(tmp_path / "c.txt"), "abc")
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    warm=False, max_queue=1)
    try:
        ok = d._rpc_submit({"tenant": "t0", "app": "wc",
                            "files": [corpus]})
        assert "job_id" in ok
        shed = d._rpc_submit({"tenant": "t1", "app": "wc",
                              "files": [corpus]})
        assert shed["error_type"] == "backpressure"
        assert shed["retry_after_s"] >= 0.2
        assert d._qos["shed"] == 1
        # The shed submission left NO spool state (zero-lost counts
        # accepted acks only).
        assert len([f for f in os.listdir(d.jobs_dir)
                    if f.endswith(".json")]) == 1
    finally:
        d._rpc.close()


def test_submit_rate_limit_injected_clock(tmp_path):
    corpus = grep_corpus(str(tmp_path / "c.txt"), "abc")
    now = [50.0]
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    warm=False, rate_limit=1.0, rate_burst=1,
                    clock=lambda: now[0])
    try:
        sub = {"tenant": "rl", "app": "wc", "files": [corpus]}
        assert "job_id" in d._rpc_submit(sub)
        rep = d._rpc_submit(sub)
        assert rep["error_type"] == "backpressure"
        assert 0.0 < rep["retry_after_s"] <= 1.0
        assert d._qos["rate_limited"] == 1
        # A different tenant has its own bucket.
        assert "job_id" in d._rpc_submit({"tenant": "other",
                                          "app": "wc",
                                          "files": [corpus]})
        now[0] += 1.0                        # one token accrues
        assert "job_id" in d._rpc_submit(sub)
    finally:
        d._rpc.close()


def test_client_honors_retry_after_hint(monkeypatch):
    """ServeBusy carries the daemon's hint; submit(retries=) sleeps
    hint x jitter (bounded) and retries; exhaustion re-raises."""
    busy = (True, qos.backpressure_reply("queue full", 1.0))
    replies = [busy, busy, (True, {"job_id": "j-000001"})]
    calls = []

    def fake_call(sock, method, args, timeout=30.0):
        calls.append(method)
        return replies[len(calls) - 1]

    slept = []
    monkeypatch.setattr(client, "call", fake_call)
    rep = client.submit("/nowhere.sock", "t", [__file__], retries=2,
                        sleep=slept.append, rng=lambda: 0.25)
    assert rep["job_id"] == "j-000001" and len(calls) == 3
    # jitter = 0.5 + rng() = 0.75, hint = 1.0 → both sleeps 0.75s.
    assert slept == [pytest.approx(0.75), pytest.approx(0.75)]
    calls.clear()
    replies[:] = [busy, busy]
    with pytest.raises(client.ServeBusy) as ei:
        client.submit("/nowhere.sock", "t", [__file__], retries=1,
                      sleep=slept.append, rng=lambda: 0.0)
    assert ei.value.retry_after_s == pytest.approx(1.0)
    assert len(calls) == 2                   # retries=1 → 2 attempts


class _StubLane:
    def __init__(self, steps: int):
        self.steps_since_resume = steps
        self.suspended = False

    def suspend(self):
        self.suspended = True


def _stub_resident(d: ServeDaemon, jid: str, tenant: str,
                   steps: int) -> _StubLane:
    lane = _StubLane(steps)
    d._jobs[jid] = {"job_id": jid, "tenant": tenant, "app": "wc",
                    "files": [], "n_reduce": 10,
                    "out_dir": os.path.join(d.out_dir, jid),
                    "pattern": None, "priority": 1, "state": "running",
                    "submitted_ts": 0.0, "done_ts": None,
                    "error": None, "stats": {}}
    d._resident[jid] = {"kind": "wc", "lane": lane}
    return lane


def test_evict_one_picks_worst_p99_tail(tmp_path):
    """Tail-driven eviction: among residents past min residency, the
    victim is the tenant whose p99 packed-step wall is worst — not the
    one furthest past quota."""
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    warm=False, quota_steps=4, evict_min_samples=3)
    try:
        _stub_resident(d, "fast-000001", "fast", steps=9)
        slow = _stub_resident(d, "slow-000002", "slow", steps=5)
        for _ in range(3):
            d._hist.record("fast", 0.001)
            d._hist.record("slow", 0.5)      # the pack-hurting tail
        with d._wake:
            d._evict_one()
        assert slow.suspended
        assert "slow-000002" not in d._resident
        assert d._jobs["slow-000002"]["state"] == "parked"
        assert "slow-000002" in d._queue
        assert d._qos["evict_p99"] == 1 and d._qos["evict_quota"] == 0
        assert d._tenants["slow"]["evictions"] == 1
    finally:
        d._rpc.close()


def test_evict_one_quota_fallback_without_tails(tmp_path):
    """No resident has a meaningful histogram yet → the original
    furthest-past-quota rule decides, counted separately."""
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    warm=False, quota_steps=2, evict_min_samples=3)
    try:
        _stub_resident(d, "a-000001", "a", steps=3)
        far = _stub_resident(d, "b-000002", "b", steps=7)
        with d._wake:
            d._evict_one()
        assert far.suspended and "b-000002" not in d._resident
        assert d._qos["evict_quota"] == 1 and d._qos["evict_p99"] == 0
        # Fresh residents under quota are never victims.
        d._resident.clear()
        _stub_resident(d, "c-000003", "c", steps=1)
        with d._wake:
            d._evict_one()
        assert "c-000003" in d._resident
    finally:
        d._rpc.close()


def test_metrics_and_statusz_bounded_by_tenant_cap(tmp_path):
    """metrics_tenants caps the per-tenant series and the statusz
    table regardless of how many tenants exist; worst-p99 tenants win
    the slots."""
    d = ServeDaemon(str(tmp_path / "spool"), socket_path=short_sock(),
                    warm=False, metrics_tenants=2)
    try:
        for i in range(5):
            d._tenant(f"m{i}")
        d._hist.record("m3", 2.0)            # the tail tenants the
        d._hist.record("m4", 1.0)            # cap must keep visible
        metrics = d._metrics_section()
        steps_lines = [l for l in metrics.splitlines()
                       if l.startswith("dsi_serve_tenant_steps{")]
        assert len(steps_lines) == 2
        assert any('tenant="m3"' in l for l in steps_lines)
        assert any('tenant="m4"' in l for l in steps_lines)
        # Every emitted series name is registry-declared (the schema
        # contract: SERVE_SERIES is the closed set).
        from dsi_tpu.obs.registry import SERVE_SERIES

        for line in metrics.splitlines():
            if line.startswith("dsi_serve"):
                name = line.split("{")[0].split(" ")[0]
                assert name in SERVE_SERIES, line
        st = d._statusz_section()
        assert "3 more tenants" in st
    finally:
        d._rpc.close()


# ── integration: the daemon end to end ──


def test_priority_admission_end_to_end(tmp_path):
    """max_resident=1 serializes the run order: a priority-0 job
    submitted LAST still finishes before the priority-2 jobs queued
    ahead of it."""
    spool = str(tmp_path / "spool")
    subs = []
    for i in range(2):
        p = grep_corpus(str(tmp_path / f"low{i}.txt"), "low", 200)
        subs.append(("low%d" % i, p, 2))
    p = grep_corpus(str(tmp_path / "hi.txt"), "hi", 200)
    subs.append(("hi", p, 0))
    d = ServeDaemon(spool, socket_path=short_sock(), warm=False,
                    max_resident=1)
    reps = {t: d._rpc_submit({"tenant": t, "app": "wc", "files": [f],
                              "priority": pr})
            for t, f, pr in subs}
    assert all("job_id" in r for r in reps.values())
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        final = client.wait(d.socket_path,
                            [r["job_id"] for r in reps.values()],
                            timeout=180)
        assert all(j["state"] == "done" for j in final.values()), final
        done = {j["tenant"]: j["done_ts"] for j in final.values()}
        assert done["hi"] <= min(done["low0"], done["low1"])
    finally:
        d.close()


def test_packed_grep_parity_and_hostpath(tmp_path):
    """Six literal grep tenants across two pattern lengths pack into
    shared waves (the packing evidence in grep_packer.stats); a
    seventh non-literal tenant rides the host path; every tenant's
    grep.json byte-compares equal to the host oracle."""
    spool = str(tmp_path / "spool")
    pats = ["abc", "dog", "cat", "whale", "zebra", "quail"]
    jobs = []
    for i, pat in enumerate(pats):
        p = grep_corpus(str(tmp_path / f"g{i}.txt"), pat, 300)
        jobs.append((f"g{i}", p, pat))
    p = grep_corpus(str(tmp_path / "re.txt"), "qaz", 300)
    jobs.append(("re", p, "q.z"))        # regex meta → host path
    p = str(tmp_path / "long.txt")
    with open(p, "w") as f:              # a line wider than one row:
        f.write("abc ok\n" + "abc " * 2000 + "\nabc tail\n")
    jobs.append(("longline", p, "abc"))  # mid-stream host fallback
    d = ServeDaemon(spool, socket_path=short_sock(), warm=False,
                    chunk_bytes=1 << 12, max_resident=8)
    reps = {t: d._rpc_submit({"tenant": t, "app": "grep",
                              "files": [f], "pattern": pat})
            for t, f, pat in jobs}
    assert all("job_id" in r for r in reps.values())
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        final = client.wait(d.socket_path,
                            [r["job_id"] for r in reps.values()],
                            timeout=180)
        assert all(j["state"] == "done" for j in final.values()), final
        for t, f, pat in jobs:
            with open(os.path.join(reps[t]["out_dir"], "grep.json"),
                      "rb") as fh:
                assert fh.read() == grep_oracle_bytes(f, pat), t
        st = d.grep_packer.stats
        assert st["packed_rows"] >= st["packed_steps"] >= 1
        assert st["max_tenants_per_step"] >= 2
        assert st["host_fallbacks"] >= 1     # the over-wide line
        tenants = client.status(d.socket_path)["tenants"]
        assert tenants["re"]["hostpath"] == 1        # born host path
        assert tenants["longline"]["hostpath"] == 1  # mid-stream flip
        metrics = d._metrics_section()
        assert "dsi_serve_grep_packed_steps" in metrics
    finally:
        d.close()


def test_grep_short_and_wide_lines_share_dispatches(tmp_path):
    """A tenant of tiny lines (more than an eighth of a row's bytes)
    and a tenant of wide lines, same pattern length: one pack group,
    shared dispatches, every dispatched row confirmed in its own step,
    byte parity for both."""
    spool = str(tmp_path / "spool")
    tiny = str(tmp_path / "tiny.txt")
    with open(tiny, "w") as f:
        for j in range(2000):
            f.write("ab\n" if j % 3 else "a\n")   # >128 lines / 1KB row
    wide = grep_corpus(str(tmp_path / "wide.txt"), "ab", 200,
                       line_fill=" " + "f" * 40)
    d = ServeDaemon(spool, socket_path=short_sock(), warm=False,
                    chunk_bytes=1 << 10, max_resident=4)
    reps = {t: d._rpc_submit({"tenant": t, "app": "grep",
                              "files": [f], "pattern": "ab"})
            for t, f in (("tiny", tiny), ("wide", wide))}
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        final = client.wait(d.socket_path,
                            [r["job_id"] for r in reps.values()],
                            timeout=180)
        assert all(j["state"] == "done" for j in final.values()), final
        for t, f in (("tiny", tiny), ("wide", wide)):
            with open(os.path.join(reps[t]["out_dir"], "grep.json"),
                      "rb") as fh:
                assert fh.read() == grep_oracle_bytes(f, "ab"), t
        st = d.grep_packer.stats
        assert st["max_tenants_per_step"] >= 2
        # Nothing is replayed: the rows the jobs confirmed are the rows
        # the packer dispatched, each once.
        assert st.get("replays", 0) == 0 and "rung_widens" not in st
        assert st["packed_rows"] == sum(
            final[r["job_id"]]["stats"]["rows"] for r in reps.values())
        assert st["host_fallbacks"] == 0
    finally:
        d.close()


def test_grep_evict_resume_parity(tmp_path):
    """Grep lanes park on their checkpoint chains and resume exact:
    max_resident=1 + a 1-step quota over two multi-row tenants forces
    evict → park → resume cycles through the PACKED grep path."""
    spool = str(tmp_path / "spool")
    jobs = []
    for i in range(2):
        p = grep_corpus(str(tmp_path / f"e{i}.txt"), f"ev{i}", 600,
                        line_fill=" pad" * 4)
        jobs.append((f"ge{i}", p, f"ev{i}"))
    d = ServeDaemon(spool, socket_path=short_sock(), warm=False,
                    chunk_bytes=1 << 10, max_resident=1, quota_steps=1,
                    checkpoint_every=1)
    reps = {t: d._rpc_submit({"tenant": t, "app": "grep",
                              "files": [f], "pattern": pat})
            for t, f, pat in jobs}
    d.start()
    try:
        client.wait_ready(d.socket_path, timeout=120)
        final = client.wait(d.socket_path,
                            [r["job_id"] for r in reps.values()],
                            timeout=240)
        assert all(j["state"] == "done" for j in final.values()), final
        for t, f, pat in jobs:
            with open(os.path.join(reps[t]["out_dir"], "grep.json"),
                      "rb") as fh:
                assert fh.read() == grep_oracle_bytes(f, pat), t
        tenants = client.status(d.socket_path)["tenants"]
        assert sum(s["evictions"] for s in tenants.values()) >= 1
        assert sum(s["resumes"] for s in tenants.values()) >= 1
    finally:
        d.close()


@pytest.mark.slow
def test_soak_thousand_tenants():
    """The acceptance bar: 1000 mixed tenants of sustained
    submit/shed/evict/resume churn — zero lost jobs, shedding engaged,
    per-tenant byte parity, bounded dsi_serve_* series."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import serve_soak
    finally:
        sys.path.pop(0)
    summary = serve_soak.run_soak(1000)
    assert summary["parity"] and summary["shed"] >= 1
    assert summary["evictions"] >= 1 and summary["resumes"] >= 1
