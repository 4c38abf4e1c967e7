"""The unified tracing/metrics subsystem (dsi_tpu/obs).

Pins the tracer core's contract — nesting, thread-safety under a
background producer, the disabled-mode zero-allocation fast path, the
durable flush discipline (atomicio CRC sidecars; survival of a REAL
``os._exit`` at a ckpt fault point) — the metrics registry's schema,
the span-totals-reconcile-with-phase-dicts acceptance criterion, and
the coordinator's requeue/heartbeat telemetry.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dsi_tpu.obs import registry as obs_registry
from dsi_tpu.obs import trace as obs_trace
from dsi_tpu.obs.registry import MetricsScope, get_registry, metrics_scope
from dsi_tpu.obs.trace import _NOOP_SPAN, Tracer
from dsi_tpu.utils.atomicio import read_bytes_verified

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ── tracer core ────────────────────────────────────────────────────────


def test_disabled_pure_span_is_shared_noop_singleton():
    t = Tracer(enabled=False)
    s1 = t.span("upload")
    s2 = t.span("kernel", step=3)
    assert s1 is _NOOP_SPAN and s2 is _NOOP_SPAN  # zero allocation
    with s1:
        pass
    assert t.mark() == 0 and t.counters == {}  # nothing buffered


def test_disabled_span_with_stats_still_accumulates():
    t = Tracer(enabled=False)
    stats = {"upload_s": 0.0}
    with t.span("upload", stats=stats, key="upload_s"):
        time.sleep(0.01)
    assert stats["upload_s"] >= 0.01
    assert t.mark() == 0  # timed for the engine, nothing traced


def test_events_and_counters_only_when_enabled():
    t = Tracer(enabled=False)
    t.event("requeue", task=1)
    t.count("steps")
    assert t.mark() == 0
    t.enabled = True
    t.event("requeue", task=1)
    t.count("steps", 2)
    assert t.counters == {"steps": 2}
    assert t.mark() == 2


def test_nesting_depth_recorded():
    t = Tracer(enabled=True)
    with t.span("finish", step=0):
        with t.span("kernel"):
            pass
        with t.span("merge"):
            pass
    rows = t.rollup()
    assert set(rows) == {"finish", "kernel", "merge"}
    # Inner spans closed first, at depth 1; the outer at depth 0.
    depths = {e[1]: e[5] for e in t._events}
    assert depths == {"kernel": 1, "merge": 1, "finish": 0}
    # Containment: children start/end inside the parent.
    by_name = {e[1]: e for e in t._events}
    f, k = by_name["finish"], by_name["kernel"]
    assert f[3] <= k[3] and k[3] + k[4] <= f[3] + f[4] + 1e-6


def test_thread_safety_under_concurrent_spans():
    t = Tracer(enabled=True)
    n_threads, per = 8, 200
    errs = []

    def work(i):
        try:
            for j in range(per):
                with t.span("materialize", step=j, thread=i):
                    pass
                t.count("items")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert not errs
    roll = t.rollup()
    assert roll["materialize"]["count"] == n_threads * per
    assert t.counters["items"] == n_threads * per


def test_buffer_cap_drops_are_counted_not_silent(tmp_path):
    t = Tracer(enabled=True, buffer_cap=10, trace_dir=str(tmp_path))
    for i in range(25):
        with t.span("upload", step=i):
            pass
    assert t.rollup()["upload"]["count"] == 10
    assert t.dropped == 15
    t.flush()
    meta = json.loads(
        (tmp_path / "trace.jsonl").read_text().splitlines()[0])
    assert meta["dropped_events"] == 15


def test_flush_is_durable_and_perfetto_loadable(tmp_path):
    t = Tracer(enabled=True, trace_dir=str(tmp_path))
    with t.span("upload", step=0):
        with t.span("kernel"):
            pass
    t.event("requeue", task=3, worker="w1")
    t.count("steps")
    paths = t.flush()
    assert paths is not None
    jsonl_path, json_path = paths
    # Durable-write discipline: CRC sidecars verify (atomicio).
    assert read_bytes_verified(jsonl_path) is not None
    assert read_bytes_verified(json_path) is not None
    doc = json.loads(open(json_path).read())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"upload", "kernel"}
    for e in xs:  # the Chrome/Perfetto complete-event contract
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    # One metadata thread_name per lane, lanes distinct.
    names = {e["args"]["name"]: e["tid"] for e in evs
             if e.get("name") == "thread_name"}
    assert {"upload", "kernel", "control", "counters"} <= set(names)
    assert len(set(names.values())) == len(names)
    assert any(e.get("ph") == "i" and e["name"] == "requeue" for e in evs)
    assert any(e.get("ph") == "C" and e["name"] == "steps" for e in evs)
    # Flush is idempotent (the fault-point flush may not be the last).
    assert t.flush() is not None


def test_configure_reaps_tmp_orphans(tmp_path):
    (tmp_path / ".tmp-trace.json.x").write_text("torn")
    Tracer(enabled=True, trace_dir=str(tmp_path))
    assert not list(tmp_path.glob(".tmp-*"))


# ── metrics registry ───────────────────────────────────────────────────


def test_registry_scope_unified_and_snapshot():
    sc = metrics_scope("stream")
    assert isinstance(sc, MetricsScope) and sc.engine == "stream"
    assert get_registry().phases("stream") is sc
    sc.update({"batch_s": 1.5, "batch_wait_s": 0.25, "upload_s": 2.0,
               "max_inflight_chunks": 2, "steps": 7})
    u = sc.unified()
    assert u["materialize_s"] == 1.5
    assert u["materialize_wait_s"] == 0.25
    assert u["max_inflight"] == 2
    assert u["upload_s"] == 2.0 and u["steps"] == 7
    assert "batch_s" not in u and "max_inflight_chunks" not in u
    get_registry().set_gauge("mr_worker_heartbeat_age_s", {"w1": 0.5})
    snap = get_registry().snapshot()
    assert snap["engines"]["stream"]["materialize_s"] == 1.5
    assert snap["gauges"]["mr_worker_heartbeat_age_s"] == {"w1": 0.5}


# ── the acceptance criterion: spans reconcile with the phase dict ──────


def test_traced_stream_spans_reconcile_with_stream_phases(tmp_path,
                                                          monkeypatch):
    jax = pytest.importorskip("jax")
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import wordcount_streaming

    tracer = Tracer(enabled=True, trace_dir=str(tmp_path / "trace"))
    monkeypatch.setattr(obs_trace, "_global", tracer)
    text = ("the quick brown fox jumps over the lazy dog " * 2000).encode()
    pstats: dict = {}
    acc = wordcount_streaming(
        [text], mesh=default_mesh(8), n_reduce=10, chunk_bytes=1 << 12,
        u_cap=1 << 10, device_accumulate=True, sync_every=4,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=4,
        pipeline_stats=pstats)
    assert acc is not None
    paths = tracer.flush()
    assert paths is not None
    roll = tracer.rollup()
    # Per-phase span totals reconcile (±5%) with the registry values the
    # same run reported — by construction they are the same measurement,
    # so this pin catches any future divergence of the two paths.
    for span_name, key in (("upload", "upload_s"), ("kernel", "kernel_s"),
                           ("materialize", "batch_s"),
                           ("fold", "fold_s"), ("sync", "sync_s"),
                           ("ckpt", "ckpt_s")):
        want = pstats[key]
        got = roll.get(span_name, {}).get("total_s", 0.0)
        assert got == pytest.approx(want, rel=0.05, abs=2e-3), \
            (span_name, key, got, want)
    # The per-step timeline exists: one finish span per step, labeled.
    assert roll["finish"]["count"] == pstats["steps"]
    doc = json.loads(open(paths[1]).read())
    fins = [e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "finish"]
    assert sorted(e["args"]["step"] for e in fins) == \
        list(range(pstats["steps"]))
    assert all(e["args"]["engine"] == "stream" for e in fins)
    # And the registry snapshot rode the artifact.
    meta = json.loads(
        open(paths[0]).read().splitlines()[0])
    assert meta["registry"]["engines"]["stream"]["materialize_s"] == \
        pstats["batch_s"]


# ── durable flush at a REAL crash (os._exit fault point) ───────────────


def test_trace_survives_real_process_death(tmp_path):
    corpus = tmp_path / "c.txt"
    words = " ".join(f"w{i:03d}" for i in range(120))
    corpus.write_bytes((words + "\n").encode() * 400)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "DSI_FAULT_POINT": "mid-fold", "DSI_FAULT_STEP": "3"})
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    trace_dir = tmp_path / "trace"
    p = subprocess.run(
        [sys.executable, "-m", "dsi_tpu.cli.wcstream", "--devices", "2",
         "--chunk-bytes", "8192", "--checkpoint-dir",
         str(tmp_path / "ck"), "--checkpoint-every", "1",
         "--trace-dir", str(trace_dir), "--workdir", str(tmp_path / "wd"),
         str(corpus)],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 87, p.stderr[-2000:]  # the injected crash
    # The fault-point flush committed BOTH artifacts durably before
    # os._exit: CRC-verified, parseable, and carrying the fault marker
    # plus real spans from before the crash.
    raw = read_bytes_verified(str(trace_dir / "trace.json"))
    assert raw is not None
    doc = json.loads(raw)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "fault" in names and "upload" in names and "ckpt" in names
    assert read_bytes_verified(str(trace_dir / "trace.jsonl")) is not None
    # tracecat renders it without error.
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tracecat.py"),
         str(trace_dir)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-1000:]
    assert "flame" in r.stdout and "fault" in r.stdout


# ── control plane: requeue telemetry + heartbeat gauge ─────────────────


def test_requeue_logs_heartbeat_age_and_reason(tmp_path, capsys):
    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.coordinator import Coordinator

    f = tmp_path / "in.txt"
    f.write_text("alpha beta")
    cfg = JobConfig(n_reduce=2, task_timeout_s=0.25,
                    workdir=str(tmp_path))
    c = Coordinator([str(f)], 2, cfg)
    try:
        reply = c.request_task({"TaskNumber": 0, "WorkerId": "w-test"})
        assert reply["TaskStatus"] == 0  # MAP assigned
        ages = c.worker_heartbeat_ages()
        assert "w-test" in ages and ages["w-test"] >= 0
        # Never complete it: the watchdog must requeue — loudly.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with c.mu:
                if c.map_log[0] == 0:  # LOG_UNTOUCHED again
                    break
            time.sleep(0.05)
        with c.mu:
            assert c.map_log[0] == 0, "task was never requeued"
        err = capsys.readouterr().err
        assert "requeue map task 0" in err
        assert "worker=w-test" in err and "heartbeat_age=" in err
        # The gauge was republished to the registry at requeue time.
        gauge = get_registry().gauge("mr_worker_heartbeat_age_s")
        assert gauge and "w-test" in gauge
    finally:
        c.close()
