"""The live telemetry plane (ISSUE 10): stage latency histograms,
the stall watchdog, /statusz + /metrics, and the bench_diff gate.

Pins the histogram bucket/percentile/merge math (hypothesis property
dormant without it), the forced-stall exactly-once contract, the
endpoint smoke against a live soak subprocess, the disabled-mode
zero-thread/zero-alloc guarantee, the coordinator's percentile-aware
heartbeat classification, and bench_diff's threshold units on
synthetic pairs plus the real r04→r05 artifacts.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from dsi_tpu.obs import hist as obs_hist
from dsi_tpu.obs.hist import (HIST_SNAPSHOT_KEYS, HIST_STAGES,
                              LatencyHistogram, StageHistograms)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # dormant without hypothesis, like the fuzz suite
    HAVE_HYPOTHESIS = False


@pytest.fixture
def clean_plane():
    """Force the histogram plane off before AND after — tests must not
    inherit (or leak) a live activation."""
    obs_hist.deactivate(force=True)
    yield
    obs_hist.deactivate(force=True)


# ── histogram core ─────────────────────────────────────────────────────


def test_histogram_bucket_units():
    h = LatencyHistogram()
    # Monotonic bucketing, sub-microsecond clamps to bucket 0.
    assert h.bucket_of(0.0) == 0
    assert h.bucket_of(5e-7) == 0
    last = -1
    for us in (1, 2, 5, 10, 100, 1e3, 1e4, 1e6, 1e8):
        b = h.bucket_of(us / 1e6)
        assert b >= last, us
        last = b
    # A bucket's midpoint brackets the values that land in it.
    for v in (3.7e-6, 1.2e-3, 0.25, 7.0):
        b = h.bucket_of(v)
        mid = h.bucket_mid_s(b)
        assert mid == pytest.approx(v, rel=0.15), (v, b, mid)


def test_histogram_percentiles_and_snapshot_keys(clean_plane):
    h = LatencyHistogram()
    assert h.percentile(0.5) == 0.0  # empty: no samples, no invention
    for _ in range(99):
        h.record(0.010)
    h.record(1.0)
    assert h.count == 100
    assert h.percentile(0.50) == pytest.approx(0.010, rel=0.15)
    assert h.percentile(0.99) == pytest.approx(0.010, rel=0.15)
    assert h.percentile(1.00) == pytest.approx(1.0, rel=0.15)
    snap = h.snapshot()
    assert tuple(snap) == HIST_SNAPSHOT_KEYS
    assert snap["max_ms"] == pytest.approx(1000.0, rel=0.01)
    assert snap["count"] == 100


def test_histogram_merge_is_bucket_exact():
    a, b, both = (LatencyHistogram() for _ in range(3))
    for i, v in enumerate((1e-5, 3e-4, 0.002, 0.002, 0.7, 12.0)):
        (a if i % 2 else b).record(v)
        both.record(v)
    a.merge(b)
    assert a._counts == both._counts
    assert a.count == both.count
    assert a.total_s == pytest.approx(both.total_s)
    assert a.max_s == both.max_s


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(xs=st.lists(st.floats(min_value=2e-6, max_value=50.0,
                                 allow_nan=False, allow_infinity=False),
                       min_size=1, max_size=80),
           ys=st.lists(st.floats(min_value=2e-6, max_value=50.0,
                                 allow_nan=False, allow_infinity=False),
                       max_size=80))
    def test_histogram_merge_property(xs, ys):
        """merge(h(xs), h(ys)) == h(xs+ys) bucket-for-bucket, and its
        percentiles stay within bucket resolution of the true ones."""
        import math

        ha, hb, hall = (LatencyHistogram() for _ in range(3))
        for v in xs:
            ha.record(v)
            hall.record(v)
        for v in ys:
            hb.record(v)
            hall.record(v)
        ha.merge(hb)
        assert ha._counts == hall._counts
        assert ha.count == len(xs) + len(ys)
        data = sorted(xs + ys)
        for q in (0.5, 0.9, 0.99):
            true = data[max(1, math.ceil(q * len(data))) - 1]
            got = ha.percentile(q)
            assert true / 1.2 <= got <= true * 1.2, (q, true, got)

else:  # pragma: no cover

    @pytest.mark.skip(reason="hypothesis not installed (dormant)")
    def test_histogram_merge_property():
        pass


# ── span-close recording ───────────────────────────────────────────────


def test_hot_spans_record_without_tracing(clean_plane):
    """statusz-without-tracing mode: the plane active, the tracer
    disabled — hot-stage spans still record their close latency, and
    nothing lands in the trace buffer."""
    from dsi_tpu.obs.trace import _NOOP_SPAN, Tracer

    hs = obs_hist.activate()
    t = Tracer(enabled=False)
    with t.span("kernel"):
        time.sleep(0.002)
    with t.span("materialize"):  # not a hot stage: stays a no-op
        pass
    stats: dict = {}
    with t.span("upload", stats=stats, key="upload_s"):
        time.sleep(0.001)
    assert t.mark() == 0  # tracer stayed out of it
    assert hs.get("kernel").count == 1
    assert hs.get("upload").count == 1
    assert t.span("materialize") is _NOOP_SPAN
    assert stats["upload_s"] > 0


def test_disabled_mode_zero_threads_zero_alloc(clean_plane):
    """The acceptance bar's cheap half: with the plane off, hot spans
    are the shared no-op singleton, a pipeline run starts no watchdog/
    sampler threads, and the registry snapshot has no histograms."""
    from dsi_tpu.obs import get_registry
    from dsi_tpu.obs.trace import _NOOP_SPAN, Tracer
    from dsi_tpu.parallel.pipeline import StepPipeline

    t = Tracer(enabled=False)
    assert t.span("kernel") is _NOOP_SPAN
    assert obs_hist.active_histograms() is None
    stats: dict = {}
    pipe = StepPipeline(depth=1, dispatch=lambda i: i,
                        finish=lambda rec: None, stats=stats,
                        engine="offtest")
    pipe.run(lambda: iter(range(4)))
    names = {th.name for th in threading.enumerate()}
    assert not any(n.startswith(("dsi-stall-watchdog", "dsi-live-sampler",
                                 "dsi-statusz")) for n in names), names
    assert "stalls" not in stats
    assert "histograms" not in get_registry().snapshot()


# ── the stall watchdog ─────────────────────────────────────────────────


def test_forced_stall_flags_exactly_once(clean_plane, monkeypatch,
                                         capsys):
    """A sleep-injected finish past the floor produces EXACTLY ONE
    stall trace event (+ gauge + stats counter), however many watchdog
    checks elapse while it stalls."""
    from dsi_tpu.obs import get_registry, get_tracer
    from dsi_tpu.parallel.pipeline import StepPipeline

    monkeypatch.setenv("DSI_STALL_FLOOR_S", "0.2")
    monkeypatch.setenv("DSI_STALL_CHECK_S", "0.03")
    tr = get_tracer()
    was = tr.enabled
    tr.enabled = True
    mark = tr.mark()
    try:
        stats: dict = {}

        def finish(rec):
            if rec == 1:
                time.sleep(0.8)  # >> floor, spans many check intervals

        pipe = StepPipeline(depth=1, dispatch=lambda i: i, finish=finish,
                            stats=stats, engine="stalltest")
        pipe.run(lambda: iter(range(3)))
        with tr._lock:
            evs = tr._events[mark:]
    finally:
        tr.enabled = was
    stalls = [e for e in evs if e[0] == "I" and e[1] == "stall"]
    assert len(stalls) == 1, stalls
    fields = stalls[0][6]
    assert fields["engine"] == "stalltest" and fields["step"] == 1
    assert fields["age_s"] >= 0.2 and fields["threshold_s"] >= 0.2
    assert stats["stalls"] == 1
    gauge = get_registry().gauge("pipeline_stall")
    assert gauge and gauge["step"] == 1
    assert "STALL stalltest step 1" in capsys.readouterr().err


def test_deep_pipeline_window_residency_is_not_a_stall(clean_plane,
                                                       monkeypatch):
    """The watchdog thresholds on head-of-line RETIRE age, not
    dispatch→finish age: at depth 8 with steady steps, the oldest
    record's since-dispatch age is ~depth × step wall (over the floor
    here), but each head of line retires on cadence — a healthy deep
    pipeline must produce zero stall flags."""
    from dsi_tpu.obs import get_tracer
    from dsi_tpu.parallel.pipeline import StepPipeline

    monkeypatch.setenv("DSI_STALL_FLOOR_S", "0.25")
    monkeypatch.setenv("DSI_STALL_CHECK_S", "0.02")
    tr = get_tracer()
    was = tr.enabled
    tr.enabled = True
    mark = tr.mark()
    try:
        stats: dict = {}
        pipe = StepPipeline(depth=8, dispatch=lambda i: i,
                            finish=lambda rec: time.sleep(0.07),
                            stats=stats, engine="deep")
        pipe.run(lambda: iter(range(12)))  # oldest waits ~8*0.07 > floor
        with tr._lock:
            evs = tr._events[mark:]
    finally:
        tr.enabled = was
    assert not [e for e in evs if e[0] == "I" and e[1] == "stall"], \
        [e for e in evs if e[0] == "I"]
    assert "stalls" not in stats


def test_no_stall_event_for_healthy_run(clean_plane, monkeypatch):
    from dsi_tpu.obs import get_tracer
    from dsi_tpu.parallel.pipeline import StepPipeline

    monkeypatch.setenv("DSI_STALL_FLOOR_S", "5.0")
    tr = get_tracer()
    was = tr.enabled
    tr.enabled = True
    mark = tr.mark()
    try:
        stats: dict = {}
        pipe = StepPipeline(depth=2, dispatch=lambda i: i,
                            finish=lambda rec: None, stats=stats,
                            engine="healthy")
        pipe.run(lambda: iter(range(8)))
        with tr._lock:
            evs = tr._events[mark:]
    finally:
        tr.enabled = was
    assert not [e for e in evs if e[0] == "I" and e[1] == "stall"]
    assert "stalls" not in stats


# ── live sampler + endpoints ───────────────────────────────────────────


def test_live_jsonl_ring_is_bounded(clean_plane, tmp_path):
    from dsi_tpu.obs.live import LiveTelemetry

    lt = LiveTelemetry(port=0, live_dir=str(tmp_path), ring=5,
                       interval_s=60.0)
    try:
        lt.start()
        for _ in range(12):
            lt._sample_once()
        lines = (tmp_path / "live.jsonl").read_text().splitlines()
        assert len(lines) == 5  # the ring bound, not 13
        snap = json.loads(lines[-1])
        assert snap["pid"] == os.getpid() and "engines" in snap
    finally:
        lt.stop()
    # The hold is released (an explicit deactivate now works); the
    # histograms themselves survive the sampler by design.
    obs_hist.deactivate()
    assert obs_hist.active_histograms() is None


def test_statusz_and_metrics_answer_during_live_soak(tmp_path):
    """The acceptance smoke: a REAL wcstream soak subprocess serving
    --statusz-port answers /statusz with a current step ordinal and
    stage p50/p99, and /metrics with the Prometheus summary, WHILE the
    stream is running."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    errpath = tmp_path / "soak.err"
    with open(errpath, "w") as errf:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "stream_soak.py"),
             "--mb", "8", "--chunk-bytes", "65536",
             "--statusz-port", "0", "--trace-dir", str(tmp_path)],
            stdout=subprocess.PIPE, stderr=errf, text=True, cwd=REPO,
            env=env)
    try:
        port = None
        deadline = time.time() + 60
        while time.time() < deadline and port is None:
            m = re.search(r"serving on http://127\.0\.0\.1:(\d+)/statusz",
                          errpath.read_text())
            if m:
                port = int(m.group(1))
                break
            assert proc.poll() is None, errpath.read_text()
            time.sleep(0.05)
        assert port, "statusz server never announced its port"

        def get(path):
            return urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5).read().decode()

        statusz = metrics = None
        deadline = time.time() + 180
        while time.time() < deadline and proc.poll() is None:
            try:
                txt = get("/statusz")
            except OSError:
                time.sleep(0.05)
                continue
            # Catch the engine MID-RUN: a pipeline registered and at
            # least one step dispatched.
            if re.search(r"dispatched=[1-9]", txt):
                statusz = txt
                metrics = get("/metrics")
                break
            time.sleep(0.02)
        assert statusz is not None, \
            f"never saw a live step; stderr:\n{errpath.read_text()}"
        # Current step ordinal + in-flight window, live.
        assert re.search(r"stream: dispatched=\d+ finished=\d+ "
                         r"inflight=\d+", statusz)
        assert "steps=" in statusz
        # Stage percentiles present (hot spans recorded without tracing).
        assert re.search(r"(kernel|upload|finish)\s+\d+", statusz)
        assert "p50" in statusz and "p99" in statusz
        assert "dsi_stage_latency_seconds" in metrics
        assert 'quantile="0.99"' in metrics
        assert re.search(r'dsi_pipeline_step\{engine="stream"\} \d+',
                         metrics)
        out, _ = proc.communicate(timeout=240)
        assert proc.returncode == 0, errpath.read_text()
        assert json.loads(out.strip().splitlines()[-1])["counts_exact"]
        # The bounded ring landed next to the trace artifacts.
        ring = (tmp_path / "live.jsonl").read_text().splitlines()
        assert ring and all(json.loads(l) for l in ring)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


# ── coordinator heartbeat percentiles ──────────────────────────────────


def test_requeue_is_percentile_aware(tmp_path, capsys):
    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.coordinator import Coordinator
    from dsi_tpu.obs import get_registry

    f = tmp_path / "in.txt"
    f.write_text("alpha beta")
    cfg = JobConfig(n_reduce=2, task_timeout_s=0.25,
                    workdir=str(tmp_path))
    c = Coordinator([str(f)], 2, cfg)
    try:
        # Two contacts close together: the gap histogram learns this
        # worker phones home on a ~30 ms cadence.
        reply = c.request_task({"TaskNumber": 0, "WorkerId": "w-hist"})
        assert reply["TaskStatus"] == 0
        time.sleep(0.03)
        c.request_task({"TaskNumber": 0, "WorkerId": "w-hist"})
        hists = c.worker_heartbeat_hists()
        assert "w-hist" in hists and hists["w-hist"]["count"] >= 1
        assert tuple(hists["w-hist"]) == HIST_SNAPSHOT_KEYS
        # Never complete the task: the watchdog requeues, now with the
        # percentile classification in the record.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with c.mu:
                if c.map_log[0] == 0:
                    break
            time.sleep(0.05)
        with c.mu:
            assert c.map_log[0] == 0, "task was never requeued"
        err = capsys.readouterr().err
        assert "p99=" in err and "presumed=" in err
        # Silence (>= timeout) way past a ~30 ms p99 gap -> dead.
        assert "presumed=dead" in err
        gauge = get_registry().gauge("mr_worker_heartbeat_hist")
        assert gauge and "w-hist" in gauge
        # The armed speculative hook sees the silent worker too (give
        # the silence a beat to clear max(k*p99, timeout)).
        time.sleep(0.15)
        assert "w-hist" in c.straggler_suspects()
    finally:
        c.close()


# ── bench_diff ─────────────────────────────────────────────────────────

BENCH_DIFF = os.path.join(REPO, "scripts", "bench_diff.py")


def run_diff(*args):
    return subprocess.run([sys.executable, BENCH_DIFF, *args],
                          capture_output=True, text=True, cwd=REPO)


def _write_pair(tmp_path, old, new):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({"parsed": old}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({"parsed": new}))


def test_bench_diff_flags_injected_20pct_stream_drop(tmp_path):
    _write_pair(tmp_path,
                {"value": 10.0, "stream_mbps": 10.0,
                 "stream_parity": True},
                {"value": 10.0, "stream_mbps": 8.0,
                 "stream_parity": True})
    p = run_diff("--dir", str(tmp_path))
    assert p.returncode == 1, p.stdout
    assert re.search(r"stream_mbps.*-20\.0%.*REGRESS", p.stdout)
    assert re.search(r"value.*ok", p.stdout)


def test_bench_diff_threshold_units(tmp_path):
    # Inside the 10% band: pass.  Parity flip: regress.  Lower-better:
    # overhead rising past +50% regresses, falling never does.
    _write_pair(tmp_path,
                {"stream_mbps": 10.0, "ckpt_overhead_pct": 10.0,
                 "stream_parity": True, "resume_gap_s": 0.05},
                {"stream_mbps": 9.5, "ckpt_overhead_pct": 16.0,
                 "stream_parity": False, "resume_gap_s": 0.01})
    p = run_diff("--dir", str(tmp_path))
    assert p.returncode == 1
    assert re.search(r"stream_mbps.*ok", p.stdout)
    assert re.search(r"ckpt_overhead_pct.*REGRESS", p.stdout)
    assert re.search(r"stream_parity.*true->false.*REGRESS", p.stdout.
                     replace("True->False", "true->false"))
    assert re.search(r"resume_gap_s.*ok", p.stdout)
    # An override loosens the gate.
    p2 = run_diff("--dir", str(tmp_path),
                  "--threshold", "ckpt_overhead_pct=2.0")
    assert "ckpt_overhead_pct" in p2.stdout
    assert not re.search(r"ckpt_overhead_pct.*REGRESS", p2.stdout)


def test_bench_diff_missing_keys_are_unknown_not_regress(tmp_path):
    _write_pair(tmp_path,
                {"value": 10.0, "kernel_sort_mbps": 5.0},
                {"value": 10.0, "grep_mbps": 7.0})
    p = run_diff("--dir", str(tmp_path))
    assert p.returncode == 0, p.stdout
    assert re.search(r"kernel_sort_mbps.*unknown", p.stdout)
    assert re.search(r"grep_mbps.*unknown", p.stdout)


def test_bench_diff_gates_serve_latency_row(tmp_path):
    # The ISSUE 19 tentpole number: the packed-grep arm's p99 gates
    # lower-better (a doubled tail regresses); the parity bool rides
    # the *_parity pattern; the tmux control arm stays ungated context.
    _write_pair(tmp_path,
                {"serve_pack_p99_s": 0.5, "serve_tmux_p99_s": 7.0,
                 "serve_lat_parity": True},
                {"serve_pack_p99_s": 1.6, "serve_tmux_p99_s": 20.0,
                 "serve_lat_parity": True})
    p = run_diff("--dir", str(tmp_path))
    assert p.returncode == 1, p.stdout
    assert re.search(r"serve_pack_p99_s.*REGRESS", p.stdout)
    assert not re.search(r"serve_tmux_p99_s.*REGRESS", p.stdout)
    assert re.search(r"serve_lat_parity.*ok", p.stdout)


def test_bench_diff_passes_on_a_steady_pair(tmp_path):
    """Two explicit record paths in the driver's wrapper format, every
    metric inside its band: PASS."""
    old = {"metric": "wc_tpu_throughput", "value": 12.0,
           "median_mbps": 11.5, "stream_mbps": 8.0, "stream_parity": True,
           "ckpt_overhead_pct": 12.0}
    new = dict(old, value=12.3, stream_mbps=7.8, ckpt_overhead_pct=13.0)
    a, b = tmp_path / "old.json", tmp_path / "new.json"
    a.write_text(json.dumps({"n": 1, "rc": 0, "parsed": old}))
    b.write_text(json.dumps({"n": 2, "rc": 0, "parsed": new}))
    p = run_diff(str(a), str(b))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "PASS" in p.stdout
