"""The one tracer (dsi_tpu/obs/trace.py) where the work happens.

Spans carry an id, their parent and their task's identity; a worker enabled
by ``DSI_TRACE_DIR`` times its tasks from their own start and opens spans
inside them; ``mrrun --trace-dir`` leaves the launch's parts; ``wcstream
--stats`` splits its pull and times its tail; with tracing off none of it
costs a span.  The reference has no tracing at all (SURVEY.md §5): this
layer is additive observability, and these tests pin its contract.
"""

import ast
import contextlib
import glob
import io
import json
import os
import re
import subprocess
import sys
import threading

import pytest

import dsi_tpu.obs.hist as obs_hist
import dsi_tpu.obs.trace as obs_trace
from dsi_tpu.obs import Tracer
from dsi_tpu.utils.corpus import ensure_corpus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAP_PARTS = ("read", "materialize", "upload", "kernel", "pull", "decode",
             "write")


def _jsonl(path):
    with open(path, encoding="utf-8") as f:
        head, *events = [json.loads(line) for line in f]
    return head, events


def _flushed(tracer):
    return _jsonl(tracer.flush()[0])[1]


@pytest.fixture
def global_tracer(tmp_path, monkeypatch):
    """An enabled tracer in the process-global slot, as ``DSI_TRACE_DIR``
    or ``--trace-dir`` would leave one."""
    tracer = Tracer(enabled=True, trace_dir=str(tmp_path / "trace"))
    monkeypatch.setattr(obs_trace, "_global", tracer)
    yield tracer
    tracer.enabled = False


@pytest.fixture
def tracing_off(monkeypatch):
    monkeypatch.delenv("DSI_TRACE_DIR", raising=False)
    monkeypatch.setattr(obs_trace, "_global", Tracer(enabled=False))
    monkeypatch.setattr(obs_hist, "_active", None)


# ── the four tests of the former utils/tracing layer, against obs ──────


def test_span_emits_event_when_traced(global_tracer):
    from dsi_tpu.obs import span

    with span("task", stats={}, phase="unit.phase", task=7) as s:
        pass
    assert s.elapsed_s >= 0
    (rec,) = _flushed(global_tracer)
    assert rec["ph"] == "X" and rec["name"] == "task"
    assert rec["phase"] == "unit.phase" and rec["task"] == 7
    assert rec["dur"] >= 0 and rec["id"] == s.id and rec["parent"] is None


def test_silent_without_env(tracing_off, capsys):
    from dsi_tpu.obs import event, flush, span

    with span("task") as s:
        pass
    event("custom", x=1)
    assert s is obs_trace._NOOP_SPAN
    assert obs_trace.get_tracer().mark() == 0 and flush() is None
    assert capsys.readouterr().err == ""


def test_worker_tasks_emit_timeline(global_tracer, tmp_path):
    # A real 1-coordinator + 2-worker job must produce one worker.map span
    # per input file and one worker.reduce span per partition that ran.
    from tests.harness import run_distributed_threads

    files = []
    for i in range(3):
        p = tmp_path / f"in-{i}.txt"
        p.write_text(f"alpha beta file{i} gamma")
        files.append(str(p))
    run_distributed_threads("wc", files, str(tmp_path), n_workers=2,
                            n_reduce=4)
    recs = _flushed(global_tracer)
    spans = [r for r in recs if r["ph"] == "X"]
    maps = [r for r in spans if r["name"] == "worker.map"]
    reduces = [r for r in spans if r["name"] == "worker.reduce"]
    assert sorted(r["task"] for r in maps) == [0, 1, 2]
    assert {r["file"] for r in maps} == set(files)
    assert {r["kind"] for r in maps} == {"map"}
    assert sorted(r["task"] for r in reduces) == [0, 1, 2, 3]
    assert all(r["dur"] >= 0 and r["ts"] > 0 for r in spans)
    # The host map opens its parts too, under its task's identity.
    for m in maps:
        kids = [r for r in spans if r["parent"] == m["id"]]
        assert {"read", "write"} <= {r["name"] for r in kids}
        assert all((r["kind"], r["task"]) == ("map", m["task"])
                   for r in kids)
    # The coordinator side of the timeline: one assign and one complete per
    # task (no crashes/requeues in this run).
    assigns = [r for r in recs if r["name"] == "assign"]
    completes = [r for r in recs if r["name"] == "complete"]
    assert sorted(r["task"] for r in assigns if r["kind"] == "map") == [0, 1, 2]
    assert sorted(r["task"] for r in completes
                  if r["kind"] == "reduce") == [0, 1, 2, 3]
    rpcs = {r["method"] for r in spans if r["name"] == "rpc"}
    assert {"Coordinator.RequestTask", "Coordinator.RecieveMapComplete",
            "Coordinator.RecieveReduceComplete"} <= rpcs


def test_no_dead_tracing_api():
    # The second tracer (utils/tracing.py: Span, log_event, the DSI_TRACE=1
    # stderr stream) and its mirror path must stay deleted.
    with pytest.raises(ImportError):
        import dsi_tpu.utils.tracing  # noqa: F401
    assert not hasattr(Tracer, "record_span")
    assert not os.path.exists(os.path.join(REPO, "scripts",
                                           "trace_timeline.py"))
    hits = []
    for path in ([os.path.join(REPO, "bench.py")]
                 + glob.glob(os.path.join(REPO, "dsi_tpu", "**", "*.py"),
                             recursive=True)
                 + glob.glob(os.path.join(REPO, "scripts", "*"))):
        with open(path, encoding="utf-8", errors="replace") as f:
            if re.search(r"utils\.tracing|record_span|\bDSI_TRACE\b(?!_)",
                         f.read()):
                hits.append(path)
    assert hits == []


# ── ids, parents, task identity ────────────────────────────────────────


def test_ids_parents_and_task_inheritance_across_a_thread(tmp_path):
    t = Tracer(enabled=True, trace_dir=str(tmp_path))
    with t.span("worker.map", lane="control", kind="map", task=5) as task:
        with t.span("read") as r:
            r.set(bytes=11)
        t.event("spilled")

        def helper():
            with t.span("decode", parent=task):
                with t.span("kernel"):
                    pass
            with t.span("merge"):   # no parent named: a root of its thread
                pass

        th = threading.Thread(target=helper)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
    with t.span("upload"):
        pass
    head, recs = _jsonl(t.flush()[0])
    # wall0 is wall0_ns rounded to the millisecond (not floored)
    assert abs(head["wall0_ns"] / 1e9 - head["wall0"]) <= 0.00051
    by = {r["name"]: r for r in recs}
    ids = [r["id"] for r in recs if r["ph"] == "X"]
    assert len(set(ids)) == len(ids) == 6
    assert by["worker.map"]["parent"] is None
    assert by["read"]["parent"] == by["worker.map"]["id"]
    assert by["read"]["bytes"] == 11 and by["read"]["depth"] == 1
    assert by["spilled"]["parent"] == by["worker.map"]["id"]
    assert by["decode"]["parent"] == by["worker.map"]["id"]
    assert by["kernel"]["parent"] == by["decode"]["id"]
    assert by["kernel"]["depth"] == 2
    for name in ("read", "spilled", "decode", "kernel"):
        assert (by[name]["kind"], by[name]["task"]) == ("map", 5), name
    for name in ("merge", "upload"):
        assert by[name]["parent"] is None and "task" not in by[name]


def test_spans_ride_the_profilers_clock(tmp_path):
    jax = pytest.importorskip("jax")
    t = Tracer(enabled=True, trace_dir=str(tmp_path / "t"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with t.span("worker.map", lane="control", kind="map", task=0):
            with t.span("kernel"):
                jax.block_until_ready(jax.numpy.arange(8) + 1)
        t.flush()
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(pb)
    seen = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("dsi:", "dsi.clock")):
                    seen.setdefault(ev.name.split("#")[0], plane.name)
    assert {"dsi:worker.map", "dsi:kernel", "dsi.clock"} <= set(seen), seen
    assert all(p.startswith("/host:") for p in seen.values()), seen


# ── a traced mrrun job on the device backend (CPU by name, tiny input) ──


@pytest.fixture(scope="module")
def traced_job(tmp_path_factory):
    root = tmp_path_factory.mktemp("traced-job")
    files = ensure_corpus(str(root / "inputs"), n_files=2, file_size=60_000)
    env = dict(os.environ, DSI_JAX_PLATFORM="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-m", "dsi_tpu.cli.mrrun", "--workers", "2",
         "--nreduce", "3", "--backend", "tpu", "--workdir", str(root / "job"),
         "--trace-dir", str(root / "trace"), "--check", "tpu_wc"] + files,
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-3000:]
    procs = {}
    for path in glob.glob(str(root / "trace" / "trace-*.jsonl")):
        head, events = _jsonl(path)
        procs[os.path.basename(path)] = (head, events)
    return procs


def test_worker_times_its_first_map_from_its_own_start(traced_job):
    workers = {name: he for name, he in traced_job.items()
               if any(e["name"] == "worker.map" for e in he[1])}
    assert workers
    for name, (head, events) in workers.items():
        first = min((e for e in events if e["name"] == "worker.map"),
                    key=lambda e: e["ts"])
        up = next(e for e in events if e["name"] == "backend_up")
        start = next(e for e in events if e["name"] == "worker.start")
        # not clamped to the tracer's epoch: it starts after the backend
        # came up, which is seconds after the worker's main
        assert 0 <= start["ts"] < up["ts"] < first["ts"], name
        kids = [e for e in events if e["ph"] == "X"
                and e["parent"] == first["id"]]
        assert {e["name"] for e in kids} == set(MAP_PARTS), name
        assert all((e["kind"], e["task"]) == ("map", first["task"])
                   for e in kids)
        covered = sum(e["dur"] for e in kids)
        assert 0.9 * first["dur"] <= covered <= first["dur"], (
            name, covered, first["dur"])
        kernel = next(e for e in kids if e["name"] == "kernel")
        assert kernel["program"].startswith("wc_kernel")
        assert kernel["attempt"] == 0 and kernel["cap"] > 0


def test_mrrun_trace_dir_leaves_the_launchs_parts(traced_job):
    head, events = traced_job["trace-mrrun.jsonl"]
    launch = [e for e in events if e["lane"] == "launch"]
    names = [e["name"] for e in launch]
    assert names[0] == "mrrun.start" and "coordinator_up" in names
    (probe,) = [e for e in launch if e["name"] == "probe"]
    assert probe["ph"] == "X"
    assert (probe["chips"], probe["how"]) == (0, "cpu")  # the CPU, by name
    spawned = {e["pid"]: e["role"] for e in launch if e["name"] == "spawn"}
    assert sorted(spawned.values()) == ["coordinator", "worker:tpu",
                                        "worker:tpu"]
    for name, (whead, wevents) in traced_job.items():
        role = spawned.get(whead["pid"])
        if role != "worker:tpu":
            continue
        (spawn,) = [e for e in launch if e.get("pid") == whead["pid"]]
        start = next(e for e in wevents if e["name"] == "worker.start")
        up = next(e for e in wevents if e["name"] == "backend_up")
        init = next(e for e in wevents if e["name"] == "backend_init")
        assert (up["platform"], up["count"]) == ("cpu", 8) and up["kind"]
        assert init["ts"] <= up["ts"] <= init["ts"] + init["dur"] + 0.05
        # one clock across processes: spawned before its main began
        assert head["wall0"] + spawn["ts"] <= whead["wall0"] + start["ts"]


# ── the stream's pull and tail ─────────────────────────────────────────


def test_wcstream_stats_split_the_pull_and_time_the_tail(tmp_path):
    pytest.importorskip("jax")
    from dsi_tpu.cli import wcstream

    (src,) = ensure_corpus(str(tmp_path / "inputs"), n_files=1,
                           file_size=120_000)
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = wcstream.main(["--devices", "2", "--chunk-bytes", "16384",
                            "--nreduce", "3", "--stats", "--workdir",
                            str(tmp_path / "out"), src])
    assert rc == 0
    m = re.search(r"^wcstream: pipeline_stats=(\{.*\})$", err.getvalue(),
                  re.M)
    ps = ast.literal_eval(m.group(1))
    for key in ("finalize_s", "write_s", "device_wait_s", "d2h_s"):
        assert ps[key] > 0, key
    assert ps["pull_bytes"] > 0 and ps["step_pulls"] >= 1
    # pull_s is the wall of the span the two parts sit in
    parts = ps["device_wait_s"] + ps["d2h_s"]
    assert parts <= ps["pull_s"] + 2e-4
    assert ps["pull_s"] - parts <= 0.002 * ps["step_pulls"] + 2e-4
    assert sorted(os.listdir(tmp_path / "out")) == [
        "mr-out-0", "mr-out-1", "mr-out-2"]


# ── tracing off ────────────────────────────────────────────────────────


def test_untraced_map_task_builds_no_span_without_a_sink(tracing_off,
                                                         tmp_path,
                                                         monkeypatch):
    pytest.importorskip("jax")
    from dsi_tpu.backends.tpu import TpuTaskRunner
    from dsi_tpu.mr.plugin import load_plugin_module

    built = []
    init = obs_trace._Span.__init__

    def spy(self, tr, name, lane, stats, *rest):
        built.append((name, stats is not None))
        init(self, tr, name, lane, stats, *rest)

    monkeypatch.setattr(obs_trace._Span, "__init__", spy)
    (src,) = ensure_corpus(str(tmp_path / "inputs"), n_files=1,
                           file_size=30_000)
    runner = TpuTaskRunner(load_plugin_module("tpu_wc"))
    runner.run_map(None, src, 0, 3, str(tmp_path))
    assert runner.device_maps == 1
    assert sorted(os.listdir(tmp_path))[-3:] == ["mr-0-0", "mr-0-1",
                                                 "mr-0-2"]
    assert [name for name, sink in built if not sink] == []
