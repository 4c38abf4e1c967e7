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


@pytest.fixture
def fresh_tracer(monkeypatch):
    """A disabled tracer in the process-global slot, for a command that
    may turn it on with ``--trace-dir``; off again afterwards."""
    monkeypatch.delenv("DSI_TRACE_DIR", raising=False)
    tracer = Tracer(enabled=False)
    monkeypatch.setattr(obs_trace, "_global", tracer)
    yield tracer
    tracer.enabled = False


def _stream_main(command, tmp_path, *flags, src=None):
    """One small job of ``wcstream`` or ``grepstream`` in this process:
    ``(exit code, its pipeline_stats, its work directory)``."""
    pytest.importorskip("jax")
    import importlib

    main = importlib.import_module(f"dsi_tpu.cli.{command}").main
    if src is None:
        (src,) = ensure_corpus(str(tmp_path / "inputs"), n_files=1,
                               file_size=120_000)
    out = str(tmp_path / f"out-{len(os.listdir(tmp_path))}")
    argv = ["--devices", "2", "--chunk-bytes", "16384", "--stats",
            "--workdir", out, *flags, src]
    argv = (["--pattern", "the"] if command == "grepstream"
            else ["--nreduce", "3"]) + argv
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = main(argv)
    m = re.search(rf"^{command}: pipeline_stats=(\{{.*\}})$",
                  err.getvalue(), re.M)
    return rc, ast.literal_eval(m.group(1)), out


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


def _tracer_under_a_task(tmp_path):
    jax = pytest.importorskip("jax")
    t = Tracer(enabled=True, trace_dir=str(tmp_path / "t"))
    with t.span("worker.map", lane="control", kind="map", task=0):
        with t.span("kernel"):
            jax.block_until_ready(jax.numpy.arange(8) + 1)
    t.flush()


def _small_wcstream_job(tmp_path):
    assert _stream_main("wcstream", tmp_path, "--trace-dir",
                        str(tmp_path / "t"))[0] == 0


@pytest.mark.parametrize("traced, want", [
    (_tracer_under_a_task, {"dsi:worker.map", "dsi:kernel"}),
    (_small_wcstream_job, {"dsi:job", "dsi:enqueue", "dsi:compact"}),
], ids=["a-task", "a-wcstream-job"])
def test_spans_ride_the_profilers_clock(tmp_path, fresh_tracer, traced,
                                        want):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        traced(tmp_path)
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
    assert want | {"dsi.clock"} <= set(seen), seen
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


@pytest.mark.parametrize("flags, replays", [
    ((), False), (("--aot",), False), (("--u-cap", "64"), True)],
    ids=["host-merge", "aot", "small-rung"])
def test_pull_spans_say_which_pack_served_them(fresh_tracer, tmp_path,
                                               flags, replays):
    """A step's ``pull`` span says whether the tensor packed at the
    step's dispatch served it (``early``); the two counters are the
    spans' count, plus the replays' payloads, which are pulled inside
    their ``replay`` span; the pull's two parts still lie inside it."""
    from dsi_tpu.obs.registry import COUNTER_KEYS

    rc, ps, _ = _stream_main("wcstream", tmp_path, "--trace-dir",
                             str(tmp_path / "trace"), *flags)
    assert rc == 0
    _, events = _jsonl(str(tmp_path / "trace" / "trace.jsonl"))
    pulls = [e for e in events if e["ph"] == "X" and e["name"] == "pull"]
    early = [e for e in pulls if e["early"]]
    assert len(early) == ps["pulls_early"] >= 1
    assert len(pulls) - len(early) == ps["pulls_late"] - ps["replays"]
    assert ps["pulls_early"] + ps["pulls_late"] == ps["step_pulls"]
    assert (ps["replays"] >= 1) == replays
    if not replays:  # the start rung's prefix held every step's table
        assert ps["pulls_late"] == 0
    assert "pulls_early" in COUNTER_KEYS and "pulls_late" in COUNTER_KEYS
    assert ps["device_wait_s"] + ps["d2h_s"] <= ps["pull_s"] + 2e-4
    # the parts are the spans inside the pulls, whichever pack they read
    spans = {e["id"]: e for e in events if e["ph"] == "X"}
    for name, key in (("wait", "device_wait_s"), ("d2h", "d2h_s")):
        inside = [e for e in spans.values() if e["name"] == name
                  and spans[e["parent"]]["name"] == "pull"]
        assert len(inside) == len(pulls)
        assert ps[key] == pytest.approx(sum(e["dur"] for e in inside),
                                        abs=5e-4), name


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


# ── a stream job's main thread, accounted for ──────────────────────────


@pytest.mark.parametrize("command, flags", [
    ("wcstream", ()), ("grepstream", ()),
    # no batcher thread: the batches are cut on the main thread too
    ("wcstream", ("--pipeline-depth", "1"))],
    ids=["wcstream", "grepstream", "wcstream-depth1"])
def test_stream_stats_account_for_the_main_thread(tracing_off, tmp_path,
                                                  command, flags):
    rc, ps, _ = _stream_main(command, tmp_path, *flags)
    assert rc == 0 and ps["depth"] == (1 if flags else 2)
    for key in ("job_s", "job_children_s", "start_s", "dispatch_s",
                "retire_s", "enqueue_s"):
        assert ps[key] > 0, key
    assert 0.95 * ps["job_s"] <= ps["job_children_s"] <= ps["job_s"] + 1e-3
    # the step program's call is inside the dispatch, the upload beside it
    assert ps["enqueue_s"] + ps["upload_s"] <= ps["dispatch_s"] + 2e-4


def _children(events, parent):
    return [e for e in events if e["ph"] == "X"
            and e["parent"] == parent["id"]]


@pytest.mark.parametrize("command, flags", [
    ("wcstream", ()), ("wcstream", ("--device-accumulate",)),
    ("grepstream", ())], ids=["wcstream", "wcstream-accumulate",
                              "grepstream"])
def test_job_children_are_the_registrys_tuple(fresh_tracer, tmp_path,
                                              monkeypatch, command, flags):
    from dsi_tpu.obs.registry import JOB_CHILDREN
    from dsi_tpu.parallel.merge import PackedCounts

    # compactions inside the steps' merges too, not only the last one
    monkeypatch.setattr(PackedCounts.__init__, "__defaults__", (512, None))
    rc, ps, _ = _stream_main(command, tmp_path, "--trace-dir",
                             str(tmp_path / "trace"), *flags)
    assert rc == 0
    _, events = _jsonl(str(tmp_path / "trace" / "trace.jsonl"))
    spans = {e["id"]: e for e in events if e["ph"] == "X"}
    (job,) = [e for e in spans.values() if e["name"] == "job"]
    assert job["parent"] is None and job["depth"] == 0
    kids = _children(events, job)
    key_of = dict(JOB_CHILDREN)
    want = {"start", "wait", "dispatch", "finish", "finalize", "write"}
    if flags:
        want.add("drain")
    assert {e["name"] for e in kids} == want <= set(key_of)
    # the keys are the spans: what the stats line subtracts is what the
    # trace holds under the root
    for name in want:
        total = sum(e["dur"] for e in kids if e["name"] == name)
        assert ps[key_of[name]] == pytest.approx(total, abs=5e-4), name
    assert ps["job_s"] == pytest.approx(job["dur"], abs=1e-4)
    assert ps["job_s"] - ps["job_children_s"] == pytest.approx(
        job["dur"] - sum(e["dur"] for e in kids), abs=2e-3)
    # the parts of a step, and of the tail, where the work happens
    parents = {}
    for e in spans.values():
        up = spans.get(e["parent"])
        parents.setdefault(e["name"], set()).add(up and up["name"])
    assert parents["upload"] == parents["enqueue"] == {"dispatch"}
    inside_finish = ("kernel",) if flags else ("kernel", "pull", "merge")
    for name in inside_finish:
        assert parents[name] == {"finish"}, name
    assert parents.get("replay", {"finish"}) == {"finish"}
    if command == "wcstream":
        # a window that filled is compacted on the merger's thread, a
        # root there; the last, partial one under ``finalize``; and a
        # wait for the merger belongs to whichever span handed the rows
        # over (a replayed step merges inside its ``replay``) or asked
        # for the table
        where = {"sync"} if flags else {"merge", "replay"}
        assert ps["merge_compacts_async"] >= 1
        assert None in parents["compact"] <= {None, "finalize"}
        assert parents.get("merge_wait", set()) <= where | {"finalize"}
        # the writer reads the merged table's arrays: nothing is decoded
        assert "decode" not in parents
        assert parents["format"] == parents["commit"] == {"write"}
        if flags:
            assert "drain" in parents["sync"]


@pytest.mark.parametrize("flags", [(), ("--device-accumulate",),
                                   ("--mesh-shards", "2")],
                         ids=["host-merge", "device-accumulate",
                              "mesh-shards"])
def test_wcstream_tail_builds_no_object_per_word(tracing_off, tmp_path,
                                                 flags):
    from dsi_tpu.obs.registry import COUNTER_KEYS, PHASE_KEYS

    rc, ps, out = _stream_main("wcstream", tmp_path, *flags)
    assert rc == 0
    (src,) = glob.glob(str(tmp_path / "inputs" / "*"))
    with open(src, encoding="ascii") as f:
        keys = len(set(re.findall(r"[A-Za-z]+", f.read())))
    # every row of mr-out-* was rendered from the arrays, none formatted
    # from a dict, and no spelling became a str
    assert ps["write_rows_packed"] == keys and ps["write_rows_dict"] == 0
    assert ps["finalize_decoded_keys"] == 0
    lines = 0
    for r in range(3):
        with open(os.path.join(out, f"mr-out-{r}"), "rb") as f:
            lines += f.read().count(b"\n")
    assert lines == keys
    # the spans' keys stay, as numbers: the decode's reads 0.0
    assert ps["finalize_decode_s"] == 0.0
    assert ps["write_format_s"] > 0 and ps["write_commit_s"] > 0
    assert ps["write_format_s"] + ps["write_commit_s"] <= ps["write_s"] + 1e-3
    assert 0.95 * ps["job_s"] <= ps["job_children_s"] <= ps["job_s"] + 1e-3
    for key in ("write_rows_packed", "write_rows_dict",
                "finalize_decoded_keys"):
        assert key in COUNTER_KEYS, key
    for key in ("finalize_decode_s", "write_format_s", "write_commit_s"):
        assert key in PHASE_KEYS, key


def test_wcstream_host_fallback_formats_from_the_dict(tracing_off,
                                                      tmp_path):
    src = tmp_path / "in.txt"
    src.write_text("caf\u00e9 words caf\u00e9 and more words",
                   encoding="utf-8")
    rc, ps, out = _stream_main("wcstream", tmp_path, src=str(src))
    assert rc == 0
    assert ps["write_rows_dict"] == 4 and ps["write_rows_packed"] == 0
    assert "finalize_decoded_keys" not in ps  # no table was finalized
    assert ps["write_format_s"] >= 0 and ps["write_commit_s"] > 0
    got = b"".join(open(os.path.join(out, f"mr-out-{r}"), "rb").read()
                   for r in range(3))
    assert sorted(got.decode("utf-8").split("\n")) == [
        "", "and 1", "caf\u00e9 2", "more 1", "words 2"]


def test_merge_counters_equal_a_hand_count():
    np = pytest.importorskip("numpy")
    from dsi_tpu.parallel.merge import PackedCounts

    def rows(*words):
        # word i is "w" and three digits, packed big-endian in one lane
        keys = np.array([[int.from_bytes(b"w%03d" % w, "big")]
                         for w in words], dtype=np.uint32).reshape(-1, 1)
        ones = np.ones(len(words), dtype=np.int32)
        return keys, 4 * ones, ones.astype(np.int64), ones

    stats = {}
    acc = PackedCounts(compact_rows=16, stats=stats)
    acc.add(*rows(*range(0, 5)))      # 5 in the window
    acc.add(*rows(*range(3, 8)))      # 10
    acc.add(*rows(*range(6, 12)))     # 16: three runs merged, 12 distinct
    acc.add(*rows(0, 1, 20))          # 3: the window alone is counted
    acc.add(*rows())                  # nothing handed over
    acc.add(*rows(9, 4, 4))           # no run: sorted on entry, 2 distinct
    got = acc.finalize()              # two runs, 5 rows, into the table
    assert len(got) == 13 and got["w007"] == (2, 1) and got["w020"] == (1, 1)
    assert got["w004"] == (4, 1)
    counts = {k: stats[k] for k in (
        "merge_rows_in", "merge_rows_sorted", "merge_compacts",
        "merge_runs_in", "merge_runs_unsorted")}
    # sorted: 16 in the first window, 3 on entry, 5 in the second window;
    # the merged table's 12 rows never again
    assert counts == {"merge_rows_in": 22, "merge_rows_sorted": 24,
                      "merge_compacts": 2, "merge_runs_in": 5,
                      "merge_runs_unsorted": 1}
    assert stats["compact_s"] > 0 and stats["finalize_decode_s"] > 0
    # one table and nothing new: finalize has nothing to merge
    assert len(acc.finalize()) == 13 and stats["merge_compacts"] == 2
    # an accumulator without an engine keeps its own
    assert PackedCounts().stats["merge_rows_in"] == 0


def test_wcstream_merge_counters_repeat_and_the_output_is_the_oracles(
        tracing_off, tmp_path, monkeypatch):
    from dsi_tpu.mr.worker import ihash
    from dsi_tpu.parallel.merge import PackedCounts

    monkeypatch.setattr(PackedCounts.__init__, "__defaults__", (512, None))
    runs = [_stream_main("wcstream", tmp_path) for _ in range(2)]
    assert [rc for rc, _, _ in runs] == [0, 0]
    counters = ("merge_rows_in", "merge_rows_sorted", "merge_compacts",
                "merge_runs_in", "merge_runs_unsorted")
    first, second = ({k: ps[k] for k in counters} for _, ps, _ in runs)
    assert first == second
    ps = runs[0][1]
    # every confirmed row of every step is handed to the accumulator
    assert first["merge_rows_in"] == sum(ps["device_rows"])
    assert first["merge_compacts"] > 1
    # every batch is a device's step table, a run as it arrives: nothing
    # is sorted on entry, every row is ordered once, in its window, and
    # the merged table never again
    assert first["merge_runs_in"] >= ps["steps"]
    assert first["merge_runs_unsorted"] == 0
    assert first["merge_rows_sorted"] == first["merge_rows_in"]
    # a window of 512 rows: the compactions follow the rows handed over
    assert first["merge_compacts"] <= first["merge_rows_in"] // 512 + 1
    # what is written has not changed: each partition holds its words in
    # order, one "word count" line each, as the parent commit wrote them
    (src,) = glob.glob(str(tmp_path / "inputs" / "*"))
    with open(src, encoding="ascii") as f:
        counts = {}
        for w in re.findall(r"[A-Za-z]+", f.read()):
            counts[w] = counts.get(w, 0) + 1
    for _, _, out in runs:
        assert sorted(os.listdir(out)) == ["mr-out-0", "mr-out-1",
                                           "mr-out-2"]
        for r in range(3):
            want = "".join(f"{w} {c}\n" for w, c in sorted(counts.items())
                           if ihash(w) % 3 == r)
            with open(os.path.join(out, f"mr-out-{r}"), "rb") as f:
                assert f.read() == want.encode(), r


@pytest.mark.parametrize("command", ["wcstream", "grepstream"])
def test_untraced_stream_step_builds_no_span_without_a_sink(
        tracing_off, tmp_path, monkeypatch, command):
    built = []
    init = obs_trace._Span.__init__

    def spy(self, tr, name, lane, stats, *rest):
        built.append((name, stats is not None))
        init(self, tr, name, lane, stats, *rest)

    monkeypatch.setattr(obs_trace._Span, "__init__", spy)
    rc, ps, _ = _stream_main(command, tmp_path)
    assert rc == 0
    assert [name for name, sink in built if not sink] == []
    # pump's spans are the keys' accumulators now, one each a step
    names = [name for name, _ in built]
    for name in ("dispatch", "finish", "enqueue"):
        assert names.count(name) == ps["steps"], name
    assert names.count("job") == names.count("start") == 1


# ── the indexer chain: the wave walk's spans and counters ──────────────


def test_indexer_chain_records_its_new_spans_and_counters(fresh_tracer,
                                                          tmp_path):
    """``planrun --chain indexer --trace-dir``: the wave program's call is
    an ``enqueue`` inside ``dispatch`` (or a ``replay``), the table is
    grouped once in a ``group`` span inside the stage that first needs it,
    the index commit is a ``write`` with ``format`` / ``commit`` inside;
    the seconds are the stats keys, and the counters say what was walked."""
    pytest.importorskip("jax")
    from dsi_tpu.cli import planrun
    from dsi_tpu.obs.registry import COUNTER_KEYS, PHASE_KEYS

    docs = ensure_corpus(str(tmp_path / "inputs"), n_files=3,
                         file_size=30_000)
    # a fourth, shorter document: a second chunk size
    short = tmp_path / "inputs" / "short.txt"
    with open(docs[0], "rb") as f:
        short.write_bytes(f.read(9_000))
    docs.append(str(short))
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = planrun.main(["--chain", "indexer", "--devices", "1",
                           "--nreduce", "3", "--u-cap", "256", "--stats",
                           "--workdir", str(tmp_path / "wd"),
                           "--trace-dir", str(tmp_path / "trace"), *docs])
    assert rc == 0, err.getvalue()[-2000:]
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", err.getvalue(),
                  re.M)
    ps = ast.literal_eval(m.group(1))
    walk = ps["stages"]["indexer"]
    new_counters = {"docs", "waves_by_size", "wave_doc_bytes",
                    "wave_chunk_bytes", "postings_rows", "index_terms",
                    "group_runs", "group_rows_sorted"}
    assert new_counters <= set(walk) and new_counters <= set(COUNTER_KEYS)
    assert {"group_s", "enqueue_s", "dispatch_s", "retire_s"} <= set(walk)
    assert "group_s" in PHASE_KEYS
    sizes = [os.path.getsize(d) for d in docs]
    assert walk["docs"] == walk["waves"] == 4
    assert walk["wave_doc_bytes"] == sum(sizes) == walk["bytes_in"]
    assert walk["waves_by_size"] == {32768: 3, 16384: 1}
    assert walk["wave_chunk_bytes"] == 3 * 32768 + 16384
    assert walk["replays"] >= 1              # wider than 256 words
    assert walk["postings_rows"] >= walk["index_terms"] > 256
    assert ps["write_rows_packed"] == walk["index_terms"]
    assert ps["write_rows_dict"] == 0

    _, events = _jsonl(str(tmp_path / "trace" / "trace.jsonl"))
    spans = {e["id"]: e for e in events if e["ph"] == "X"}
    assert {e["name"] for e in spans.values()} <= obs_trace.SPAN_NAMES
    parents = {}
    for e in spans.values():
        up = spans.get(e["parent"])
        parents.setdefault(e["name"], set()).add(up and up["name"])
    assert parents["enqueue"] <= {"dispatch", "replay"}
    assert "dispatch" in parents["enqueue"]
    assert parents["upload"] == parents["enqueue"]
    assert parents["group"] == {"plan"}
    assert parents["format"] == parents["commit"] == {"write"}
    (group,) = [e for e in spans.values() if e["name"] == "group"]
    assert group["rows"] == walk["postings_rows"]
    assert group["terms"] == walk["index_terms"]
    # a wave's rows leave the device in word order: a run a wave, merged,
    # and no row through a sort
    assert group["runs"] == walk["group_runs"] == walk["waves"]
    assert group["rows_sorted"] == walk["group_rows_sorted"] == 0
    assert walk["group_s"] == pytest.approx(group["dur"], abs=5e-4)
    for name, key in (("enqueue", "enqueue_s"), ("dispatch", "dispatch_s"),
                      ("finish", "retire_s")):
        total = sum(e["dur"] for e in spans.values() if e["name"] == name)
        assert walk[key] == pytest.approx(total, abs=5e-4), name
    (write,) = [e for e in spans.values() if e["name"] == "write"]
    assert write["keys"] == walk["index_terms"] and write["bytes"] > 0
    assert ps["write_s"] == pytest.approx(write["dur"], abs=5e-4)
    # the wave program has its own name in a device trace
    enq = [e for e in spans.values() if e["name"] == "enqueue"]
    assert {e["program"] for e in enq} == {"idx_wave_step"}


# ── the starvation account ─────────────────────────────────────────────


class _Clock:
    """``time`` for ``obs/trace.py``: the test moves it."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def time_ns(self):
        return int(self.now * 1e9)


class _Result:
    """A stub of a device array: the test sets its readiness, counts who
    asks and says what an answer costs on its clock."""

    def __init__(self, ready=False, clock=None, cost_s=0.0):
        self.ready = ready
        self.asked = 0
        self.clock, self.cost_s = clock, cost_s

    def is_ready(self):
        self.asked += 1
        if self.clock is not None:
            self.clock.now += self.cost_s
        return self.ready


@pytest.fixture
def account(monkeypatch):
    """A tracer with tracing off on a clock the test moves:
    ``(tracer, clock)``."""
    clock = _Clock()
    monkeypatch.setattr(obs_trace, "time", clock)
    monkeypatch.setattr(obs_hist, "_active", None)
    tracer = Tracer(enabled=False)
    monkeypatch.setattr(obs_trace, "_global", tracer)
    return tracer, clock


def _walk_one_job(tracer, clock, stats):
    """One step of a job whose every piece has a length of its own."""
    res = _Result()
    with tracer.span("job", stats=stats):
        clock.now += 1.0                      # job: nothing in flight
        with tracer.span("start", stats=stats):
            clock.now += 2.0
        with tracer.span("dispatch", stats=stats):
            clock.now += 0.5                  # before the program's call
            with tracer.span("enqueue", lane="dispatch", stats=stats):
                clock.now += 0.25
                tracer.enqueued(res)          # began with nothing: ran dry
                clock.now += 0.25
            clock.now += 0.1                  # in flight at both ends: fed
        with tracer.span("finish", stats=stats, key="retire_s"):
            clock.now += 1.0                  # fed
            with tracer.span("kernel", stats=stats):
                clock.now += 3.0              # held by the device
                res.ready = True
            clock.now += 0.2                  # ran out somewhere in here
            with tracer.span("merge", stats=stats):
                clock.now += 0.7              # nothing in flight
        clock.now += 0.3
    return res


def test_account_pieces_partition_the_root(account):
    from dsi_tpu.obs.registry import STARVED_GROUPS

    tracer, clock = account
    stats = {}
    _walk_one_job(tracer, clock, stats)
    assert stats["job_s"] == pytest.approx(9.3)
    # each piece on the innermost span that was open through it
    assert stats["starved_by"] == pytest.approx(
        {"job": 1.3, "start": 2.0, "dispatch": 0.5, "enqueue": 0.5,
         "finish": 0.2, "merge": 0.7})
    assert stats["starved_s"] == pytest.approx(5.2)
    # dry: nothing queued at its start and nothing enqueued in it
    assert stats["starved_dry_s"] == pytest.approx(1.3 + 2.0 + 0.5 + 0.7)
    assert stats["starved_groups"] == pytest.approx(
        {"input": 2.0, "dispatch": 1.0, "merge": 0.9, "tail": 1.3})
    assert [g for g, _ in STARVED_GROUPS] == list(stats["starved_groups"])
    assert sum(stats["starved_groups"].values()) == pytest.approx(
        stats["starved_s"])
    # fed is the rest: the pieces sum to the root
    fed = 0.1 + 1.0 + 3.0
    assert stats["starved_s"] + fed == pytest.approx(stats["job_s"])
    # the account is closed, and lets go of the job's array
    assert tracer._acct is None and tracer.newest(0) is None


def test_every_span_name_is_in_one_starved_group():
    from dsi_tpu.obs.registry import DEVICE_BLOCKED, STARVED_GROUPS

    listed = [name for _, names in STARVED_GROUPS for name in names]
    assert len(listed) == len(set(listed))
    assert set(listed) <= obs_trace.SPAN_NAMES
    # the names of a stream or plan job's main thread are all listed
    for name in ("start", "read", "read_wait", "sample", "wait", "plan",
                 "dispatch", "upload", "enqueue", "relay_append", "finish",
                 "pull", "merge", "compact", "merge_wait", "replay", "fold",
                 "sync",
                 "widen", "group", "ckpt", "drain", "finalize", "decode",
                 "write", "format", "commit", "report", "job"):
        assert name in listed, name
    assert {name for name, _ in DEVICE_BLOCKED} <= obs_trace.SPAN_NAMES
    assert {lane for _, lane in DEVICE_BLOCKED} <= set(obs_trace.LANES)


def test_the_mergers_wait_and_keys_are_registered():
    """``merge_wait``, the accumulator's caller held by a compaction in
    flight, is a span of the ``merge`` lane and group; the two keys the
    accumulator adds to its scope are the schema's."""
    from dsi_tpu.obs.registry import (COUNTER_KEYS, PHASE_KEYS,
                                      STARVED_GROUPS)
    from dsi_tpu.parallel.merge import PackedCounts

    assert "merge_wait" in obs_trace.SPAN_NAMES
    assert "merge_wait" in dict(STARVED_GROUPS)["merge"]
    assert obs_trace._GROUP_OF["merge_wait"] == "merge"
    assert "compact_caller_s" in PHASE_KEYS
    assert "merge_compacts_async" in COUNTER_KEYS
    stats = PackedCounts().stats
    assert stats["merge_compacts_async"] == 0
    assert stats["compact_caller_s"] == 0.0


def test_a_device_blocked_span_is_never_starved(account):
    from dsi_tpu.obs.registry import DEVICE_BLOCKED

    tracer, clock = account
    stats = {}
    with tracer.span("job", stats=stats):
        for name, lane in DEVICE_BLOCKED:
            # nothing in flight, and yet: held by the device by definition
            with tracer.span(name, lane=lane, stats=stats):
                clock.now += 1.0
        # the step loop's wait for its producer is not the pull's wait
        with tracer.span("wait", lane="materialize", stats=stats,
                         key="batch_wait_s"):
            clock.now += 0.5
    assert stats["starved_by"] == pytest.approx({"wait": 0.5})
    assert stats["starved_s"] == pytest.approx(0.5)
    assert stats["job_s"] == pytest.approx(len(DEVICE_BLOCKED) + 0.5)


def _step(tracer, clock, stats, res, older, device_s):
    """One turn of a depth-2 step loop: ``res``'s dispatch, then the
    retirement of ``older`` (``(ordinal, result)`` as the pipeline core
    keeps it), whose program the device ends ``device_s`` into the
    ``kernel`` span.  Returns what the core keeps of ``res``."""
    before = tracer.enqueued_n
    with tracer.span("dispatch", stats=stats):
        with tracer.span("upload", stats=stats):
            clock.now += 300e-6
        with tracer.span("enqueue", lane="dispatch", stats=stats):
            clock.now += 100e-6
            tracer.enqueued(res)
            clock.now += 100e-6
    told = tracer.newest(before)
    if older is not None:
        with tracer.span("finish", stats=stats, key="retire_s"):
            tracer.landed(*older)
            with tracer.span("kernel", stats=stats):
                clock.now += device_s
                older[1].ready = True
            with tracer.span("pull", stats=stats):
                with tracer.span("wait", lane="pull", stats=stats,
                                 key="device_wait_s"):
                    clock.now += 10e-6
                with tracer.span("d2h", lane="pull", stats=stats):
                    clock.now += 200e-6
            with tracer.span("merge", stats=stats):
                clock.now += 1e-3
    with tracer.span("wait", lane="materialize", stats=stats,
                     key="batch_wait_s"):
        clock.now += 20e-6
    return told


@pytest.mark.parametrize("cost_s", [0.25e-6, 40e-6], ids=["cheap", "dear"])
def test_account_spends_its_share_of_a_device_paced_job_on_looks(account,
                                                                 cost_s):
    """Whatever an answer costs, asking takes ``_LOOK_SHARE`` of the wall
    at most (and one look more: the first is free)."""
    tracer, clock = account
    stats = {}
    results = [_Result(clock=clock, cost_s=cost_s) for _ in range(40)]
    with tracer.span("job", stats=stats):
        older = None
        for res in results:
            # the device is a step behind: 8 ms of each retirement
            older = _step(tracer, clock, stats, res, older, 8e-3)
    looks = sum(r.asked for r in results)
    # the pipeline's own look a step is asked whatever it costs
    own = len(results) - 1
    assert (looks - own - 1) * cost_s <= \
        obs_trace._LOOK_SHARE * stats["job_s"]
    # the chip had work all along, bar the first step's way to it; what
    # it took for fed without a look at either end it counts as unseen
    first = 300e-6 + 100e-6
    assert stats["starved_s"] == pytest.approx(first, abs=2e-4)
    if cost_s < 1e-6:
        assert stats["starved_unseen_s"] <= 0.005 * stats["job_s"]
    else:   # the host's 1.7 ms of a step that the device does not hold
        assert 0.05 * stats["job_s"] <= stats["starved_unseen_s"] \
            <= 0.2 * stats["job_s"]
    assert stats["starved_s"] + stats["starved_unseen_s"] <= stats["job_s"]


def test_account_asks_a_host_paced_dispatch_once(account):
    tracer, clock = account
    stats = {}
    results = [_Result(ready=True) for _ in range(40)]
    with tracer.span("job", stats=stats):
        older = None
        for res in results:
            # the program is done before its ``enqueue`` span is
            older = _step(tracer, clock, stats, res, older, 0.0)
    # seen ready at the first boundary behind it; not asked again, by
    # the account or by the pipeline's ``landed``
    assert [r.asked for r in results] == [1] * len(results)
    # every piece but the blocked ones began or ended with nothing queued
    blocked = 39 * (0.0 + 10e-6 + 200e-6)
    assert stats["starved_s"] == pytest.approx(stats["job_s"] - blocked,
                                               abs=2e-4)
    assert stats["starved_groups"]["dispatch"] == pytest.approx(
        40 * 500e-6, abs=2e-4)
    assert stats["starved_dry_s"] <= stats["starved_s"]


class _TimedResult(_Result):
    """Ready once the test's clock has reached ``at``."""

    def __init__(self, clock):
        super().__init__()
        self.clock, self.at = clock, None

    def is_ready(self):
        self.asked += 1
        return self.clock.now >= self.at


@pytest.mark.parametrize("device_s, idle, bounds", [
    # the chip runs out between two dispatches, step after step
    (1.1e-3, 0.50, (0.35, 0.90)),
    # it runs out for a moment a step, inside the next dispatch
    (2.0e-3, 0.10, (0.0, 0.70)),
    # it never does: the steps queue up behind each other
    (3.0e-3, 0.0, (0.0, 0.02))], ids=["half-idle", "nearly-fed", "fed"])
def test_account_finds_the_chip_idle_between_dispatches(account, device_s,
                                                        idle, bounds):
    """A step's program of ``device_s`` behind a host that takes 2.2 ms a
    step: the chip's true idle share lies between the account's two
    bounds."""
    tracer, clock = account
    stats = {}
    results = [_TimedResult(clock) for _ in range(120)]
    busy_until = busy = 0.0
    with tracer.span("job", stats=stats):
        older = None
        for res in results:
            before = tracer.enqueued_n
            with tracer.span("dispatch", stats=stats):
                with tracer.span("upload", stats=stats):
                    clock.now += 680e-6
                with tracer.span("enqueue", lane="dispatch", stats=stats):
                    clock.now += 80e-6   # the call reaches the device
                    res.at = max(clock.now, busy_until) + device_s
                    busy_until, busy = res.at, busy + device_s
                    clock.now += 720e-6  # and returns
                    tracer.enqueued(res)
            told = tracer.newest(before)
            if older is not None:
                with tracer.span("finish", stats=stats, key="retire_s"):
                    tracer.landed(*older)
                    with tracer.span("kernel", stats=stats):
                        clock.now = max(clock.now, older[1].at) + 5e-6
                    with tracer.span("merge", stats=stats):
                        for _ in range(14):   # the host's part, in pieces
                            with tracer.span("compact", stats=stats):
                                clock.now += 50e-6
            older = told
        clock.now = max(clock.now, busy_until)
    job = stats["job_s"]
    assert (job - busy) / job == pytest.approx(idle, abs=0.06)
    low, high = bounds
    assert low <= stats["starved_dry_s"] / job <= (job - busy) / job + 0.01
    assert (job - busy) / job - 0.01 <= stats["starved_s"] / job <= high
    # looks that cost nothing are all due: nothing is left unseen
    assert stats["starved_unseen_s"] == 0.0


def test_another_threads_spans_do_not_cut(account):
    tracer, clock = account
    stats, other = {}, {}
    res = _Result()

    def reader():
        # a producer's spans, and a program it enqueues: not boundaries
        with tracer.span("materialize", stats=other, key="batch_s"):
            tracer.enqueued(res)

    with tracer.span("job", stats=stats):
        clock.now += 1.0
        with tracer.span("merge", stats=stats):
            clock.now += 1.0
            t = threading.Thread(target=reader)
            t.start()
            t.join()
            clock.now += 1.0    # the other thread fed the chip in here
        clock.now += 1.0        # in flight at both ends
    assert "batch_s" in other and "starved_s" not in other
    assert stats["starved_by"] == pytest.approx({"job": 1.0, "merge": 2.0})
    assert stats["starved_dry_s"] == pytest.approx(1.0)
    # a span with a sink on a thread without an account costs no ask
    assert "materialize" not in stats["starved_by"]


def test_a_deleted_array_lets_the_newest_answer(account):
    """An array donated to a later program cannot say whether the chip
    has run the one that made it: the newest one told answers for it,
    and where there is none the chip counts as busy."""
    tracer, clock = account

    class Donated:
        def is_ready(self):
            raise RuntimeError("Array has been deleted.")

    stats = {}
    with tracer.span("job", stats=stats):
        tracer.enqueued(Donated())
        told = tracer.newest(0)
        clock.now += 1.0
        with tracer.span("merge", stats=stats):
            clock.now += 1.0
        assert not tracer.landed(*told)
        newer = _Result()
        tracer.enqueued(newer)
        assert not tracer.landed(*told)
        newer.ready = True
        assert tracer.landed(*told) and newer.asked == 2
    # began with nothing; after that nobody could say
    assert stats["starved_by"] == pytest.approx({"job": 1.0})


def test_what_is_taken_for_fed_unseen_is_counted(account):
    """At a boundary at which something is in flight and no look is due
    the chip is taken for busy; a piece taken for fed with neither end
    seen is counted in ``starved_unseen_s``."""
    tracer, clock = account
    stats = {}
    res = _Result(clock=clock, cost_s=50e-6)   # the next look: 10 ms on
    with tracer.span("job", stats=stats):
        with tracer.span("dispatch", stats=stats):
            tracer.enqueued(res)
            clock.now += 1e-3             # began with nothing: starved
        # asked at ``dispatch``'s exit: busy; the look took 50 us
        with tracer.span("merge", stats=stats):
            clock.now += 2e-3             # neither end seen
        with tracer.span("compact", stats=stats):
            clock.now += 3e-3             # neither end seen
        with tracer.span("merge", stats=stats):
            clock.now += 4e-3             # neither end seen
        with tracer.span("compact", stats=stats):
            clock.now += 20e-3            # asked at its end: busy, fed
        with tracer.span("merge", stats=stats):
            clock.now += 30e-3
            res.ready = True
        clock.now += 1.0                  # asked: ran out in here
    assert res.asked == 3
    assert stats["starved_unseen_s"] == pytest.approx(9e-3, abs=2e-4)
    assert stats["starved_by"] == pytest.approx(
        {"dispatch": 1e-3, "merge": 30e-3, "job": 1.0}, abs=2e-4)


def test_traced_span_records_carry_their_own_dry_seconds(monkeypatch,
                                                         tmp_path):
    clock = _Clock()
    monkeypatch.setattr(obs_trace, "time", clock)
    tracer = Tracer(enabled=True, trace_dir=str(tmp_path / "trace"))
    try:
        stats = {}
        _walk_one_job(tracer, clock, stats)
        spans = {e["name"]: e for e in _flushed(tracer) if e["ph"] == "X"}
    finally:
        tracer.enabled = False
    dry = {name: e["dry"] for name, e in spans.items() if "dry" in e}
    assert dry == pytest.approx(stats["starved_by"])
    assert "dry" not in spans["kernel"]
    # a sink-less span records, and is cut, only while tracing is on
    assert stats["starved_s"] == pytest.approx(5.2)


def _plan_inputs(tmp_path, chain):
    if chain == "sort":
        import numpy as np

        path = tmp_path / "records.bin"
        path.write_bytes(np.random.default_rng(3).integers(
            0, 256, size=100 * 700, dtype=np.uint8).tobytes())
        return [str(path)]
    if chain == "agg":
        path = tmp_path / "rows.txt"
        path.write_text("".join(
            f"10.{i % 7}.{i % 13}.{i % 5}|u{i}|d|{i % 9}.{i % 100:02d}|a\n"
            for i in range(3000)))
        return [str(path)]
    return ensure_corpus(str(tmp_path / "inputs"), n_files=2,
                         file_size=60_000)


def _planrun_main(tmp_path, chain, *flags):
    pytest.importorskip("jax")
    from dsi_tpu.cli import planrun

    argv = ["--chain", chain, "--devices", "1", "--nreduce", "3",
            "--chunk-bytes", "16384", "--stats", "--workdir",
            str(tmp_path / "wd"), *flags]
    if chain == "grep-wc":
        argv += ["--pattern", "the"]
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = planrun.main(argv + _plan_inputs(tmp_path, chain))
    assert rc == 0, err.getvalue()[-2000:]
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", err.getvalue(),
                  re.M)
    return ast.literal_eval(m.group(1))


def _holds_the_account(ps):
    from dsi_tpu.obs.registry import PHASE_KEYS, STARVED_GROUPS

    assert list(ps["starved_groups"]) == [g for g, _ in STARVED_GROUPS]
    assert sum(ps["starved_groups"].values()) == pytest.approx(
        ps["starved_s"], abs=1e-6)
    assert sum(ps["starved_by"].values()) == pytest.approx(
        ps["starved_s"], abs=5e-4 * len(ps["starved_by"]))
    assert 0 <= ps["starved_dry_s"] <= ps["starved_s"] <= ps["job_s"]
    assert 0 <= ps["starved_unseen_s"] <= ps["job_s"] - ps["starved_s"] \
        + 1e-3
    assert set(ps["starved_by"]) <= obs_trace.SPAN_NAMES
    # held by the device: never starved
    assert "kernel" not in ps["starved_by"]
    assert "d2h" not in ps["starved_by"]
    for key in ("starved_s", "starved_dry_s", "starved_unseen_s",
                "starved_by", "starved_groups"):
        assert key in PHASE_KEYS, key


@pytest.mark.parametrize("command, flags", [
    ("wcstream", ()), ("wcstream", ("--device-accumulate",)),
    ("grepstream", ())], ids=["wcstream", "wcstream-accumulate",
                              "grepstream"])
def test_stream_stats_say_where_the_chip_was_starved(tracing_off, tmp_path,
                                                     command, flags):
    rc, ps, _ = _stream_main(command, tmp_path, *flags)
    assert rc == 0
    _holds_the_account(ps)
    # the start ends before the first program is enqueued: all of it dry
    assert ps["starved_by"]["start"] == pytest.approx(ps["start_s"],
                                                      abs=2e-4)
    assert ps["starved_dry_s"] >= ps["starved_by"]["start"] - 2e-4
    # every engine on the pipeline core counts its steps found done
    assert 0 <= ps["results_ready"] <= ps["steps"]
    if flags:
        assert 0 < ps["sync_wait_s"] <= ps["fold_s"] + ps["sync_s"] + 1e-3


@pytest.mark.parametrize("chain", ["grep-wc", "indexer", "sort", "agg"])
def test_planrun_stats_say_where_the_chip_was_starved(tracing_off,
                                                      tmp_path, chain):
    from dsi_tpu.obs.registry import PHASE_KEYS, plan_job_children_s

    ps = _planrun_main(tmp_path, chain)
    _holds_the_account(ps)
    for key in ("job_s", "start_s", "report_s", "job_children_s",
                "write_s"):
        assert ps[key] > 0, key
        assert key in PHASE_KEYS, key
    assert ("read_s" in ps) == (chain == "indexer")
    assert ps["job_children_s"] == pytest.approx(plan_job_children_s(ps),
                                                 abs=1e-4)
    assert 0.95 * ps["job_s"] <= ps["job_children_s"] <= ps["job_s"] + 1e-3
    assert {"start", "plan"} <= set(ps["starved_by"])
    # per stage as before, and the account nowhere but at the top
    for stage in ps["stages"].values():
        assert "starved_s" not in stage


@pytest.mark.parametrize("chain", ["grep-wc", "indexer", "sort"])
def test_planrun_job_children_are_the_registrys_tuple(fresh_tracer,
                                                      tmp_path, chain):
    from dsi_tpu.obs.registry import PLAN_JOB_CHILDREN

    ps = _planrun_main(tmp_path, chain, "--trace-dir",
                       str(tmp_path / "trace"))
    _, events = _jsonl(str(tmp_path / "trace" / "trace.jsonl"))
    spans = {e["id"]: e for e in events if e["ph"] == "X"}
    (job,) = [e for e in spans.values() if e["name"] == "job"]
    assert job["parent"] is None and job["depth"] == 0
    kids = _children(events, job)
    key_of = dict(PLAN_JOB_CHILDREN)
    want = {"start", "plan", "write", "report"}
    if chain == "indexer":
        want.add("read")
    assert {e["name"] for e in kids} == want <= set(key_of)
    stages = {"grep-wc": 2, "indexer": 3, "sort": 2}[chain]
    assert len([e for e in kids if e["name"] == "plan"]) == stages
    for name in want:
        total = sum(e["dur"] for e in kids if e["name"] == name)
        got = ps["plan"]["plan_s"] if name == "plan" else ps[key_of[name]]
        assert got == pytest.approx(total, abs=5e-4), name
    assert ps["job_s"] == pytest.approx(job["dur"], abs=1e-4)
    assert ps["job_s"] - ps["job_children_s"] == pytest.approx(
        job["dur"] - sum(e["dur"] for e in kids), abs=2e-3)
    # what the records say was starved is what the line says
    dry = {}
    for e in spans.values():
        if "dry" in e:
            dry[e["name"]] = dry.get(e["name"], 0.0) + e["dry"]
    for name in set(dry) | set(ps["starved_by"]):
        assert dry.get(name, 0.0) == pytest.approx(
            ps["starved_by"].get(name, 0.0), abs=2e-3), name
    assert sum(dry.values()) <= job["dur"] + 1e-3


def test_cached_compile_times_its_two_halves(global_tracer):
    jax = pytest.importorskip("jax")
    from dsi_tpu.backends import aotcache

    before = dict(aotcache.stats)
    fn = aotcache.cached_compile(
        "test_two_halves", lambda x: x * 2 + 1,
        (jax.ShapeDtypeStruct((8,), "int32"),))
    assert fn(jax.numpy.arange(8, dtype="int32"))[3] == 7
    assert aotcache.stats["compiles"] == before["compiles"] + 1
    assert aotcache.stats["lowered_s"] > before["lowered_s"]
    assert aotcache.stats["compiled_s"] > before["compiled_s"]
    spans = [e for e in _flushed(global_tracer) if e["ph"] == "X"]
    assert [(e["name"], e["program"]) for e in spans] == [
        ("lower", "test_two_halves"), ("compile", "test_two_halves")]
    assert {"lower", "compile"} <= obs_trace.SPAN_NAMES
