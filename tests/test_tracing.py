"""The DSI_TRACE structured-event layer (utils/tracing.py).

The worker's task bodies must emit a
per-task timeline under DSI_TRACE=1, and the tracing module must carry no
dead code.  The reference has no tracing at all (SURVEY.md §5) — this layer
is additive observability; these tests pin its contract.
"""

import json

from dsi_tpu.utils.tracing import Span, log_event


def _trace_lines(capsys):
    err = capsys.readouterr().err
    out = []
    for line in err.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("event"):
            out.append(rec)
    return out


def test_span_emits_event_when_traced(monkeypatch, capsys):
    monkeypatch.setenv("DSI_TRACE", "1")
    with Span("unit.phase", task=7) as s:
        pass
    assert s.elapsed_s >= 0
    (rec,) = _trace_lines(capsys)
    assert rec["event"] == "span"
    assert rec["name"] == "unit.phase"
    assert rec["task"] == 7
    assert rec["seconds"] >= 0


def test_silent_without_env(monkeypatch, capsys):
    monkeypatch.delenv("DSI_TRACE", raising=False)
    with Span("quiet.phase"):
        pass
    log_event("custom", x=1)
    assert _trace_lines(capsys) == []


def test_worker_tasks_emit_timeline(monkeypatch, capsys, tmp_path):
    # A real 1-coordinator + 2-worker job under DSI_TRACE=1 must produce one
    # worker.map span per input file and one worker.reduce span per
    # partition that ran.
    from tests.harness import run_distributed_threads

    monkeypatch.setenv("DSI_TRACE", "1")
    files = []
    for i in range(3):
        p = tmp_path / f"in-{i}.txt"
        p.write_text(f"alpha beta file{i} gamma")
        files.append(str(p))
    run_distributed_threads("wc", files, str(tmp_path), n_workers=2,
                            n_reduce=4)
    recs = _trace_lines(capsys)
    spans = [r for r in recs if r["event"] == "span"]
    maps = [r for r in spans if r["name"] == "worker.map"]
    reduces = [r for r in spans if r["name"] == "worker.reduce"]
    assert sorted(r["task"] for r in maps) == [0, 1, 2]
    assert {r["file"] for r in maps} == set(files)
    assert sorted(r["task"] for r in reduces) == [0, 1, 2, 3]
    assert all(r["seconds"] >= 0 for r in spans)
    # The coordinator side of the timeline: one assign and one complete per
    # task (no crashes/requeues in this run).
    assigns = [r for r in recs if r["event"] == "assign"]
    completes = [r for r in recs if r["event"] == "complete"]
    assert sorted(r["task"] for r in assigns if r["kind"] == "map") == [0, 1, 2]
    assert sorted(r["task"] for r in completes
                  if r["kind"] == "reduce") == [0, 1, 2, 3]


def test_no_dead_tracing_api():
    # PhaseTimer / maybe_jax_profile were dead code: they must
    # stay deleted rather than unreferenced.
    import dsi_tpu.utils.tracing as t

    public = {n for n in dir(t) if not n.startswith("_")
              and getattr(getattr(t, n), "__module__", None) == t.__name__}
    assert public == {"Span", "log_event"}
