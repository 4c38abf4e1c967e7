"""``planrun --chain grep-wc --workdir`` against the benchmark's plain
reference.

The chain is driven through its entry point over seeded text from the
benchmark's generator at the separator shares of the cell's traffic mix
(``benchmarks/traffic/filter1pct-1pass.json``); the merged, sorted
``mr-out-*`` must equal, byte for byte and with no tolerance, what
``benchmarks/reference_grepwc.py`` computes (a file that imports nothing of
the program; loaded here by path).  The default handoff, ``--pipeline`` and
``--staged`` are each held to the reference, not only to each other.
"""

import ast
import collections
import importlib.util
import json
import os
import re

import pytest

from dsi_tpu.cli import planrun as cli

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


corpus = _load("corpus")
reference = _load("reference")
reference_grepwc = _load("reference_grepwc")

with open(os.path.join(BENCH, "traffic", "filter1pct-1pass.json")) as _f:
    MIX = json.load(_f)

#: A literal that ``_files(..., marked=True)`` puts on every line.
MARK = " #"


def _files(tmp_path, n_files, seed, end_newline=False, marked=False,
           file_bytes=60_000):
    """Seeded text as the benchmark draws it at the mix's separator shares
    (a file ends mid-line); ``end_newline`` ends every file after its last
    newline instead; ``marked`` ends every line with ``MARK``."""
    params = corpus.effective({"vocab_per_file": 500}, MIX["corpus"])
    paths = []
    for i in range(n_files):
        data = corpus.generate_bytes(file_bytes, seed * 1000 + i, params)
        if marked:
            data = b"\n".join(line + MARK.encode()
                              for line in data.split(b"\n"))
        if end_newline:
            data = data[:data.rindex(b"\n") + 1]
        path = tmp_path / f"pg-{i:02d}.txt"
        path.write_bytes(data)
        paths.append(str(path))
    return paths


def _stats(err: str) -> dict:
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", err, re.M)
    return ast.literal_eval(m.group(1))


def _run(files, pattern, workdir, *flags, devices=1, chunk=4096):
    return cli.main(["--chain", "grep-wc", "--pattern", pattern,
                     "--devices", str(devices), "--nreduce", "10",
                     "--chunk-bytes", str(chunk), "--stats",
                     "--workdir", workdir, *flags, *files])


CASES = {
    # id: (pattern, files, seed, files end in "\n", devices, chunk bytes,
    #      extra flags)
    "mix-1file": (MIX["reference_params"]["pattern"], 1, 11, False, 1,
                  4096, ()),
    "mix-3files": ("; ", 3, 11, False, 1, 4096, ()),
    "mix-3files-end-newline": ("; ", 3, 11, True, 1, 4096, ()),
    "mix-second-seed": ("; ", 3, 3000000011, False, 1, 4096, ()),
    "mix-4devices": ("; ", 3, 11, False, 4, 4096, ()),
    "mix-pipeline": ("; ", 3, 11, False, 1, 4096, ("--pipeline",)),
    "mix-staged": ("; ", 3, 11, False, 1, 4096, ("--staged",)),
    "letters": ("th", 3, 11, False, 1, 4096, ()),
    "letters-1file-end-newline": ("th", 1, 13, True, 1, 4096, ()),
    "letters-4devices-end-newline": ("e", 3, 12, True, 4, 4096, ()),
    "letters-pipeline-4devices": ("e", 3, 12, False, 4, 4096,
                                  ("--pipeline",)),
    "letters-staged-second-seed": ("e", 3, 3000000012, False, 1, 4096,
                                   ("--staged",)),
    "absent": ("QZQ", 3, 11, False, 1, 4096, ()),
    "absent-staged": ("QZQ", 1, 11, True, 1, 4096, ("--staged",)),
    "every-line": (MARK, 3, 11, False, 1, 4096, ()),
    "every-line-end-newline-pipeline": (MARK, 3, 12, True, 1, 4096,
                                        ("--pipeline",)),
    # a 2 KiB relay row seals tens of buffers, and 64 distinct words a
    # step are fewer than a row of this text holds: stage 2 widens
    "many-buffers-widen": ("e", 3, 11, False, 1, 2048, ("--u-cap", "64")),
    "many-buffers-widen-pipeline": ("e", 3, 11, False, 1, 2048,
                                    ("--u-cap", "64", "--pipeline")),
    "many-buffers-widen-staged": ("e", 3, 11, False, 1, 2048,
                                  ("--u-cap", "64", "--staged")),
    "many-buffers-widen-4devices": ("e", 3, 12, True, 4, 2048,
                                    ("--u-cap", "64")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_committed_output_equals_the_plain_reference(case, tmp_path, capsys):
    pattern, n_files, seed, end_newline, devices, chunk, flags = CASES[case]
    files = _files(tmp_path, n_files, seed, end_newline,
                   marked=pattern == MARK)
    workdir = str(tmp_path / "wd")
    assert _run(files, pattern, workdir, *flags, devices=devices,
                chunk=chunk) == 0
    want = reference_grepwc.lines(files, {"pattern": pattern})
    got = reference.read_output(workdir)
    assert got == want
    assert sorted(os.listdir(workdir)) == [f"mr-out-{r}" for r in range(10)]
    ps = _stats(capsys.readouterr().err)
    plan, grep, wc = ps["plan"], ps["stages"]["grep"], ps["stages"]["wc"]
    staged = "--staged" in flags
    assert plan["plan_handoff"] == ("host" if staged else "device")
    assert plan["plan_pipelined"] == int("--pipeline" in flags)
    assert plan["plan_spilled_bytes" if not staged
                else "plan_commit_bytes"] == 0
    assert plan["plan_intermediate_bytes"] == \
        (plan["plan_handoff_bytes"] if staged else 0)
    assert grep["steps"] >= 1 and len(grep["device_rows"]) == devices
    assert min(grep["device_rows"]) > 0 and grep["replays"] == 0
    assert grep["bytes_in"] == sum(os.path.getsize(f) for f in files) \
        + len(files) - 1
    assert wc["bytes_in"] == plan["plan_handoff_bytes"]
    assert plan["relay_appends"] == grep["steps"]
    if pattern == "QZQ":
        assert want == [] and wc["steps"] == 0
        assert plan["plan_handoff_bytes"] == 0
    else:
        assert want and wc["steps"] >= 1 and sum(wc["device_rows"]) > 0
        assert isinstance(ps["write_s"], float)
    if pattern == MARK:
        # every record passed: the chain is a word count of the whole input
        assert want == reference.wc_lines(files, {})
    if "--u-cap" in flags:
        assert wc["replays"] >= 1
        if not staged:
            assert plan["plan_relay_buffers"] >= 10
            assert wc["steps"] >= plan["plan_relay_buffers"]


def test_reference_passes_multiply_the_counts(tmp_path):
    files = _files(tmp_path, 2, 11)
    once = reference_grepwc.lines(files, {"pattern": "e"})
    twice = reference_grepwc.lines(files, {"pattern": "e", "passes": 2})
    assert [l.split()[0] for l in once] == [l.split()[0] for l in twice]
    assert [2 * int(l.split()[1]) for l in once] == \
        [int(l.split()[1]) for l in twice]


def test_new_spans_counters_and_keys_are_recorded(tmp_path, capsys):
    """``relay_append`` with its fields, the ``plan`` span's ``steps`` and
    ``bytes_in``, the ``write`` span around the commit and the relay's two
    counters are in the one tracer's output; the stage scopes and the plan
    scope carry the same numbers."""
    from dsi_tpu.obs import get_tracer

    files = _files(tmp_path, 2, 11)
    trace_dir = tmp_path / "trace"
    tracer = get_tracer()
    was = tracer.enabled
    since = tracer.mark()  # a flush writes the process's whole buffer
    try:
        rc = _run(files, "e", str(tmp_path / "wd"), "--trace-dir",
                  str(trace_dir), chunk=2048)
    finally:
        tracer.enabled = was
    assert rc == 0
    ps = _stats(capsys.readouterr().err)
    plan, stages = ps["plan"], ps["stages"]
    for stage in ("grep", "wc"):
        for key in ("upload_s", "kernel_s", "device_wait_s", "d2h_s",
                    "merge_s", "finalize_s", "batch_s", "batch_wait_s"):
            assert isinstance(stages[stage][key], float), (stage, key)
        assert stages[stage]["steps"] > 1
    assert stages["grep"]["results_ready"] >= 0
    assert stages["wc"]["donate_chunks"] is False
    with open(trace_dir / "trace.jsonl") as f:
        head, *events = [json.loads(line) for line in f]
    assert head["counters"]["relay_appends"] == plan["relay_appends"]
    assert head["counters"]["relay_seals"] == plan["relay_seals"] \
        == plan["plan_relay_buffers"] >= 2
    spans = [e for e in events[since:] if e.get("ph") == "X"]
    names = collections.Counter(e["name"] for e in spans)
    assert names["relay_append"] == plan["relay_appends"]
    assert names["plan"] == 2 and names["write"] == 1
    appends = [e for e in spans if e["name"] == "relay_append"]
    assert all(e["lane"] == "plan" for e in appends)
    assert sum(e["bytes"] for e in appends) == plan["plan_handoff_bytes"]
    # the last buffer is sealed by the consumer's first ask, not an append
    assert sum(e["sealed"] for e in appends) == plan["relay_seals"] - 1
    assert abs(sum(e["dur"] for e in appends)
               - plan["relay_append_s"]) < 1e-3
    by_stage = {e["stage"]: e for e in spans if e["name"] == "plan"}
    for stage in ("grep", "wc"):
        assert by_stage[stage]["steps"] == stages[stage]["steps"]
        assert by_stage[stage]["bytes_in"] == stages[stage]["bytes_in"]
    write = next(e for e in spans if e["name"] == "write")
    committed = sum(os.path.getsize(tmp_path / "wd" / name)
                    for name in os.listdir(tmp_path / "wd"))
    assert write["bytes"] == committed and write["keys"] > 0
    assert abs(write["dur"] - ps["write_s"]) < 1e-3


def test_a_spill_budget_is_counted_and_spanned(tmp_path, capsys, monkeypatch):
    """With a budget of one relay row, every sealed buffer but the open one
    is pulled: ``relay_spill`` spans, spilled bytes counted as intermediate
    bytes, and the answer unchanged."""
    from dsi_tpu.obs import get_tracer

    files = _files(tmp_path, 2, 11)
    monkeypatch.setenv("DSI_PLAN_SPILL_MB", "0.003")
    tracer = get_tracer()
    was = tracer.enabled
    since = tracer.mark()  # a flush writes the process's whole buffer
    try:
        rc = _run(files, "e", str(tmp_path / "wd"), "--trace-dir",
                  str(tmp_path / "trace"), chunk=2048)
    finally:
        tracer.enabled = was
    assert rc == 0
    assert reference.read_output(str(tmp_path / "wd")) == \
        reference_grepwc.lines(files, {"pattern": "e"})
    plan = _stats(capsys.readouterr().err)["plan"]
    assert plan["plan_spilled_bytes"] == plan["plan_intermediate_bytes"] > 0
    assert plan["relay_spill_s"] >= 0.0
    with open(tmp_path / "trace" / "trace.jsonl") as f:
        spills = [e for e in list(map(json.loads, f))[1 + since:]
                  if e.get("name") == "relay_spill"]
    assert sum(e["bytes"] for e in spills) == plan["plan_spilled_bytes"]


@pytest.mark.parametrize("flags", [(), ("--pipeline",), ("--staged",)],
                         ids=["default", "pipeline", "staged"])
def test_a_stage_on_the_host_path_fails_the_job(flags, tmp_path, capsys):
    """A non-ASCII byte in a record that passes reaches the word count,
    which has no device path for it: exit 1, and no ``mr-out-*``."""
    files = _files(tmp_path, 1, 11)
    with open(files[0], "ab") as f:
        f.write("\ncafé ; au lait\n".encode("utf-8"))
    workdir = tmp_path / "wd"
    assert _run(files, "; ", str(workdir), *flags) == 1
    assert "needs the host path" in capsys.readouterr().err
    assert not workdir.exists() or os.listdir(workdir) == []
