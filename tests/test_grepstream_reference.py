"""``grepstream --workdir`` against the benchmark's plain reference.

The command is driven through its entry point over seeded text from the
benchmark's generator; its committed ``mr-out-0`` must equal, byte for
byte and with no tolerance, what ``benchmarks/reference_grepstats.py``
computes (a file that imports nothing of the program; loaded here by
path).  The guarantees are exact counts and the exact top-16.
"""

import ast
import collections
import importlib.util
import json
import os
import re

import numpy as np
import pytest

from dsi_tpu.cli import grepstream as cli

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
reference_grepstats = _load("reference_grepstats")


def _rare_trigram(data: bytes) -> str:
    """A lower-case three-letter literal that occurs in ``data``, but
    rarely: the least frequent of those that occur three times or more."""
    grams = collections.Counter(
        data[i:i + 3] for i in range(len(data) - 2))
    count, gram = min((c, g) for g, c in grams.items()
                      if c >= 3 and g.isalpha() and g.islower())
    return gram.decode("ascii")


def _files(tmp_path, n_files, seed, end_newline, short_lines,
           runs_of_a=False):
    """Seeded text as the benchmark draws it (a file ends mid-line);
    ``end_newline`` ends every file after its last newline instead;
    ``short_lines`` breaks the text every four bytes, so that a step holds
    more lines than an eighth of its bytes;
    ``runs_of_a`` turns a file's first sixty ``e`` into ``aaa``, so that
    ``aa`` overlaps itself there."""
    params = corpus.effective({"vocab_per_file": 500}, {})
    paths = []
    for i in range(n_files):
        data = corpus.generate_bytes(30_000, seed * 1000 + i, params)
        if runs_of_a:
            data = data.replace(b"e", b"aaa", 60)
        if short_lines:
            data = data[:len(data) // 4 * 4]
            cut = np.frombuffer(data, np.uint8).reshape(-1, 4)
            data = np.concatenate(
                [cut, np.full((len(cut), 1), 10, np.uint8)], axis=1).tobytes()
        if end_newline:
            data = data[:data.rindex(b"\n") + 1]
        path = tmp_path / f"pg-{i:02d}.txt"
        path.write_bytes(data)
        paths.append(str(path))
    return paths


def _stats(err: str) -> dict:
    m = re.search(r"^grepstream: pipeline_stats=(\{.*\})$", err, re.M)
    return ast.literal_eval(m.group(1))


CASES = {
    # id: (pattern kind, files, seed, files end in "\n", short lines, devices)
    "rare-1file": ("rare", 1, 11, False, False, 1),
    "rare-3files": ("rare", 3, 11, False, False, 1),
    "rare-3files-end-newline": ("rare", 3, 11, True, False, 1),
    "rare-second-seed": ("rare", 3, 2600000011, False, False, 1),
    "rare-4devices": ("rare", 3, 11, False, False, 4),
    "common-e": ("e", 3, 11, False, False, 1),
    "common-e-4devices-end-newline": ("e", 3, 12, True, False, 4),
    "absent": ("QZQ", 3, 11, False, False, 1),
    "self-overlapping-aa": ("aa", 3, 11, False, False, 1),
    "self-overlapping-aa-1file-end-newline": ("aa", 1, 13, True, False, 1),
    "short-lines": ("e", 3, 11, False, True, 1),
    "short-lines-4devices": ("rare", 3, 12, True, True, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_committed_output_equals_the_plain_reference(case, tmp_path, capsys):
    kind, n_files, seed, end_newline, short_lines, devices = CASES[case]
    files = _files(tmp_path, n_files, seed, end_newline, short_lines,
                   runs_of_a=kind == "aa")
    pattern = kind
    if kind == "rare":
        with open(files[0], "rb") as f:
            pattern = _rare_trigram(f.read())
    workdir = str(tmp_path / "wd")
    rc = cli.main(["--pattern", pattern, "--workdir", workdir,
                   "--devices", str(devices), "--topk", "16", "--stats",
                   "--chunk-bytes", "2048" if short_lines else "4096",
                   *files])
    assert rc == 0
    want = reference_grepstats.lines(
        files, {"pattern": pattern, "bins": 8, "topk": 16})
    got = reference.read_output(workdir)
    assert got == want
    assert os.listdir(workdir) == ["mr-out-0"]  # one commit, no temp file
    by_name = {l.split()[0]: l.split()[1:] for l in want
               if l.split()[0] in ("lines", "matched", "occurrences")}
    if kind == "QZQ":
        assert by_name["matched"] == ["0"]
        assert not any(l.startswith("top ") for l in want)
    else:
        assert int(by_name["matched"][0]) > 0
    if kind in ("e", "aa"):  # some line holds the pattern more than once
        assert int(by_name["occurrences"][0]) > int(by_name["matched"][0])
    ps = _stats(capsys.readouterr().err)
    assert "needed the host path" not in ps
    assert ps["replays"] == 0 and ps["steps"] >= 1
    assert len(ps["device_rows"]) == devices and min(ps["device_rows"]) > 0
    assert sum(ps["device_rows"]) == int(by_name["lines"][0])


def test_reference_passes_repeat_the_file_list(tmp_path):
    files = _files(tmp_path, 2, 11, False, False)
    params = {"pattern": "e", "bins": 8, "topk": 16}
    assert reference_grepstats.lines(files, dict(params, passes=2)) == \
        reference_grepstats.lines(files * 2, params)


def test_commit_is_atomic(tmp_path, monkeypatch):
    """A job that fails leaves no ``mr-out-0`` and no temp file."""
    files = _files(tmp_path, 1, 11, False, False)
    workdir = tmp_path / "wd"
    argv = ["--pattern", "e", "--workdir", str(workdir), "--devices", "1",
            "--chunk-bytes", "4096", *files]

    def refuse(src, dst):
        raise OSError("no rename today")

    with monkeypatch.context() as m:
        m.setattr(os, "rename", refuse)
        with pytest.raises(OSError, match="no rename today"):
            cli.main(argv)
    assert os.listdir(workdir) == []
    assert cli.main(argv) == 0
    assert os.listdir(workdir) == ["mr-out-0"]


def test_new_spans_counter_and_keys_are_recorded(tmp_path, capsys):
    """``wait`` and ``d2h`` inside ``pull``, ``finalize``, ``write`` and
    the ``pull_bytes`` count are in the one tracer's output and in
    ``pipeline_stats``."""
    from dsi_tpu.obs import get_tracer

    files = _files(tmp_path, 2, 11, False, False)
    trace_dir = tmp_path / "trace"
    tracer = get_tracer()
    was = tracer.enabled
    # the tracer is the process's: an earlier test of this worker may have
    # left spans in its buffer, and a flush writes the whole buffer
    since = tracer.mark()
    try:
        rc = cli.main(["--pattern", "e", "--workdir", str(tmp_path / "wd"),
                       "--devices", "1", "--chunk-bytes", "4096", "--stats",
                       "--trace-dir", str(trace_dir), *files])
    finally:
        tracer.enabled = was
    assert rc == 0
    ps = _stats(capsys.readouterr().err)
    for key in ("pull_s", "device_wait_s", "d2h_s", "finalize_s", "write_s",
                "kernel_s", "merge_s", "upload_s", "batch_wait_s"):
        assert isinstance(ps[key], float), key
    assert ps["steps"] == ps["step_pulls"] > 1
    # per step: the histogram row (11 x u32) and 16 candidate rows of 5 x u32
    assert ps["pull_bytes"] == ps["steps"] * (44 + 320)
    assert ps["pull_s"] >= ps["device_wait_s"] + ps["d2h_s"] - 1e-3
    with open(trace_dir / "trace.jsonl") as f:
        events = [json.loads(line) for line in f][1 + since:]
    spans = {e["id"]: e for e in events if e.get("ph") == "X"}
    names = collections.Counter(e["name"] for e in spans.values())
    assert names["pull"] == names["d2h"] == ps["steps"]
    assert names["finalize"] == names["write"] == 1
    # a pull is a wait for the device, then the copy (the pipeline has
    # ``wait`` spans of its own, for input batches, outside any pull)
    in_pull = collections.Counter(
        e["name"] for e in spans.values()
        if e.get("parent") in spans and spans[e["parent"]]["name"] == "pull")
    assert in_pull == {"wait": ps["steps"], "d2h": ps["steps"]}
    d2h = [e for e in spans.values() if e["name"] == "d2h"]
    assert all(e["bytes"] == 364 for e in d2h)
    write = next(e for e in spans.values() if e["name"] == "write")
    committed = os.path.getsize(tmp_path / "wd" / "mr-out-0")
    assert write["records"] >= 11 and write["bytes"] == committed
    final = next(e for e in spans.values() if e["name"] == "finalize")
    assert final["cands"] >= 1
