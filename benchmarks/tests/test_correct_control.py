"""The comparison that decides ``correct`` has to be able to fail.

``run.py`` is driven from the arguments to the result line over a stand-in
entry point (a plain word count in a few lines, nothing of the program), in
a directory of its own with one cell; the look for a chip is passed by
``--rehearse-cpu``.  Sound, the run is ``correct`` with every compared
number at its limit 0; with the timed path broken underneath (an answer
altered where it is produced, half of the input left out, an answer never
committed, the host path taken, a non-zero exit) ``correct`` comes out
false and the number that caught it is above its limit."""

import json
import os
import sys
import textwrap

import pytest

STAND_IN = textwrap.dedent('''
    """A stream word count with the faults a test can switch on."""
    import collections, os, re, sys

    def main(argv):
        workdir = argv[argv.index("--workdir") + 1]
        fault = argv[argv.index("--fault") + 1]
        files = [a for a in argv if a.endswith(".txt")]
        if fault == "half":
            files = files[:len(files) // 2]
        counts = collections.Counter()
        for path in files:
            with open(path, "rb") as f:
                counts.update(re.findall(rb"[A-Za-z]+", f.read()))
        lines = [f"{w.decode()} {c}" for w, c in sorted(counts.items())]
        if fault == "altered":
            word, _, n = lines[len(lines) // 2].rpartition(" ")
            lines[len(lines) // 2] = f"{word} {int(n) + 1}"
        if fault == "exit" and os.path.basename(workdir) != "warm":
            return 3   # the warm-up passes, a job of the window fails
        if fault == "host":
            print("standin: stream needs the host path", file=sys.stderr)
        os.makedirs(workdir, exist_ok=True)
        if fault != "uncommitted":
            with open(os.path.join(workdir, "mr-out-0"), "w") as f:
                f.write("\\n".join(lines) + "\\n")
        print("standin: pipeline_stats={'steps': 64, 'device_rows': [1]}",
              file=sys.stderr)
        return 0
''')

CONFIG = {
    "name": "standin-1chip", "source": "a test", "driver": "stream_inproc",
    "throughput_metric": "stream_MBps", "entry": "standin_wc",
    "stats_tag": "standin",
    "argv": ["--stats", "--workdir", "{workdir}"],
    "devices": 1, "chunk_bytes": 65536, "trace_seconds": 1,
    "corpus": {"files": 2, "file_bytes": 60000, "vocab_per_file": 500},
    "kernels": {"step": {}},
    "rehearsal": {"corpus": {}},
}

BENCH = {
    "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"],
    "run_seconds": 1,
    "configs": [{"name": "standin-1chip"}],
    "workloads": [{"name": f"standin-{fault}", "config": "standin-1chip",
                   "traffic": f"standin-{fault}", "chips": 1, "why": "a test"}
                  for fault in ("none", "altered", "half", "uncommitted",
                                "host", "exit")],
    "end_to_end": [{"name": "stream_MBps", "unit": "MB/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


@pytest.fixture
def bench_root(tmp_path, monkeypatch):
    """A checkout in small: ``BENCHMARK.json``, a configuration, a mix per
    fault, the stand-in where ``entry`` finds it, an empty ``dsi_tpu``."""
    import run

    here = tmp_path / "benchmarks"
    (here / "configs").mkdir(parents=True)
    (here / "traffic").mkdir()
    (tmp_path / "dsi_tpu").mkdir()
    (tmp_path / "standin_wc.py").write_text(STAND_IN)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    (here / "configs" / "standin-1chip.json").write_text(json.dumps(CONFIG))
    for cell in BENCH["workloads"]:
        fault = cell["name"].split("-", 1)[1]
        (here / "traffic" / f"{cell['traffic']}.json").write_text(json.dumps(
            {"kernel": "step", "reference": "wc", "reference_params": {},
             "passes": 1, "max_jobs": 1, "corpus": {},
             "extra_args": ["--fault", fault]}))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "HERE", str(here))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.delitem(sys.modules, "standin_wc", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / ".jaxcache"))
    return tmp_path


def _run_cell(capsys, fault):
    import run

    rc = run.main(["--workload", f"standin-{fault}", "--seed", "3400000777",
                   "--seconds", "1", "--trace", "0", "--rehearse-cpu"])
    out, err = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"      # its own key, and it comes last
    tail = err.strip().splitlines()[-len(last["compared"]):]
    for line, (name, c) in zip(tail, last["compared"].items()):
        assert line == f"compared {name}: {c['value']} (limit {c['limit']})"
    return last


def test_a_sound_run_is_correct_with_every_number_at_its_limit(
        bench_root, capsys):
    last = _run_cell(capsys, "none")
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 1
    assert {c["value"] for c in last["compared"].values()} == {0}
    assert {c["limit"] for c in last["compared"].values()} == {0}


@pytest.mark.parametrize("fault, caught_by", [
    ("altered", "output_lines_differing"),     # one count off by one
    ("half", "output_lines_differing"),        # half of the input left out
    ("uncommitted", "output_lines_differing"),  # the answer never comes
    ("host", "driver_conditions_broken"),      # right answer, wrong path
    ("exit", "jobs_exited_nonzero"),
])
def test_a_broken_timed_path_is_not_correct(bench_root, capsys, fault,
                                            caught_by):
    last = _run_cell(capsys, fault)
    assert last["correct"] is False
    assert last["failed"] == 1 and last["attempted"] == 1
    caught = last["compared"][caught_by]
    assert caught["value"] > caught["limit"] == 0
    if fault == "altered":
        # the altered line is missing and its stand-in is surplus
        assert caught["value"] == 2
