"""``drivers/stream_inproc`` on a stand-in entry point, and the window's
inputs for a batch mix that repeats its files.

The traced job's profiler is anchored to the job: however soon ``main``
returns, and however long it outlasts ``trace_seconds``, the profiler is
started once, just before the call, and stopped once.  The word-count
configurations run through this driver too, so its checks on a job are tried
on both commands' own wording."""

import argparse
import collections
import json
import os
import sys
import textwrap
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STAND_IN = textwrap.dedent('''
    """A stream command's shape, with nothing of the program in it."""
    import os, sys, time

    CALLS = []

    def main(argv):
        workdir = argv[argv.index("--workdir") + 1]
        nap = float(argv[argv.index("--nap") + 1])
        CALLS.append(list(argv))
        time.sleep(nap)
        if "--refuse" in argv:
            sys.exit("standin: no device")
        os.makedirs(workdir, exist_ok=True)
        with open(os.path.join(workdir, "mr-out-0"), "w") as f:
            f.write("a 1\\n")
        print("standin: pipeline_stats={'steps': 3, 'device_rows': [7]}",
              file=sys.stderr)
        return 0
''')


@pytest.fixture
def stand_in(tmp_path, monkeypatch):
    (tmp_path / "standin_entry.py").write_text(STAND_IN)
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.delitem(sys.modules, "standin_entry", raising=False)
    return tmp_path


@pytest.fixture
def profiler(monkeypatch):
    """``jax.profiler``'s two calls, counted in place of run."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda out_dir: calls.append(("start", out_dir)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    return calls


def _cell(tmp_path, nap, trace_seconds, extra=()):
    """What ``run.Cell`` gives a driver, for a test configuration that
    names the stand-in as its entry."""
    return types.SimpleNamespace(
        name="standin-cell", trace=True, rehearsal=False,
        workroot=str(tmp_path / "work"), files=["f0", "f1"], job_bytes=2048,
        config={"entry": "standin_entry", "stats_tag": "standin",
                "argv": ["--stats", "--workdir", "{workdir}",
                         "--nap", str(nap)],
                "devices": 1, "chunk_bytes": 1024,
                "trace_seconds": trace_seconds},
        traffic={"extra_args": list(extra)}, obs={}, device={})


@pytest.mark.parametrize("nap, trace_seconds, extra, rc", [
    (0.1, 4, [], 0),             # main returns long before the window ends
    (0.4, 0.05, [], 0),          # the timer stops the trace, the exit not again
    (0.1, 4, ["--refuse"], 1),   # the entry point exits: still stopped once
])
def test_a_traced_job_starts_and_stops_the_profiler_exactly_once(
        stand_in, profiler, nap, trace_seconds, extra, rc):
    from drivers import stream_inproc

    cell = _cell(stand_in, nap, trace_seconds, extra)
    job = stream_inproc.run_job(cell, 0)
    assert job["rc"] == rc and job["traced"] is True
    assert [c[0] for c in profiler] == ["start", "stop"]
    assert profiler[0][1] == os.path.join(cell.workroot, "profile")
    assert job["wall_s"] >= nap
    if rc == 0:
        assert job["pipeline_stats"] == {"steps": 3, "device_rows": [7]}
        assert stream_inproc.job_problems(cell, job) == []
    # the flags as data: {workdir} filled in, the mix's arguments, the files
    argv = sys.modules["standin_entry"].CALLS[-1]
    assert argv[:3] == ["--stats", "--workdir",
                        os.path.join(cell.workroot, "job-0")]
    assert argv[-2:] == ["f0", "f1"] and argv[5:-2] == list(extra)


def test_only_the_first_job_of_a_traced_run_is_traced(stand_in, profiler):
    from drivers import stream_inproc

    cell = _cell(stand_in, 0.0, 4)
    assert stream_inproc.run_job(cell, 1)["traced"] is False
    cell.rehearsal = True
    assert stream_inproc.run_job(cell, 0)["traced"] is False
    assert profiler == []


@pytest.mark.parametrize("log_text, stats, want", [
    ("", {"steps": 2, "device_rows": [5]}, []),
    # wcstream's wording and grepstream's
    ("wcstream: stream needs the host path; running host word count",
     {"steps": 2, "device_rows": [5]}, ["host path"]),
    ("grepstream: stream needed the host path; ran the host scan",
     {"steps": 2, "device_rows": [5]}, ["host path"]),
    ("", None, ["no pipeline_stats"]),
    ("", {"steps": 1, "device_rows": [5]}, ["cannot hold"]),
    ("", {"steps": 2, "device_rows": [5, 0]}, ["not every one"]),
    ("", {"steps": 2, "device_rows": []}, ["not every one"]),
])
def test_a_jobs_own_conditions(stand_in, log_text, stats, want):
    """Host path, the steps the bytes need, every device of the layout."""
    from drivers import stream_inproc

    cell = _cell(stand_in, 0.0, 4)
    problems = stream_inproc.job_problems(
        cell, {"log_text": log_text, "pipeline_stats": stats})
    assert len(problems) == len(want)
    for problem, part in zip(problems, want):
        assert part in problem


def test_a_mesh_layout_asks_for_every_device(stand_in):
    from drivers import stream_inproc

    cell = _cell(stand_in, 0.0, 4)
    cell.config.update(devices=4, chunk_bytes=256)
    ok = {"log_text": "", "pipeline_stats": {"steps": 2,
                                             "device_rows": [1, 2, 3, 4]}}
    assert stream_inproc.job_problems(cell, ok) == []
    short = {"log_text": "", "pipeline_stats": {"steps": 2,
                                                "device_rows": [1, 2, 3]}}
    assert len(stream_inproc.job_problems(cell, short)) == 1


def test_the_word_count_configurations_are_traced_from_the_jobs_start():
    """No wall-clock offset is left in any configuration, and the two
    word-count layouts give the stream driver what it reads."""
    configs = os.path.join(ROOT, "benchmarks", "configs")
    for name in sorted(os.listdir(configs)):
        with open(os.path.join(configs, name)) as f:
            cfg = json.load(f)
        assert "trace_after_s" not in cfg, name
        if name.startswith("wcstream-"):
            assert cfg["driver"] == "stream_inproc"
            assert cfg["entry"] == "dsi_tpu.cli.wcstream"
            assert cfg["stats_tag"] == "wcstream"
            at = cfg["argv"].index("--devices")
            assert int(cfg["argv"][at + 1]) == cfg["devices"]
            assert "{workdir}" in cfg["argv"] and "--stats" in cfg["argv"]
            assert cfg["chunk_bytes"] == \
                cfg["kernels"]["step"]["shapes"]["input_bytes"]


def test_a_batch_mix_with_passes_is_one_job_of_every_file_that_often(
        tmp_path, monkeypatch):
    """``batch-grep``: each of the 32 files ``passes`` times in the job's
    arguments, that many times the bytes, every count of the plain
    reference times ``passes``; the corpus on disk stays 32 files.  The
    text follows the run's seed, as in every other cell."""
    import corpus
    import reference
    import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    args = argparse.Namespace(workload="batch-grep", seed=3400000019,
                              seconds=1.0, trace=0, rehearse_cpu=False)
    cell = run.Cell(bench, args)
    passes = cell.passes
    assert passes >= 8 and cell.traffic["max_jobs"] == 1
    assert "corpus_seed" not in cell.traffic
    # the cell's own corpus parameters, at a size a test can hold
    small = cell.config["rehearsal"]["corpus"]
    cell.corpus_params = corpus.effective(
        {**cell.config["corpus"], **small}, cell.traffic["corpus"])
    assert cell.corpus_params["files"] == 32
    cell.cache_root = str(tmp_path / "cache")
    monkeypatch.setattr(run, "log", lambda msg: None)
    run.prepare_inputs(cell)
    assert len(cell.files) == 32 * passes
    each = collections.Counter(cell.files)
    assert len(each) == 32 and set(each.values()) == {passes}
    files = sorted(each)
    once = sum(os.path.getsize(f) for f in files)
    assert once == 32 * small["file_bytes"]
    assert cell.job_bytes == passes * once
    one_pass = reference.grep_lines(
        files, dict(cell.traffic["reference_params"], passes=1))
    assert one_pass
    want = []
    for line in one_pass:
        text, _, count = line.rpartition(" ")
        want.append(f"{text} {int(count) * passes}")
    assert cell.reference_lines == sorted(want)
    # the same seed again: the same files in the same order, found where
    # the first run left them, and the answer loaded
    logged = []
    monkeypatch.setattr(run, "log", logged.append)
    same = run.Cell(bench, args)
    same.corpus_params, same.cache_root = cell.corpus_params, cell.cache_root
    run.prepare_inputs(same)
    inputs = json.loads(logged[0])["inputs"]
    assert inputs["generated"] is False and inputs["reference"] == "loaded"
    assert same.files == cell.files
    assert same.reference_lines == cell.reference_lines


def test_a_batch_mix_takes_its_text_from_the_runs_seed(tmp_path,
                                                       monkeypatch):
    """Another seed is another text and another answer, and the cache
    holds one seed's corpus at a time."""
    import corpus
    import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    monkeypatch.setattr(run, "log", lambda msg: None)
    cells = []
    for seed in (3400000019, 3400000023):
        args = argparse.Namespace(workload="batch-grep", seed=seed,
                                  seconds=1.0, trace=0, rehearse_cpu=False)
        cell = run.Cell(bench, args)
        cell.corpus_params = corpus.effective(
            {**cell.config["corpus"], **cell.config["rehearsal"]["corpus"]},
            cell.traffic["corpus"])
        cell.cache_root = str(tmp_path / "cache")
        run.prepare_inputs(cell)
        with open(cell.files[0], "rb") as f:
            cells.append((cell, f.read()))
    (first, text_a), (second, text_b) = cells
    assert text_a != text_b
    assert first.reference_lines != second.reference_lines
    assert first.job_bytes == second.job_bytes
    assert not os.path.exists(os.path.dirname(first.files[0]))
    assert os.path.basename(os.path.dirname(second.files[0])).endswith(
        f"-s{second.seed}")
