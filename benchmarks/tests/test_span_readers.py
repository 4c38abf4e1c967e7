"""The readers of the program's own task, launch and tail spans: each on a
hand-made ``obs`` whose answer can be worked out by eye, each on what the
program recorded on the chip (``recorded/batch-grep-spans.json``, the first
maps of a ``tpu_grep`` job through ``mrrun --trace-dir``, and
``recorded/stream-pipeline-stats.json``, the ``pipeline_stats`` lines of two
``wcstream`` jobs), and each on a program that does not open such spans,
where it has to return None."""

import copy
import importlib
import json
import os

import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")

BATCH = ("map_read_s", "map_device_wait_s", "map_decode_s", "map_write_s",
         "task_gap_ms", "probe_s", "worker_boot_s")
STREAM = ("device_wait_share", "pull_d2h_s", "finalize_s", "write_s")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _ev(ph, name, pid, wall, dur=0.0, **fields):
    return {"ph": ph, "name": name, "lane": fields.pop("lane", name),
            "pid": pid, "wall": wall, "ts": wall - 1000.0, "dur": dur,
            "depth": 0, **fields}


def _batch_obs():
    """One device worker (pid 7) with two maps and a reduce, ``mrrun``
    (pid 1) with its probe, a host helper (pid 8) that only starts."""
    events = [
        _ev("I", "mrrun.start", 1, 1000.0, lane="launch", parent=None),
        _ev("X", "probe", 1, 1000.1, 11.0, lane="launch", id=1, parent=None,
            chips=1),
        _ev("I", "worker.start", 8, 1012.5, lane="launch", parent=None),
        _ev("I", "worker.start", 7, 1012.6, lane="launch", parent=None),
        _ev("I", "backend_up", 7, 1022.6, lane="launch", parent=None,
            platform="tpu", kind="TPU v5 lite", count=1),
    ]
    for task, t0 in ((0, 1023.0), (1, 1024.0)):
        mid = 10 * (task + 1)
        events.append(_ev("X", "worker.map", 7, t0, 0.9, lane="control",
                          id=mid, parent=None, kind="map", task=task))
        parts = (("read", 0.01 + task * 0.02), ("decode", 0.02),
                 ("upload", 0.03), ("kernel", 0.5), ("pull", 0.07),
                 ("decode", 0.1), ("write", 0.04 + task * 0.02))
        at = t0
        for i, (name, dur) in enumerate(parts):
            events.append(_ev("X", name, 7, at, dur, id=mid + 1 + i,
                              parent=mid, kind="map", task=task))
            at += dur
        # a grandchild is its parent's time, not the map's twice
        events.append(_ev("X", "decode", 7, t0 + 0.5, 0.3, id=mid + 9,
                          parent=mid + 4, kind="map", task=task))
    events.append(_ev("X", "worker.reduce", 7, 1024.95, 0.1, lane="control",
                      id=40, parent=None, kind="reduce", task=0))
    return {"traced_job": {"spawn_wall": 999.5,
                           "spans": {"events": events, "counters": {}}}}


def _stream_obs():
    def job(t_end, **ps):
        return {"t_start": 0.0, "t_end": t_end, "problems": [],
                "pipeline_stats": ps}
    return {"jobs": [
        job(20.0, kernel_s=1.0, pull_s=12.5, device_wait_s=12.0, d2h_s=0.5,
            finalize_s=2.0, write_s=1.0),
        job(40.0, kernel_s=2.0, pull_s=21.0, device_wait_s=20.0, d2h_s=0.7,
            finalize_s=3.0, write_s=1.5)]}


def test_batch_readers_on_a_hand_made_job():
    obs = _batch_obs()
    assert _read("map_read_s", obs) == pytest.approx(0.02)   # 0.01, 0.03
    assert _read("map_device_wait_s", obs) == pytest.approx(0.6)
    assert _read("map_decode_s", obs) == pytest.approx(0.12)
    assert _read("map_write_s", obs) == pytest.approx(0.05)  # 0.04, 0.06
    # map 0 ends 1023.9, map 1 starts 1024.0 and ends 1024.9, reduce 1024.95
    assert _read("task_gap_ms", obs) == pytest.approx(75.0)
    assert _read("probe_s", obs) == pytest.approx(11.0)
    assert _read("worker_boot_s", obs) == pytest.approx(10.0)


def test_stream_readers_on_hand_made_jobs():
    obs = _stream_obs()
    # (1 + 12) / 20 = 65 %, (2 + 20) / 40 = 55 %
    assert _read("device_wait_share", obs) == pytest.approx(60.0)
    assert _read("pull_d2h_s", obs) == pytest.approx(0.6)
    assert _read("finalize_s", obs) == pytest.approx(2.5)
    assert _read("write_s", obs) == pytest.approx(1.25)


def test_readers_find_nothing_in_a_program_without_these_spans():
    """The parent's trace: task spans mirrored after the fact (no ``id``,
    no ``parent``, no children), no launch lane; ``pipeline_stats`` without
    the newer keys; and no traced job at all."""
    old = copy.deepcopy(_batch_obs())
    events = old["traced_job"]["spans"]["events"]
    events[:] = [{k: v for k, v in e.items() if k not in ("id", "parent")}
                 for e in events
                 if e["name"] in ("worker.map", "worker.reduce")]
    stream = _stream_obs()
    for j in stream["jobs"]:
        j["pipeline_stats"] = {"kernel_s": 1.0, "pull_s": 12.5}
    for name in BATCH:
        assert _read(name, old) is None, name
        assert _read(name, {}) is None, name
    for name in STREAM:
        assert _read(name, stream) is None, name
        assert _read(name, {"jobs": []}) is None, name


def _recorded(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.skip(f"no {name} under benchmarks/tests/recorded")
    with open(path) as f:
        return json.load(f)


def test_batch_readers_on_a_recorded_job():
    rec = _recorded("batch-grep-spans.json")
    obs = rec["obs"]
    got = {name: _read(name, obs) for name in BATCH + ("map_task_s",)}
    assert got == pytest.approx(rec["expected"], rel=1e-9, abs=1e-9)
    parts = sum(got[n] for n in ("map_read_s", "map_device_wait_s",
                                 "map_decode_s", "map_write_s"))
    assert 0.9 * got["map_task_s"] <= parts <= 1.02 * got["map_task_s"]
    # the readers the benchmark had read this trace as before
    assert _read("launch_s", obs) == pytest.approx(rec["launch_s"])
    assert _read("reduce_phase_s", obs) is None   # cut before the reduces


def test_stream_readers_on_recorded_jobs():
    rec = _recorded("stream-pipeline-stats.json")
    got = {name: _read(name, rec["obs"])
           for name in STREAM + ("pull_share",)}
    assert got == pytest.approx(rec["expected"], rel=1e-9, abs=1e-9)


def test_a_split_quantity_reads_through_the_quantitys_reader():
    """``batch-grep`` reports its throughput under a metric of its own, so
    its batch-plane quantities carry the suffix ``.grep``: each is read by
    the file of the name before the dot and printed under the whole name,
    and the cell keeps none of the unsplit names that move the other
    metric."""
    import argparse

    import run

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    args = argparse.Namespace(workload="batch-grep", seed=1, seconds=1.0,
                              trace=1, rehearse_cpu=False)
    cell = run.Cell(bench, args)
    assert cell.traffic["throughput_metric"] == "batch_plane_grep_MBps"
    mine = {m["name"]: m for m in cell.metric_entries("per_layer")}
    split = {n for n in mine if n.endswith(".grep")}
    assert {"map_task_s.grep", "map_write_s.grep",
            "worker_device_idle.grep"} <= split
    for name, m in mine.items():
        assert m["moves"] in ("setup_s", "batch_plane_grep_MBps"), name
    cell.obs = _batch_obs()
    spans_only = ("map_read_s", "map_device_wait_s", "map_decode_s",
                  "map_write_s", "task_gap_ms")   # what the hand-made obs holds
    entries = [mine[name + ".grep"] for name in spans_only]
    cell.metric_entries = lambda group: entries
    got = run.read_layer_metrics(cell)
    assert set(got) == {name + ".grep" for name in spans_only}
    for name in spans_only:
        assert name not in got
        assert got[name + ".grep"]["value"] == pytest.approx(
            _read(name, cell.obs))
        assert got[name + ".grep"]["unit"] == mine[name + ".grep"]["unit"]
