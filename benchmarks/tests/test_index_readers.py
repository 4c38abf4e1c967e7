"""The readers PR 38 adds for an index job (``layer_metrics/index_*.py``,
``plan_index_stage_s.py``, ``plan_join_stage_s.py``): on a hand-made
``obs`` whose answer can be worked out by eye, on what ``planrun --stats``
printed and the trace reduction gave on the chip
(``recorded/index-pipeline-stats.json``: the jobs of one traced
``plan-index-books`` run, with the reduction's ``modules``), and on a
program that reports no such line or key (the parent), where they have to
return None and must not raise.  The older readers the cell lists are read
over the same record."""

import copy
import importlib
import json
import os

import pytest

import roofline_index

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "index-pipeline-stats.json")
SPAN_READERS = ("plan_index_stage_s", "plan_join_stage_s", "index_wave_ms",
                "index_group_s")
COUNT_READERS = ("index_wave_fill", "index_postings_M")
TRACE_READERS = ("index_wave_ms_per_MiB", "index_wave_roofline")
NEW = SPAN_READERS + COUNT_READERS + TRACE_READERS
OLDER = ("write_s", "plan_tail_s", "step_sort_share", "stream_device_idle",
         "cache_load_s")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "plan-index-1chip.json")) as f:
        return json.load(f)


def _job(t_end, walk, problems=(), **plan):
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": {"stages": {"indexer": walk}, "plan": plan,
                               "write_s": 0.5}}


def _walls(i, d, j):
    return {"indexer": i, "dftopk": d, "join": j}


def test_span_readers_are_medians_over_whole_jobs():
    obs = {"jobs": [
        _job(10.0, {"waves": 300, "group_s": 2.0},
             plan_stage_walls=_walls(6.0, 2.1, 0.1), plan_s=8.2),
        _job(12.0, {"waves": 300, "group_s": 3.0},
             plan_stage_walls=_walls(9.0, 0.2, 3.1), plan_s=12.3),
        _job(11.0, {"waves": 300, "group_s": 2.5},
             plan_stage_walls=_walls(7.5, 2.6, 0.1), plan_s=10.2),
        # a failed job counts for nothing
        _job(1.0, {"waves": 1, "group_s": 9.0}, ["exit code 1"],
             plan_stage_walls=_walls(0.1, 0.1, 0.1), plan_s=0.3)]}
    assert _read("plan_index_stage_s", obs) == pytest.approx(7.5)
    assert _read("plan_join_stage_s", obs) == pytest.approx(2.7)
    assert _read("index_wave_ms", obs) == pytest.approx(25.0)  # 20, 30, 25
    assert _read("index_group_s", obs) == pytest.approx(2.5)


def _traced(**walk):
    return _job(10.0, walk, plan_stage_walls=_walls(6.0, 2.0, 0.1))


def test_counts_are_the_traced_jobs():
    job = _traced(wave_doc_bytes=3 << 20, wave_chunk_bytes=4 << 20,
                  postings_rows=4_060_010)
    for name in COUNT_READERS:
        assert _read(name, {"jobs": [job]}) is None  # no traced job
    obs = {"jobs": [job], "traced_job": job}
    assert _read("index_wave_fill", obs) == pytest.approx(75.0)
    assert _read("index_postings_M", obs) == pytest.approx(4.06001)


def test_trace_readers_divide_by_what_the_job_uploaded():
    """Two waves of 1 MiB and two of 256 KiB under one module name: 2.5
    MiB uploaded, 50 ms on the device."""
    sizes = {1048576: 2, 262144: 2}
    job = _traced(waves_by_size=sizes, wave_chunk_bytes=2 * 1048576
                  + 2 * 262144, wave_doc_bytes=2_000_000)
    obs = {"jobs": [job], "traced_job": job, "config": _config(),
           "traffic": {"kernel": "idx_wave"},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"modules": {
               "jit_idx_wave_step(7)": {"runs": 3, "seconds": 0.03},
               "jit_idx_wave_step(9)": {"runs": 3, "seconds": 0.02},
               "jit_dynamic_slice(3)": {"runs": 4, "seconds": 0.001}}}}
    assert _read("index_wave_ms_per_MiB", obs) == pytest.approx(20.0)
    shapes = _config()["kernels"]["idx_wave"]["shapes"]
    least = 2 * 1048576 + 2 * 262144 + 4 * 65536 * (32 + 28)
    assert roofline_index.wave_bytes(shapes, sizes) == least
    # the counter's keys may have gone through JSON
    assert roofline_index.wave_bytes(
        shapes, {str(k): v for k, v in sizes.items()}) == least
    assert _read("index_wave_roofline", obs) == pytest.approx(
        100 * least / 819e9 / 0.05)
    # a trace cut before the job's end holds fewer runs than waves: a
    # part of the seconds over the whole of the bytes is no reading
    obs["trace"]["modules"]["jit_idx_wave_step(7)"]["runs"] = 0
    assert _read("index_wave_ms_per_MiB", obs) is None
    assert _read("index_wave_roofline", obs) is None
    del obs["trace"]["modules"]["jit_idx_wave_step(7)"]
    del obs["trace"]["modules"]["jit_idx_wave_step(9)"]
    assert _read("index_wave_ms_per_MiB", obs) is None
    assert _read("index_wave_roofline", obs) is None


def _recorded():
    with open(DATA) as f:
        rec = json.load(f)
    return rec, dict(rec["obs"], config=_config(),
                     traffic={"kernel": "idx_wave"},
                     peaks={"hbm_bytes_per_s": 819e9})


def test_on_what_the_chip_recorded():
    rec, obs = _recorded()
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert set(NEW) | set(OLDER) <= set(rec["expected"])
    ps = obs["traced_job"]["pipeline_stats"]
    walk, plan = ps["stages"]["indexer"], ps["plan"]
    # every document through a device wave, three chunk sizes, the
    # handoff on the device, the whole index written from the arrays
    assert walk["docs"] == walk["waves"] > 250
    assert sorted(int(s) for s in walk["waves_by_size"]) == [
        262144, 524288, 1048576]
    assert sum(walk["waves_by_size"].values()) == walk["waves"]
    assert walk["wave_doc_bytes"] == walk["bytes_in"] == 134217216
    assert plan["plan_handoff"] == "device"
    assert walk["postings_rows"] > 3e6 and walk["index_terms"] > 3e5
    assert ps["write_rows_packed"] == walk["index_terms"]
    assert ps["write_rows_dict"] == 0
    assert 60.0 < _read("index_wave_fill", obs) < 80.0
    assert 0.0 < _read("index_wave_roofline", obs) < 100.0
    # the stages and the commit are the job
    walls = sorted(j["t_end"] - j["t_start"] for j in obs["jobs"])
    assert _read("plan_index_stage_s", obs) + _read("plan_join_stage_s",
                                                    obs) \
        + _read("write_s", obs) > 0.9 * walls[len(walls) // 2]


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    """The parent's ``planrun`` commits no index and reports no such key;
    an older stream command prints a flat line; a scope may lack a key."""
    _, obs = _recorded()
    obs = copy.deepcopy(obs)
    jobs = obs["jobs"] + [obs["traced_job"]]
    for job in jobs:
        ps = job["pipeline_stats"]
        ps["plan"] = {"plan_stages": 3}
        ps["stages"]["indexer"] = {"waves": 0, "replays": 2}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"]["stages"] = {}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"] = {"steps": 513, "upload_s": 0.3}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"] = None
    assert _read(name, obs) is None
    assert _read(name, {"jobs": [], "config": _config(),
                        "traffic": {}}) is None
