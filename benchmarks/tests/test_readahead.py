"""What PR 42 adds for the index cells: the reader
``layer_metrics/index_read_wait_s.py``.

It is tried, as ``test_indexpack.py`` tries PR 41's three, on a hand-made
``obs`` whose answer can be worked out by eye, on what ``planrun --stats``
printed on the chip (``recorded/readahead-pipeline-stats.json``: the jobs of
one traced ``plan-index-pages`` run of the program that reads its documents
ahead of the walk), and on a program that prints no such key (the parent,
which reads its documents whole before the first stage), where it returns
None and does not raise.  ``index_read_s`` reads the same line's ``read_s``
as before: there it is the lengths alone."""

import copy
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "readahead-pipeline-stats.json")
PARENT = os.path.join(HERE, "recorded", "indexpack-pipeline-stats.json")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _job(t_end, problems=(), **top):
    ps = {"stages": {"indexer": {"waves": 130, "docs": 12000}},
          "plan": {}, "write_s": 0.5}
    ps.update(top)
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": ps}


def test_the_median_over_whole_jobs():
    obs = {"jobs": [
        _job(4.0, read_s=0.2, read_wait_s=0.05),
        _job(4.4, read_s=0.2, read_wait_s=0.0),
        _job(4.2, read_s=0.2, read_wait_s=0.3),
        _job(4.1, read_s=0.2, read_wait_s=0.1),
        # a failed job counts for nothing
        _job(1.0, ["exit code 1"], read_s=0.2, read_wait_s=9.0)]}
    assert _read("index_read_wait_s", obs) == pytest.approx(0.075)
    # a walk that waited for nothing reads 0.0, a number and not None
    obs = {"jobs": [_job(4.0, read_s=0.2, read_wait_s=0.0)]}
    assert _read("index_read_wait_s", obs) == 0.0


def test_on_what_the_chip_recorded():
    with open(DATA) as f:
        rec = json.load(f)
    obs = rec["obs"]
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert {"index_read_wait_s", "index_read_s"} <= set(rec["expected"])
    assert len(obs["jobs"]) >= 8
    for job in obs["jobs"]:
        ps = job["pipeline_stats"]
        walk = ps["stages"]["indexer"]
        # every document asked for once, by the walk; most were there
        assert ps["read_docs"] == walk["docs"] > 11_000
        assert 0.9 * ps["read_docs"] < ps["read_ahead_hits"] \
            <= ps["read_docs"]
        assert ps["read_threads"] >= 1
        # the lengths, not the bytes; the wait inside the walk
        assert ps["read_s"] < 0.5
        assert ps["read_wait_s"] < ps["plan"]["plan_stage_walls"]["indexer"]
        assert walk["pack_docs"] is True and walk["pack_s"] > 0.0


def test_none_where_the_program_prints_no_such_key():
    """The parent's line (PR 41's recording) has ``read_s`` and no
    ``read_wait_s``."""
    with open(PARENT) as f:
        obs = json.load(f)["obs"]
    assert _read("index_read_s", obs) is not None
    assert _read("index_read_wait_s", obs) is None
    obs = copy.deepcopy(obs)
    for job in obs["jobs"]:
        job["pipeline_stats"] = {"steps": 513, "upload_s": 0.3}
    assert _read("index_read_wait_s", obs) is None
    for job in obs["jobs"]:
        job["pipeline_stats"] = None
    assert _read("index_read_wait_s", obs) is None
    assert _read("index_read_wait_s", {"jobs": []}) is None
    assert _read("index_read_wait_s", {}) is None
