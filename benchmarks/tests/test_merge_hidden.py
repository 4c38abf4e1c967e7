"""What PR 52 adds for the five word-count stream cells that print
``merge_compact_s``: the reader ``layer_metrics/merge_hidden_share.py``.

It is tried, as ``test_pull_early.py`` tries PR 50's, on a hand-made
``obs`` whose answer can be worked out by eye, on the programs from before
the merger thread (PR 51's recording of what ``wcstream --stats`` printed
on the chip, and PR 43's: ``compact_s`` and no ``compact_caller_s``), where
it returns None and does not raise, and over a job with ``problems``,
which is left out."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BEFORE = ("starved-stream-pipeline-stats.json",
          "mergeruns-pipeline-stats.json")


def _read(obs):
    return importlib.import_module(
        "layer_metrics.merge_hidden_share").read(obs)


def _job(compact_s, caller_s=None, problems=()):
    ps = {"compact_s": compact_s, "merge_compacts": 3}
    if caller_s is not None:
        ps.update(compact_caller_s=caller_s, merge_compacts_async=2)
    return {"t_start": 0.0, "t_end": 2.0, "problems": list(problems),
            "pipeline_stats": ps}


def test_the_share_of_the_compactions_that_held_nobody():
    # the caller was held for 0.1 of 0.4 s, 0.06 of 0.3 and all of 0.2
    jobs = [_job(0.4, 0.1), _job(0.3, 0.06), _job(0.2, 0.2),
            _job(0.5, 0.0, ["exit code 1"])]  # a failed job: for nothing
    assert _read({"jobs": jobs}) == pytest.approx(75.0)  # 75, 80, 0
    assert _read({"jobs": jobs[:2]}) == pytest.approx(77.5)
    # every compaction on the caller's thread reads 0.0, a number
    assert _read({"jobs": [_job(0.2, 0.2)]}) == 0.0
    assert _read({"jobs": [_job(0.25, 0.0)]}) == 100.0


def test_none_where_there_is_nothing_to_read():
    for name in BEFORE:
        with open(os.path.join(HERE, "recorded", name)) as f:
            obs = json.load(f)["obs"]
        assert any("compact_s" in j["pipeline_stats"] for j in obs["jobs"])
        assert _read(obs) is None, name
    assert _read({"jobs": [_job(0.3)]}) is None  # the parent's line
    # a job that compacted nothing has no share of it
    assert _read({"jobs": [_job(0.0, 0.0)]}) is None
    assert _read({"jobs": [_job(0.3, 0.1, ["exit code 1"])]}) is None
    assert _read({"jobs": [{"t_start": 0.0, "t_end": 1.0,
                            "pipeline_stats": None}]}) is None
    assert _read({"jobs": []}) is None
    assert _read({}) is None
