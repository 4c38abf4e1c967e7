"""The two readers PR 26 adds over ``pipeline_stats`` (``batch_wait_share``,
``upload_share``): on a hand-made ``obs`` whose answer can be worked out by
eye, on what ``grepstream --stats`` printed on the chip
(``recorded/grepstream-pipeline-stats.json``, three jobs of one
``grepstream-rare`` run), and on a program that does not report the key,
where they have to return None.  The older stream readers are read over
the same record, since the cell lists them too."""

import copy
import importlib
import json
import os

import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                    "grepstream-pipeline-stats.json")
NEW = ("batch_wait_share", "upload_share")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _job(t_end, problems=(), **ps):
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": ps}


def test_shares_are_medians_over_whole_jobs_of_seconds_over_the_wall():
    obs = {"jobs": [_job(10.0, batch_wait_s=1.0, upload_s=0.5),
                    _job(20.0, batch_wait_s=1.0, upload_s=3.0),
                    _job(5.0, batch_wait_s=2.0, upload_s=1.0),
                    # a failed job counts for nothing
                    _job(1.0, ["exit code 1"], batch_wait_s=1.0,
                         upload_s=1.0)]}
    assert _read("batch_wait_share", obs) == pytest.approx(10.0)  # 10, 5, 40
    assert _read("upload_share", obs) == pytest.approx(15.0)      # 5, 15, 20


def test_on_what_the_chip_recorded():
    with open(DATA) as f:
        rec = json.load(f)
    for name, want in rec["expected"].items():
        assert _read(name, rec["obs"]) == pytest.approx(want), name
    job = rec["obs"]["jobs"][0]
    wall = job["t_end"] - job["t_start"]
    ps = job["pipeline_stats"]
    shares = sorted(100.0 * j["pipeline_stats"]["batch_wait_s"]
                    / (j["t_end"] - j["t_start"]) for j in rec["obs"]["jobs"])
    assert _read("batch_wait_share", rec["obs"]) == pytest.approx(shares[1])
    # the engine is starved of input for under 1 % of a job, and its
    # uploads block it for about a tenth
    assert 0.0 < 100.0 * ps["batch_wait_s"] / wall < 1.0
    assert 5.0 < _read("upload_share", rec["obs"]) < 15.0
    # a pull here is the copy, not a wait: the scalars were read before it
    assert ps["d2h_s"] > 10 * ps["device_wait_s"]
    assert ps["pull_bytes"] == ps["steps"] * 364


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_key(name):
    with open(DATA) as f:
        obs = copy.deepcopy(json.load(f)["obs"])
    for job in obs["jobs"]:
        for key in ("batch_wait_s", "upload_s"):
            del job["pipeline_stats"][key]
    assert _read(name, obs) is None
    assert _read(name, {"jobs": []}) is None
    assert _read(name, {}) is None
