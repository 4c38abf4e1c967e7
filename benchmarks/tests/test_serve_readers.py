"""The eight readers PR 32 adds for a served wave
(``layer_metrics/serve_*.py``): on a hand-made ``obs`` whose answer can be
worked out by eye, on a recorded ``Status`` ``stats`` pair
(``recorded/serve-wave-stats.json``: before and after one wave, with the
wave's job records and spans), subtracted as the driver subtracts it, and
on a program that reports none of it, where they return None and do not
raise."""

import copy
import importlib
import json
import os

import pytest

from drivers import serve_child

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                    "serve-wave-stats.json")
NEW = ("serve_step_ms", "serve_take_share", "serve_transfer_share",
       "serve_ckpt_share", "serve_submit_ms", "serve_queue_wait_s",
       "serve_finish_ms", "serve_evictions")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _wave(wall_s, steps, take_s, upload_s, pull_s, ckpt_s, evictions,
          submit_ms, finish_ms, waits, problems=()):
    return {"problems": list(problems), "serve": {
        "wall_s": wall_s,
        "stats": {"serve_grep": {"packed_steps": steps, "take_s": take_s,
                                 "upload_s": upload_s, "pull_s": pull_s},
                  "daemon": {"ckpt_s": ckpt_s, "evictions": evictions}},
        "jobs": [{"stats": {"queue_wait_s": w}} for w in waits],
        "spans": {"submit_ms": submit_ms, "finish_ms": finish_ms}}}


def test_each_reader_is_the_median_over_whole_waves():
    waves = [
        _wave(4.0, 500, 1.0, 0.4, 1.2, 0.4, 24, [2, 4, 9], [5, 7], [0.1, 0.5, 0.9]),
        _wave(5.0, 500, 1.5, 0.5, 1.5, 1.0, 30, [3, 5, 50], [6, 8], [0.2, 0.6, 1.0]),
        _wave(4.4, 550, 1.1, 0.44, 1.32, 0.22, 26, [1, 6, 7], [9, 11], [0.3, 0.4, 0.5]),
        # a failed wave counts for nothing
        _wave(1.0, 1, 1.0, 1.0, 1.0, 1.0, 999, [999], [999], [99],
              problems=["11 of 12 jobs done"])]
    obs = {"jobs": waves, "traced_job": waves[0]}
    assert _read("serve_step_ms", obs) == pytest.approx(8.0)   # 8, 10, 8
    assert _read("serve_take_share", obs) == pytest.approx(25.0)  # 25 30 25
    assert _read("serve_transfer_share", obs) == pytest.approx(40.0)
    assert _read("serve_ckpt_share", obs) == pytest.approx(10.0)  # 10 20 5
    assert _read("serve_submit_ms", obs) == pytest.approx(5.0)    # 4 5 6
    assert _read("serve_finish_ms", obs) == pytest.approx(7.0)    # 6 7 10
    assert _read("serve_queue_wait_s", obs) == pytest.approx(0.5)
    assert _read("serve_evictions", obs) == 26
    # the count is the traced run's: an untraced run or a rehearsal has
    # no traced job, and prints it in its job lines only
    assert _read("serve_evictions", {"jobs": waves}) is None


def _recorded_obs():
    with open(DATA) as f:
        rec = json.load(f)
    wave = {"problems": [], "serve": {
        "wall_s": rec["wall_s"], "chunk_bytes": rec["chunk_bytes"],
        "stats": serve_child._diff(rec["before"], rec["after"]),
        "jobs": rec["jobs"], "spans": rec["spans"]}}
    return rec, {"jobs": [wave], "traced_job": wave}


def test_readers_over_a_recorded_stats_pair():
    rec, obs = _recorded_obs()
    diff = obs["jobs"][0]["serve"]["stats"]
    # the subtraction: counts of the one wave, not of the daemon's life
    assert rec["before"]["daemon"]["submits"] == 12
    assert diff["daemon"]["submits"] == 12
    assert diff["daemon"]["jobs_done"] == 12
    steps = diff["serve_grep"]["packed_steps"]
    assert steps == rec["after"]["serve_grep"]["packed_steps"] \
        - rec["before"]["serve_grep"]["packed_steps"]
    assert steps * rec["chunk_bytes"] >= 32 * 65536
    assert "ckpt_compress" not in diff["daemon"]   # a flag, not a count
    values = {name: _read(name, obs) for name in NEW}
    assert all(v is not None for v in values.values()), values
    assert values["serve_step_ms"] == \
        pytest.approx(1e3 * rec["wall_s"] / steps)
    assert values["serve_evictions"] == diff["daemon"]["evictions"] >= 1
    for name in ("serve_take_share", "serve_transfer_share",
                 "serve_ckpt_share"):
        assert 0.0 < values[name] < 100.0, name
    assert values["serve_take_share"] + values["serve_transfer_share"] \
        + values["serve_ckpt_share"] < 100.0   # all on one thread
    assert len(rec["spans"]["submit_ms"]) == 12
    assert min(rec["spans"]["submit_ms"]) <= values["serve_submit_ms"] \
        <= max(rec["spans"]["submit_ms"])
    assert 0.0 <= values["serve_queue_wait_s"] <= rec["wall_s"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_statistics_gives_none(name):
    """The parent commit: no ``serve`` record, or one whose scopes lack the
    keys (no steps, no ``take_s``, no daemon scope, no spans, no per-job
    wait)."""
    _rec, obs = _recorded_obs()
    assert _read(name, {}) is None
    assert _read(name, {"jobs": []}) is None
    assert _read(name, {"jobs": [{"problems": [], "pipeline_stats": {}}],
                        "traced_job": {}}) is None
    bare = copy.deepcopy(obs)
    serve = bare["jobs"][0]["serve"]
    serve["stats"] = {"serve_grep": {"packed_steps": 0, "merge_s": 0.1}}
    serve["spans"] = {}
    serve["jobs"] = [{"stats": {"steps": 3}}]
    assert _read(name, bare) is None
