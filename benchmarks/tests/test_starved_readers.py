"""The six readers PR 51 adds over the starvation account that ``wcstream``,
``grepstream`` and ``planrun`` print at the top level of ``pipeline_stats``
(``starved_share`` and its four parts, ``plan_unspanned_share``): on a
hand-made ``obs`` whose answers can be worked out by eye, on what the
commands printed on the chip (``recorded/starved-*.json``: the whole jobs of
one traced run each of ``stream-wc-20k`` and ``plan-sort-gensort``), on the
recordings of the programs from before the account (where every reader has
to return None, so that the parent's side of a pair prints none of them) and
over a job with ``problems``, which is left out."""

import copy
import importlib
import json
import os
import statistics

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PARTS = ("starved_input_share", "starved_dispatch_share",
         "starved_merge_share", "starved_tail_share")
FIVE = ("starved_share",) + PARTS
SIX = FIVE + ("plan_unspanned_share",)
# what each command's recording holds, as BENCHMARK.json lists the cells
CELLS = {"starved-stream-pipeline-stats.json": FIVE,
         "starved-plan-pipeline-stats.json": SIX}
BEFORE = ("account-wcstream-pipeline-stats.json",
          "account-grepstream-pipeline-stats.json",
          "plan-pipeline-stats.json", "sort-pipeline-stats.json",
          "agg-pipeline-stats.json", "index-pipeline-stats.json")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name)) as f:
        return json.load(f)


def _job(job_s, groups, problems=(), **more):
    ps = {"job_s": job_s, "starved_s": sum(groups), "starved_dry_s": 0.0,
          "starved_by": {},
          "starved_groups": dict(zip(("input", "dispatch", "merge", "tail"),
                                     groups))}
    return {"t_start": 0.0, "t_end": job_s + 0.1, "problems": list(problems),
            "pipeline_stats": dict(ps, **more)}


def test_each_is_a_median_over_whole_jobs_of_one_share_a_job():
    plan = {"plan_s": 1.0}
    jobs = [_job(10.0, (1.0, 2.0, 0.5, 0.5), plan=plan, job_children_s=9.9),
            _job(20.0, (1.0, 1.0, 1.0, 1.0), plan=plan, job_children_s=19.0),
            _job(8.0, (0.8, 0.0, 1.6, 2.4), plan=plan, job_children_s=7.6),
            # a failed job counts for nothing
            _job(1.0, (1.0, 0.0, 0.0, 0.0), ["exit code 1"], plan=plan,
                 job_children_s=0.0)]
    obs = {"jobs": jobs, "traced_job": jobs[0]}
    want = {"starved_share": 40.0,            # 40, 20, 60 % of job_s
            "starved_input_share": 10.0,      # 10, 5, 10
            "starved_dispatch_share": 5.0,    # 20, 5, 0
            "starved_merge_share": 5.0,       # 5, 5, 20
            "starved_tail_share": 5.0,        # 5, 5, 30
            "plan_unspanned_share": 5.0}      # 1, 5, 5
    assert {n: _read(n, obs) for n in SIX} == pytest.approx(want)
    # a stream command's line has no plan group: not this reader's
    for job in jobs:
        del job["pipeline_stats"]["plan"]
    assert _read("plan_unspanned_share", obs) is None
    assert _read("starved_share", obs) == pytest.approx(40.0)


@pytest.mark.parametrize("recording", sorted(CELLS))
def test_on_what_the_chip_recorded(recording):
    rec = _recorded(recording)
    obs, names = rec["obs"], CELLS[recording]
    assert set(rec["expected"]) == set(names)
    for name in names:
        assert _read(name, obs) == pytest.approx(rec["expected"][name]), name
    whole = [j["pipeline_stats"] for j in obs["jobs"] if not j["problems"]]
    assert len(whole) >= 3
    assert any(j["traced"] for j in obs["jobs"])
    for p in whole:
        groups = p["starved_groups"]
        assert list(groups) == ["input", "dispatch", "merge", "tail"]
        assert sum(groups.values()) == pytest.approx(p["starved_s"],
                                                     abs=1e-6)
        assert 0 <= p["starved_dry_s"] <= p["starved_s"] <= p["job_s"]
        assert sum(p["starved_by"].values()) == pytest.approx(
            p["starved_s"], abs=2e-3)
    assert sum(_read(n, obs) for n in PARTS) == pytest.approx(
        _read("starved_share", obs), abs=3.0)  # medians of parts
    assert _read("starved_share", obs) == pytest.approx(statistics.median(
        100.0 * p["starved_s"] / p["job_s"] for p in whole))
    if "plan_unspanned_share" in names:
        assert _read("plan_unspanned_share", obs) < 5.0


@pytest.mark.parametrize("recording", BEFORE)
def test_a_program_before_the_account_has_nothing_to_read(recording):
    obs = _recorded(recording)["obs"]
    assert [j for j in obs["jobs"] if j.get("pipeline_stats")]
    for name in SIX:
        assert _read(name, obs) is None, name


@pytest.mark.parametrize("recording", sorted(CELLS))
def test_a_job_with_problems_is_left_out(recording):
    obs = copy.deepcopy(_recorded(recording)["obs"])
    whole = [j for j in obs["jobs"] if not j["problems"]]
    before = {n: _read(n, obs) for n in CELLS[recording]}
    # a job whose line would move every median, had it counted
    bad = copy.deepcopy(whole[0])
    bad["problems"] = ["output differs from the plain reference"]
    ps = bad["pipeline_stats"]
    ps["starved_s"] = ps["job_s"]
    ps["starved_groups"] = {"input": ps["job_s"], "dispatch": 0.0,
                            "merge": 0.0, "tail": 0.0}
    ps["job_children_s"] = 0.0
    obs["jobs"] = [bad] * (len(whole) + 1) + obs["jobs"]
    assert {n: _read(n, obs) for n in CELLS[recording]} == \
        pytest.approx(before)
    # and with nothing but such jobs there is nothing to read
    obs["jobs"] = [bad]
    for name in CELLS[recording]:
        assert _read(name, obs) is None, name
    # nor in a line that lacks a key a reader needs
    del whole[0]["pipeline_stats"]["starved_groups"]
    obs["jobs"] = [whole[0]]
    for name in PARTS:
        assert _read(name, obs) is None, name
