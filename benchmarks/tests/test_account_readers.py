"""The nine readers PR 36 adds over the stream commands' ``pipeline_stats``
(``host_unspanned_share``, ``job_start_s``, ``dispatch_share``,
``enqueue_ms``, ``batch_share``, ``merge_compact_s``, ``merge_resort_x``,
``finalize_decode_s``, ``write_commit_s``): on a hand-made ``obs`` whose
answers can be worked out by eye, on what ``wcstream --stats`` and
``grepstream --stats`` printed on the chip (``recorded/account-*.json``:
the whole jobs of one traced run each of ``stream-wc-heaps`` and
``grepstream-rare``), on the recordings of the programs from before the
account (where every reader has to return None, so that the parent's side
of a pair prints none of them) and on a run without a traced job, a
rehearsal's shape, where the count has nothing to read."""

import copy
import importlib
import json
import os
import statistics

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("host_unspanned_share", "job_start_s", "dispatch_share",
         "enqueue_ms")
MERGE = ("merge_compact_s", "merge_resort_x", "finalize_decode_s",
         "write_commit_s")
NINE = SPANS + ("batch_share",) + MERGE
# what each engine's recording holds, as BENCHMARK.json lists the cells
CELLS = {"account-wcstream-pipeline-stats.json": SPANS + MERGE,
         "account-grepstream-pipeline-stats.json": SPANS + ("batch_share",)}


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name)) as f:
        return json.load(f)


def _job(t_end, problems=(), **ps):
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": ps}


def test_each_is_a_median_over_whole_jobs_of_one_value_a_job():
    jobs = [_job(10.0, job_s=10.0, job_children_s=9.0, start_s=0.5,
                 dispatch_s=2.0, enqueue_s=0.5, steps=100, batch_s=9.0,
                 compact_s=1.0, finalize_decode_s=2.0, write_commit_s=0.2,
                 merge_rows_in=1000, merge_rows_sorted=4000),
            _job(20.0, job_s=20.0, job_children_s=19.8, start_s=0.1,
                 dispatch_s=2.0, enqueue_s=0.1, steps=100, batch_s=10.0,
                 compact_s=3.0, finalize_decode_s=1.0, write_commit_s=0.4,
                 merge_rows_in=1000, merge_rows_sorted=4000),
            _job(8.0, job_s=8.0, job_children_s=7.6, start_s=0.3,
                 dispatch_s=4.0, enqueue_s=0.3, steps=100, batch_s=2.0,
                 compact_s=2.0, finalize_decode_s=3.0, write_commit_s=0.3,
                 merge_rows_in=1000, merge_rows_sorted=4000),
            # a failed job counts for nothing
            _job(1.0, ["exit code 1"], job_s=1.0, job_children_s=0.0,
                 start_s=9.0, dispatch_s=1.0, enqueue_s=1.0, steps=1,
                 batch_s=1.0, compact_s=9.0, finalize_decode_s=9.0,
                 write_commit_s=9.0)]
    obs = {"jobs": jobs, "traced_job": jobs[0]}
    want = {"host_unspanned_share": 5.0,      # 10, 1, 5 %
            "job_start_s": 0.3,
            "dispatch_share": 20.0,           # 20, 10, 50 % of the wall
            "enqueue_ms": 3.0,                # 5, 1, 3 ms a step
            "batch_share": 50.0,              # 90, 50, 25 %
            "merge_compact_s": 2.0,
            "merge_resort_x": 4.0,
            "finalize_decode_s": 2.0,
            "write_commit_s": 0.3}
    assert {n: _read(n, obs) for n in NINE} == pytest.approx(want)


@pytest.mark.parametrize("recording", sorted(CELLS))
def test_on_what_the_chip_recorded(recording):
    rec = _recorded(recording)
    obs, names = rec["obs"], CELLS[recording]
    assert set(rec["expected"]) == set(names)
    for name in names:
        assert _read(name, obs) == pytest.approx(rec["expected"][name]), name
    whole = [dict(j["pipeline_stats"], wall_s=j["t_end"] - j["t_start"])
             for j in obs["jobs"] if not j["problems"]]
    assert len(whole) >= 3
    for p in whole:
        # the children cover the root, the root the harness's wall, and
        # the step's parts sit inside the spans that hold them
        assert 0.95 * p["job_s"] <= p["job_children_s"] <= p["job_s"] + 1e-3
        assert 0.98 * p["wall_s"] <= p["job_s"] <= p["wall_s"]
        assert p["upload_s"] + p["enqueue_s"] <= p["dispatch_s"] + 1e-3
        assert p["kernel_s"] + p["pull_s"] + p["merge_s"] \
            <= p["retire_s"] + 1e-3
    assert _read("host_unspanned_share", obs) == pytest.approx(
        statistics.median(100.0 * (p["job_s"] - p["job_children_s"])
                          / p["job_s"] for p in whole))
    assert _read("host_unspanned_share", obs) < 5.0
    if "merge_resort_x" in names:
        # a count: the same in every job of a run over one corpus
        assert len({(p["merge_rows_in"], p["merge_rows_sorted"],
                     p["merge_compacts"]) for p in whole}) == 1
        p = obs["traced_job"]["pipeline_stats"]
        assert _read("merge_resort_x", obs) == pytest.approx(
            p["merge_rows_sorted"] / p["merge_rows_in"])
        assert p["merge_rows_in"] == sum(p["device_rows"])
        for p in whole:
            assert p["compact_s"] <= (p["merge_s"] + p["replay_s"]
                                      + p["finalize_s"] + 1e-3)
            assert p["finalize_decode_s"] <= p["finalize_s"]
            assert p["write_format_s"] + p["write_commit_s"] \
                <= p["write_s"] + 1e-3


@pytest.mark.parametrize("name", NINE)
@pytest.mark.parametrize("recording", ["stream-pipeline-stats.json",
                                       "grepstream-pipeline-stats.json"])
def test_none_on_a_program_from_before_the_account(recording, name):
    """The older recordings are the parent's shape: ``batch_s`` and the
    phase keys, no ``job_s``.  With these files laid over the parent's
    program a traced run prints none of the nine."""
    obs = copy.deepcopy(_recorded(recording)["obs"])
    assert all("job_s" not in j["pipeline_stats"] for j in obs["jobs"])
    assert _read(name, obs) is None
    obs["traced_job"] = obs["jobs"][0]
    assert _read(name, obs) is None
    assert _read(name, {"jobs": []}) is None
    assert _read(name, {}) is None


def test_a_run_without_a_traced_job_reads_no_count():
    """A rehearsal prints its ``program_counter`` metrics, and its test
    allows a CPU run ``window_compiles`` alone: without a traced job the
    count is not read, the spans' readers are (the harness leaves those
    out of a rehearsal by their ``source``)."""
    obs = copy.deepcopy(_recorded("account-wcstream-pipeline-stats.json")
                        ["obs"])
    del obs["traced_job"]
    assert _read("merge_resort_x", obs) is None
    assert _read("merge_compact_s", obs) is not None
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    counters = [n for n in NINE if entries[n]["source"] == "program_counter"]
    assert counters == ["merge_resort_x"]
    assert all(entries[n]["source"] == "program_span"
               for n in NINE if n not in counters)
