"""What PR 43 adds for the five word-count stream cells: the reader
``layer_metrics/merge_presorted_share.py``.

It is tried, as ``test_readahead.py`` tries PR 42's, on a hand-made ``obs``
whose answer can be worked out by eye, on what ``wcstream --stats`` printed
on the chip (``recorded/mergeruns-pipeline-stats.json``: the whole jobs of
one traced ``stream-wc-heaps`` run of the program whose accumulator merges
sorted runs), and on a program that counts no runs (the parent, PR 36's
recording), where it returns None and does not raise.  ``merge_resort_x``
and ``merge_compact_s`` read the same line as before: there they read 1.0
(every row ordered once, the merged table never again) and the seconds of
three compactions."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "mergeruns-pipeline-stats.json")
PARENT = os.path.join(HERE, "recorded",
                      "account-wcstream-pipeline-stats.json")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _traced(**ps):
    return {"traced_job": {"pipeline_stats": ps}, "jobs": []}


def test_the_share_of_batches_that_arrived_as_runs():
    assert _read("merge_presorted_share", _traced(
        merge_runs_in=128, merge_runs_unsorted=0)) == 100.0
    assert _read("merge_presorted_share", _traced(
        merge_runs_in=128, merge_runs_unsorted=32)) == pytest.approx(75.0)
    # every batch sorted on entry reads 0.0, a number and not None
    assert _read("merge_presorted_share", _traced(
        merge_runs_in=4, merge_runs_unsorted=4)) == 0.0


def test_on_what_the_chip_recorded():
    with open(DATA) as f:
        rec = json.load(f)
    obs = rec["obs"]
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert {"merge_presorted_share", "merge_resort_x",
            "merge_compact_s"} <= set(rec["expected"])
    assert rec["expected"]["merge_presorted_share"] == 100.0
    assert rec["expected"]["merge_resort_x"] == 1.0
    assert len(obs["jobs"]) >= 8
    counts = set()
    for job in obs["jobs"]:
        ps = job["pipeline_stats"]
        # a run a device a step that held a row; none sorted on entry;
        # every row ordered once, in its window
        assert ps["steps"] <= ps["merge_runs_in"] <= ps["steps"] + 2 * ps[
            "replays"]
        assert ps["merge_runs_unsorted"] == 0
        assert ps["merge_rows_sorted"] == ps["merge_rows_in"] \
            == sum(ps["device_rows"])
        # the window alone is counted: one compaction every 2^21 rows
        # handed over, and the last
        assert ps["merge_compacts"] == ps["merge_rows_in"] // (1 << 21) + 1
        assert 0.0 < ps["compact_s"] < ps["merge_s"] + ps["finalize_s"]
        counts.add((ps["merge_rows_in"], ps["merge_runs_in"],
                    ps["merge_compacts"]))
    assert len(counts) == 1  # they repeat exactly for one input


def test_none_where_the_program_counts_no_runs():
    """The parent's line (PR 36's recording) has ``merge_rows_in`` and no
    ``merge_runs_in``."""
    with open(PARENT) as f:
        obs = json.load(f)["obs"]
    assert _read("merge_resort_x", obs) is not None
    assert _read("merge_presorted_share", obs) is None
    assert _read("merge_presorted_share", _traced(merge_rows_in=9)) is None
    assert _read("merge_presorted_share", _traced(merge_runs_in=0,
                                                  merge_runs_unsorted=0)) is None
    assert _read("merge_presorted_share", {"traced_job": None}) is None
    assert _read("merge_presorted_share",
                 {"traced_job": {"pipeline_stats": None}}) is None
    assert _read("merge_presorted_share", {"jobs": []}) is None
    assert _read("merge_presorted_share", {}) is None
