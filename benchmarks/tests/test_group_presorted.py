"""What PR 48 adds for the two index cells: the reader
``layer_metrics/index_group_presorted_share.py``.

It is tried, as ``test_merge_presorted.py`` tries PR 43's, on a hand-made
``obs`` whose answer can be worked out by eye (the counts of a job as the
chip printed them: every row in a run; and a job a part of whose rows went
through the sort on entry), and on what a program prints whose group
counts no sorted rows (the parent: PR 38's and PR 41's recordings of
``planrun --stats`` on the chip), where it returns None and does not
raise.  ``index_group_s`` and ``index_postings_M`` read the same scope as
before."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PARENTS = [os.path.join(HERE, "recorded", name) for name in
           ("index-pipeline-stats.json", "indexpack-pipeline-stats.json")]


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _traced(**walk):
    return {"traced_job": {"pipeline_stats": {"stages": {"indexer": walk}}},
            "jobs": []}


def test_the_share_of_rows_that_arrived_in_runs():
    # a page job: 130 waves, 130 runs, no row sorted
    assert _read("index_group_presorted_share", _traced(
        postings_rows=5_520_000, group_runs=130,
        group_rows_sorted=0)) == 100.0
    # a quarter of the rows came in a buffer that was no run
    assert _read("index_group_presorted_share", _traced(
        postings_rows=4_000_000, group_runs=230,
        group_rows_sorted=1_000_000)) == pytest.approx(75.0)
    # every row sorted on entry reads 0.0, a number and not None
    assert _read("index_group_presorted_share", _traced(
        postings_rows=12, group_runs=1, group_rows_sorted=12)) == 0.0
    # the readers beside it see the same scope
    assert _read("index_postings_M", _traced(
        postings_rows=5_520_000, group_rows_sorted=0)) == pytest.approx(5.52)


@pytest.mark.parametrize("path", PARENTS)
def test_none_where_the_group_counts_no_sorted_rows(path):
    """The parent's lines have ``postings_rows`` and no
    ``group_rows_sorted``."""
    with open(path) as f:
        obs = json.load(f)["obs"]
    assert _read("index_postings_M", obs) is not None
    assert _read("index_group_s", obs) is not None
    assert _read("index_group_presorted_share", obs) is None


def test_none_without_a_traced_job_or_rows():
    assert _read("index_group_presorted_share",
                 _traced(postings_rows=9)) is None
    assert _read("index_group_presorted_share", _traced(
        postings_rows=0, group_rows_sorted=0)) is None
    assert _read("index_group_presorted_share",
                 _traced(group_rows_sorted=0)) is None
    assert _read("index_group_presorted_share", {"traced_job": None}) is None
    assert _read("index_group_presorted_share",
                 {"traced_job": {"pipeline_stats": None}}) is None
    assert _read("index_group_presorted_share", {"traced_job": {
        "pipeline_stats": {"stages": {"indexer": None}}}}) is None
    assert _read("index_group_presorted_share", {"jobs": []}) is None
    assert _read("index_group_presorted_share", {}) is None
