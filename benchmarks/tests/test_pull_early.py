"""What PR 50 adds for the three word-count stream cells that merge on the
host: the reader ``layer_metrics/pull_early_share.py``.

It is tried, as ``test_merge_presorted.py`` tries PR 43's, on a hand-made
``obs`` whose answer can be worked out by eye, and on a program that does
not count its pulls by kind (the parent: PR 43's recording of what
``wcstream --stats`` printed on the chip), where it returns None and does
not raise."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PARENT = os.path.join(HERE, "recorded", "mergeruns-pipeline-stats.json")


def _read(obs):
    return importlib.import_module("layer_metrics.pull_early_share").read(obs)


def _traced(**ps):
    return {"traced_job": {"pipeline_stats": ps}, "jobs": []}


def test_the_share_of_pulls_served_by_the_pack_at_dispatch():
    assert _read(_traced(step_pulls=128, pulls_early=128,
                         pulls_late=0)) == 100.0
    assert _read(_traced(step_pulls=128, pulls_early=120,
                         pulls_late=8)) == pytest.approx(93.75)
    # every pull packed at retirement reads 0.0, a number and not None
    assert _read(_traced(step_pulls=4, pulls_early=0, pulls_late=4)) == 0.0


def test_none_where_there_is_nothing_to_read():
    """The parent's line has ``step_pulls`` and no ``pulls_early``; a job
    that folds on the device pulls no step table."""
    with open(PARENT) as f:
        obs = json.load(f)["obs"]
    assert obs["traced_job"]["pipeline_stats"]["step_pulls"] > 0
    assert _read(obs) is None
    assert _read(_traced(step_pulls=128)) is None
    assert _read(_traced(step_pulls=0, pulls_early=0, pulls_late=0)) is None
    assert _read({"traced_job": None}) is None
    assert _read({"traced_job": {"pipeline_stats": None}}) is None
    assert _read({"jobs": []}) is None
    assert _read({}) is None
