"""What PR 54 adds for ``serve-grep-fb12``: the reader
``layer_metrics/serve_results_ready.py``.

It is tried, as ``test_serve_readers.py`` tries PR 32's eight, on a
hand-made ``obs`` whose answer can be worked out by eye, on the program
from before the packed scheduler kept a step in flight (PR 32's recorded
``Status`` ``stats`` pair, ``recorded/serve-wave-stats.json``, subtracted
as the driver subtracts it: ``packed_steps`` and no ``results_ready``),
where it returns None and does not raise, and over a wave with
``problems``, which is left out."""

import importlib
import json
import os

import pytest

from drivers import serve_child

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                    "serve-wave-stats.json")


def _read(obs):
    return importlib.import_module(
        "layer_metrics.serve_results_ready").read(obs)


def _traced(waves):
    """The ``obs`` of a traced run over ``waves``."""
    return {"jobs": list(waves), "traced_job": waves[0] if waves else {}}


def _wave(steps, ready=None, settles=0, problems=()):
    grep = {"packed_steps": steps, "packed_rows": steps, "pull_s": 0.1}
    if ready is not None:
        grep.update(results_ready=ready, settles=settles)
    return {"problems": list(problems),
            "serve": {"wall_s": 4.0, "stats": {"serve_grep": grep}}}


def test_the_share_of_a_waves_steps_that_were_ready_when_read():
    waves = [_wave(500, 450, 39), _wave(520, 494, 40), _wave(400, 100, 38),
             _wave(10, 10, problems=["11 of 12 jobs done"])]  # for nothing
    assert _read(_traced(waves)) == pytest.approx(90.0)   # 90, 95, 25
    assert _read(_traced(waves[:2])) == pytest.approx(92.5)
    # no step found ready reads 0.0, a number; every step, 100
    assert _read(_traced([_wave(524, 0)])) == 0.0
    assert _read(_traced([_wave(524, 524)])) == 100.0
    # the count is the traced run's: an untraced run or a rehearsal has
    # no traced job, and prints it in its job lines only
    assert _read({"jobs": waves}) is None


def test_the_difference_of_two_status_replies_is_what_is_read():
    """As the driver makes a wave's record: the scope after the wave
    minus the scope before it, so the counts are the wave's own."""
    before = {"serve_grep": {"packed_steps": 524, "results_ready": 500,
                             "settles": 39}}
    after = {"serve_grep": {"packed_steps": 1048, "results_ready": 972,
                            "settles": 77}}
    wave = {"problems": [], "serve": {
        "wall_s": 3.5, "stats": serve_child._diff(before, after)}}
    assert wave["serve"]["stats"]["serve_grep"] == {
        "packed_steps": 524, "results_ready": 472, "settles": 38}
    assert _read(_traced([wave])) == pytest.approx(100.0 * 472 / 524)


def test_none_where_there_is_nothing_to_read():
    with open(DATA) as f:
        rec = json.load(f)
    stats = serve_child._diff(rec["before"], rec["after"])
    assert stats["serve_grep"]["packed_steps"] > 0
    assert "results_ready" not in stats["serve_grep"]   # the parent's
    parent = {"problems": [], "serve": {
        "wall_s": rec["wall_s"], "stats": stats, "jobs": rec["jobs"],
        "spans": rec["spans"]}}
    assert _read({"jobs": [parent], "traced_job": parent}) is None
    assert _read(_traced([_wave(524)])) is None
    # a wave that ran no step has no share of them
    assert _read(_traced([_wave(0, 0)])) is None
    assert _read(_traced([_wave(5, 5, problems=["shed 1"])])) is None
    assert _read({"jobs": [{"problems": [], "pipeline_stats": {}}],
                  "traced_job": {}}) is None
    assert _read({"jobs": []}) is None
    assert _read({}) is None
