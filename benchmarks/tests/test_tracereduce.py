"""The reduction from a trace to numbers: its interval arithmetic on
hand-made events, and the whole of it on a small trace recorded on the chip
(``recorded/``; see ``recorded/README.md`` for how it was cut)."""

import glob
import json
import os

import pytest

import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.merge([(3, 4), (0, 1), (1, 2), (1.5, 2.5)]) == [(0, 2.5),
                                                                (3, 4)]
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4


def test_self_time_charges_a_parent_only_what_children_leave():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "sort"), (4.0, 6.0, "fusion"),
              (2.0, 3.0, "inner"), (12.0, 13.0, "sort")]
    got = tr.self_times(events)
    assert got == {"while": 5.0, "sort": 3.0, "fusion": 2.0, "inner": 1.0}
    assert sum(got.values()) == tr.union_seconds(
        [(s, e) for s, e, _ in events])


def test_covered_seconds():
    assert tr._covered([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert tr._covered([(0, 1), (4, 5)], [(2, 3)]) == 0


def test_categories_and_labels_from_the_hlo_text():
    assert tr.category("sort") == "sort"
    assert tr.category("all-to-all") == "all-to-all"
    assert tr.category("fusion") == "fusion"
    assert tr.category("custom-call") == "other"
    text = ("%fusion.7 = (u32[4194305]{0:T(1024)S(1)}, u32[4194305]{0:T(1024)"
            "S(1)}) fusion(u32[4194305]{0:T(1024)S(1)} %broadcast.17.clone), "
            "kind=kCustom, calls=%fused_computation.3")
    assert tr.short_op(text) == (
        "fusion.7 fusion (u32[4194305],u32[4194305]) kCustom", "fusion")
    assert tr.short_op("%sort.24 = (u32[8]{0}, s32[8]{0}) sort(u32[8]{0} "
                       "%a, s32[8]{0} %b), dimensions={0}")[1] == "sort"
    assert tr.short_op("not hlo text") == ("not hlo text", "")


def test_gap_is_named_by_the_innermost_frame_that_spans_it():
    frames = sorted([(0.0, 100.0, "main"), (10.0, 30.0, "pull_packed"),
                     (11.0, 29.0, "np.asarray"), (12.0, 13.0, "tiny")])
    starts = [f[0] for f in frames]
    assert tr._name_gap((12.0, 28.0), frames, starts) == "np.asarray"
    assert tr._name_gap((200.0, 201.0), frames, starts) \
        == "host: nothing traced"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb"))) or [None])
def test_recorded_trace_reduces_to_the_recorded_numbers(path):
    if path is None:
        pytest.skip("no recorded trace under benchmarks/tests/recorded")
    got = tr.reduce_file(path)
    with open(path.replace(".xplane.pb", ".expected.json")) as f:
        want = json.load(f)
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s", "a2a_s", "a2a_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["modules"] == want["modules"]
    assert got["breakdown"]["device_ops"][0][0] \
        == want["breakdown"]["device_ops"][0][0]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"])
