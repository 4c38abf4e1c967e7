"""What PR 55 adds for the cell ``plan-join-week``: the plain reference
(``reference_join.py``) against a join worked out by hand, the generator
(``rankvisits.py``: the two tables' layout, their determinism, that every
visit's URL is a page and no page comes twice, the rows' ratio), the least
bytes (``roofline_join.py``), the seven readers (``layer_metrics/join_*.py``)
and the driver ``drivers/join_inproc``.

The readers are tried on a hand-made ``obs`` whose answer can be worked
out by eye and on a program that reports no such line or key (the
parent), where they return None and do not raise.  The driver's
conditions are each seen to fire."""

import copy
import importlib
import json
import os
import re
import types

import numpy as np
import pytest

import rankvisits
import reference
import reference_join
import roofline_join

HERE = os.path.dirname(os.path.abspath(__file__))
WEEK = "2000-01-15:2000-01-22"
SPAN_READERS = ("join_build_s", "join_probe_s")
COUNT_READERS = ("join_table_MB", "join_window_ppm")
TRACE_READERS = ("join_probe_ms_per_MiB", "join_probe_roofline",
                 "join_build_roofline")
NEW = SPAN_READERS + COUNT_READERS + TRACE_READERS


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "pavlo-join-1chip.json")) as f:
        return json.load(f)


# ── the plain reference ────────────────────────────────────────────────


def _two_tables(tmp_path):
    r, v = tmp_path / "r.txt", tmp_path / "v.txt"
    r.write_bytes(b"http://a/|10|5\nhttp://b/|7|1\nzz|4")
    v.write_bytes(
        b"1.2.3.4|http://a/|2000-01-15|1.5|agent|USA|en-US|word|5\n"
        b"1.2.3.4|http://b/|2000-01-22|2.25|x\n"
        b"1.2.3.4|zz|2000-01-18|0.000001\n"      # (10 + 7 + 4) / 3 = 7
        b"9.9.9.9|zz|2000-01-16|3.75|x\n"
        b"9.9.9.9|http://c/|2000-01-16|5|x\n"     # no page
        b"8.8.8.8|zz|2000-01-23|5|x\n"            # a day late
        b"8.8.8.8|zz|2000-01-14|5|x")             # a day early
    return [str(r)], [str(v)]


def test_a_join_worked_out_by_hand(tmp_path):
    build, probe = _two_tables(tmp_path)
    total, counts = reference_join.sums(build, probe, WEEK)
    assert total == {b"1.2.3.4": [3_750_001, 21, 3],
                     b"9.9.9.9": [3_750_000, 4, 1]}
    assert counts == {"build_rows": 3, "probe_rows": 7, "window_rows": 5,
                      "matched_rows": 4}
    by_hand = ["#top 1.2.3.4 3.750001 7.000000",
               "1.2.3.4 3.750001 7.000000", "9.9.9.9 3.750000 4.000000"]
    assert reference_join.lines_of(total) == by_hand
    # an average that does not end: truncated; a tie: the least key
    assert reference_join.line(b"k", [1, 2, 3]) == "k 0.000001 0.666666"
    assert reference_join.top({b"b": [5, 1, 1], b"a": [5, 9, 1],
                               b"c": [4, 1, 1]}) == b"a"
    assert reference_join.top({}) is None
    assert reference_join.lines_of({}) == []
    parts = reference_join.partitions(build, probe, WEEK, 3)
    assert sorted(b"".join(parts).decode().splitlines()) == by_hand[1:]
    # as the harness reads a committed job back, the top row among it
    out = tmp_path / "out"
    out.mkdir()
    for r, part in enumerate(parts):
        (out / f"mr-out-{r}").write_bytes(part)
    (out / "mr-out-top").write_text(by_hand[0] + "\n")
    assert reference.read_output(str(out)) == by_hand
    # every visit inside another window's one day
    assert reference_join.sums(build, probe, "2000-01-23:2000-01-23")[1][
        "window_rows"] == 1


@pytest.mark.parametrize("row", [
    b"justakey", b"|5", b"k" * 101 + b"|5", b"caf\xe9|5", b"k|", b"k|5x",
    b"k|1234567890", b"k|-1"])
def test_a_rankings_row_outside_the_grammar_is_an_error(tmp_path, row):
    build, probe = _two_tables(tmp_path)
    with open(build[0], "wb") as f:
        f.write(b"ok|1\n" + row + b"\nalso|2\n")
    with pytest.raises(ValueError, match=r"r\.txt:2: "):
        reference_join.sums(build, probe, WEEK)


@pytest.mark.parametrize("row", [
    b"1.1.1.1|zz|2000-01-16", b"|zz|2000-01-16|1", b"1" * 17 + b"|zz|2000-01-16|1",
    b"1.1.1.1||2000-01-16|1", b"1.1.1.1|" + b"u" * 101 + b"|2000-01-16|1",
    b"1.1.1.1|zz|2000-1-16|1", b"1.1.1.1|zz|2000/01/16|1",
    b"1.1.1.1|zz|1999-01-01|1e3"])
def test_a_visits_row_outside_the_grammar_is_an_error(tmp_path, row):
    build, probe = _two_tables(tmp_path)
    with open(probe[0], "wb") as f:
        f.write(b"1.1.1.1|zz|2000-01-16|1\n" + row + b"\n")
    with pytest.raises(ValueError, match=r"v\.txt:2: "):
        reference_join.sums(build, probe, WEEK)


def test_a_page_that_comes_twice_is_an_error(tmp_path):
    build, probe = _two_tables(tmp_path)
    with open(build[0], "ab") as f:
        f.write(b"\nhttp://a/|99\n")
    with pytest.raises(ValueError, match=r"r\.txt:1 and .*r\.txt:4 hold"):
        reference_join.sums(build, probe, WEEK)


# ── the generator ──────────────────────────────────────────────────────

_RANK = re.compile(rb"http://[a-z0-9./]{12,52}\|[1-9]\d{0,3}\|[1-9]\d{0,2}")
_VISIT = re.compile(
    rb"(\d{1,3})\.(\d{1,3})\.(\d{1,3})\.(\d{1,3})\|http://[a-z0-9./]{12,52}"
    rb"\|\d{4}-\d{2}-\d{2}\|\d{1,3}\.\d{1,6}\|[A-Za-z0-9 /.;]{24,32}"
    rb"\|[A-Z]{3}\|[a-z]{2}-[A-Z]{2}\|[a-z]{6,12}\|[1-9]\d{0,3}")


def test_the_two_tables_layout():
    rng = np.random.default_rng(1)
    urls, lengths = rankvisits.pages(500, rng)
    assert urls.shape == (500, 59) and len({u.tobytes() for u in urls}) == 500
    assert lengths.min() >= 19 and lengths.max() <= 59
    ranks = rankvisits.ranking_rows(urls, lengths, rng).tobytes()
    rows = ranks.split(b"\n")[:-1]
    assert len(rows) == 500 and all(_RANK.fullmatch(r) for r in rows)
    known = {r.split(b"|")[0] for r in rows}
    visits = rankvisits.visit_rows(3000, np.random.default_rng(2), urls,
                                   lengths).tobytes()
    rows = visits.split(b"\n")[:-1]
    assert len(rows) == 3000 and all(_VISIT.fullmatch(r) for r in rows)
    assert {r.split(b"|")[1] for r in rows} <= known
    sizes = [len(r) + 1 for r in rows]
    assert 87 <= min(sizes) and max(sizes) <= 159
    assert 127.5 < sum(sizes) / 3000 < 130.5
    # the same generator, the same rows
    assert rankvisits.visit_rows(3000, np.random.default_rng(2), urls,
                                 lengths).tobytes() == visits


def _corpus(tmp_path, sizes, salt=b""):
    paths = []
    os.makedirs(tmp_path, exist_ok=True)
    for i, size in enumerate(sizes):
        path = os.path.join(tmp_path, f"pg-{i:02d}.txt")
        with open(path, "wb") as f:
            f.write(((salt + b"some text %d " % i) * (size // 8 + 1))[:size])
        paths.append(path)
    return paths


def test_the_jobs_tables_follow_the_corpus(tmp_path):
    """Four files of rankings and one of visits a corpus file, of as many
    whole rows as fit its bytes, seeded by the first file's CRC-32; written
    once; every visit's URL a page, no page twice, 18 pages to 155 visits."""
    sizes = (200_000, 123_456, 100)
    corpus = _corpus(tmp_path / "one", sizes)
    build, probe = rankvisits.job_files(corpus)
    assert [os.path.basename(p) for p in build] == [
        f"r{i:03d}.txt" for i in range(4)]
    assert [os.path.basename(p) for p in probe] == [
        f"v{i:03d}.txt" for i in range(3)]
    got = [os.path.getsize(p) for p in probe]
    assert all(0 <= want - have < 159 for want, have in zip(sizes[:2], got))
    assert got[2] == 0
    ranks = reference_join.rankings(build)   # raises on a page held twice
    visits = b"".join(open(p, "rb").read() for p in probe).split(b"\n")[:-1]
    assert all(_VISIT.fullmatch(r) for r in visits)
    assert {r.split(b"|")[1] for r in visits} <= set(ranks)
    assert len(ranks) == round(sum(sizes) // 129 * 18 / 155)
    assert abs(len(ranks) / len(visits) - 18 / 155) < 0.005
    # the same corpus elsewhere: the same bytes; another first file: others
    again = rankvisits.job_files(_corpus(tmp_path / "two", sizes))
    assert [open(p, "rb").read() for p in again[0] + again[1]] == \
        [open(p, "rb").read() for p in build + probe]
    other = rankvisits.job_files(_corpus(tmp_path / "salt", sizes, b"!"))
    assert open(other[0][0], "rb").read() != open(build[0], "rb").read()
    assert open(other[1][0], "rb").read() != open(probe[0], "rb").read()
    # once a seed: a second call writes nothing
    stamp = [os.stat(p).st_mtime_ns for p in build + probe]
    assert rankvisits.job_files(corpus) == (build, probe)
    assert [os.stat(p).st_mtime_ns for p in build + probe] == stamp


def test_the_registered_reference_reads_both_tables(tmp_path):
    from drivers import join_inproc

    corpus = _corpus(tmp_path, (400_000, 300_000))
    assert reference.KINDS["join"] is join_inproc._reference_lines
    dates = "2000-01-01:2000-12-31"
    got = reference.KINDS["join"](corpus, {"passes": 1, "dates": dates})
    build, probe = rankvisits.job_files(corpus)
    total, counts = reference_join.sums(build, probe, dates)
    assert got == reference_join.lines_of(total) == sorted(got)
    assert got == reference_join.lines(corpus, {"dates": dates})
    assert got[0].startswith("#top ") and len(got) == len(total) + 1
    # a tenth of the visits lie in the year, and every one matches
    assert counts["window_rows"] == counts["matched_rows"] > 400
    with open(join_inproc._counts_path(corpus, dates)) as f:
        assert json.load(f) == counts


# ── the least bytes ────────────────────────────────────────────────────


def test_least_bytes_count_the_work():
    kernels = _config()["kernels"]
    assert kernels["join_probe"]["shapes"] == {
        "input_bytes": 1048576, "key_bytes": 100, "group_bytes": 32}
    assert kernels["join_build"]["shapes"] == {"row_bytes": 108}
    assert roofline_join.probe_bytes(dict(
        kernels["join_probe"]["shapes"], steps=513, window_rows=9_100)) == \
        513 * 1048576 + 9_100 * 132
    assert roofline_join.build_bytes(dict(
        kernels["join_build"]["shapes"], build_rows=483_000,
        build_bytes=27_000_000)) == 27_000_000 + 483_000 * 108


# ── the readers ────────────────────────────────────────────────────────


def _job(t_end, join, problems=(), **top):
    scope = dict({"join_probe_rows": 4_000_000, "join_window_rows": 8_800,
                  "join_table_bytes": 64_000_000}, **join)
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": dict({
                "stages": {"join": scope},
                "plan": {"plan_s": 2.0, "plan_stage_walls": {"join": 2.0}},
                "write_s": 0.05, "write_commit_s": 0.02}, **top)}


def test_span_readers_are_medians_over_whole_jobs():
    obs = {"jobs": [
        _job(2.4, {"join_build_s": 0.4, "join_probe_s": 1.5}),
        _job(3.0, {"join_build_s": 0.6, "join_probe_s": 1.9}),
        _job(2.7, {"join_build_s": 0.5, "join_probe_s": 1.7}),
        # a failed job counts for nothing
        _job(0.1, {"join_build_s": 9.0, "join_probe_s": 9.0},
             ["exit code 1"])]}
    assert _read("join_build_s", obs) == pytest.approx(0.5)
    assert _read("join_probe_s", obs) == pytest.approx(1.7)
    for name in COUNT_READERS:  # counts of the traced job alone
        assert _read(name, obs) is None, name
    obs["traced_job"] = obs["jobs"][0]
    assert _read("join_table_MB", obs) == pytest.approx(64.0)
    assert _read("join_window_ppm", obs) == pytest.approx(2200.0)
    # the older readers the cell lists
    assert _read("write_s", obs) == pytest.approx(0.05)
    assert _read("write_commit_s", obs) == pytest.approx(0.02)


def _traced_obs(modules, **join):
    job = _job(2.0, dict({"steps": 4, "join_build_steps": 2,
                          "join_build_rows": 30_000,
                          "join_build_bytes": 1_700_000}, **join))
    return {"jobs": [job], "traced_job": job, "config": _config(),
            "traffic": {"kernel": "join_probe"},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"modules": modules}}


def test_trace_readers_by_hand():
    obs = _traced_obs({
        "jit_join_probe_step(12)": {"runs": 4, "seconds": 0.008},
        "jit_join_build_step(3)": {"runs": 2, "seconds": 0.018},
        "jit_join_build_order(4)": {"runs": 1, "seconds": 0.002},
        "jit__slice_pack(7)": {"runs": 4, "seconds": 0.001}})
    assert _read("join_probe_ms_per_MiB", obs) == pytest.approx(2.0)
    assert _read("join_probe_roofline", obs) == pytest.approx(
        100 * (4 * 1048576 + 8_800 * 132) / 819e9 / 0.008)
    assert _read("join_build_roofline", obs) == pytest.approx(
        100 * (1_700_000 + 30_000 * 108) / 819e9 / 0.020)
    for name in TRACE_READERS:
        assert 0.0 < _read(name, obs) < 100.0 or "ms" in name


def test_a_trace_cut_before_the_jobs_end_reads_nothing():
    obs = _traced_obs({
        "jit_join_probe_step(12)": {"runs": 3, "seconds": 0.006},
        "jit_join_build_step(3)": {"runs": 2, "seconds": 0.018}})
    for name in TRACE_READERS:  # a probe step and the ordering are missing
        assert _read(name, obs) is None, name
    obs["trace"]["modules"] = {
        "jit_join_probe_step(1)": {"runs": 4, "seconds": 0.012},
        "jit_join_build_step(3)": {"runs": 2, "seconds": 0.018},
        "jit_join_build_order(4)": {"runs": 2, "seconds": 0.004}}
    del obs["peaks"]
    assert _read("join_probe_roofline", obs) is None
    assert _read("join_build_roofline", obs) is None
    assert _read("join_probe_ms_per_MiB", obs) == pytest.approx(3.0)
    # another plan job traced under the same module names
    del obs["traced_job"]["pipeline_stats"]["stages"]["join"][
        "join_probe_rows"]
    assert _read("join_probe_ms_per_MiB", obs) is None


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    obs = _traced_obs({
        "jit_join_probe_step(12)": {"runs": 4, "seconds": 0.008},
        "jit_join_build_step(3)": {"runs": 2, "seconds": 0.018},
        "jit_join_build_order(4)": {"runs": 1, "seconds": 0.002}},
        join_build_s=0.4, join_probe_s=1.5)
    assert _read(name, obs) is not None
    obs = copy.deepcopy(obs)
    jobs = obs["jobs"] + [obs["traced_job"]]
    for job in jobs:  # the parent's plan jobs: other stages
        job["pipeline_stats"]["stages"] = {"agg": {"steps": 513,
                                                   "agg_rows": 4_000_000}}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"]["stages"] = {}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"] = {"steps": 513, "upload_s": 0.3}
    assert _read(name, obs) is None
    for job in jobs:
        job["pipeline_stats"] = None
    assert _read(name, obs) is None
    assert _read(name, {"jobs": [], "config": _config(),
                        "traffic": {}}) is None


# ── the driver ─────────────────────────────────────────────────────────


def _cell(tmp_path):
    cell = types.SimpleNamespace(
        config=_config(), name="plan-join-week",
        reference_lines=["#top a 1.000000 2.000000", "a 1.000000 2.000000",
                         "b 0.500000 1.000000"],
        obs={"join_counts": {"build_rows": 300, "probe_rows": 2600,
                             "window_rows": 6, "matched_rows": 6},
             "join_file_rows": {"build": 300, "probe": 2600},
             "join_probe_bytes": 3 * 1048576 - 5})
    workdir = tmp_path / "job"
    workdir.mkdir()
    for r in range(10):
        (workdir / f"mr-out-{r}").write_bytes(b"")
    (workdir / "plan-top.json").write_text('{"top": null}')
    job = {"rc": 0, "workdir": str(workdir), "log_text": "", "problems": [],
           "pipeline_stats": {
               "stages": {"join": {
                   "steps": 3, "join_build_rows": 300,
                   "join_probe_rows": 2600, "join_window_rows": 6,
                   "join_matched_rows": 6, "join_groups": 2,
                   "join_table_bytes": 4_571_136}},
               "write_rows_dict": 0}}
    return cell, job


def test_a_job_that_is_right_breaks_no_condition(tmp_path):
    from drivers import join_inproc

    cell, job = _cell(tmp_path)
    assert join_inproc.job_problems(cell, job) == []


@pytest.mark.parametrize("change, said", [
    ({"join_table_bytes": 14_000}, "join_table_bytes 14000 cannot be"),
    ({"join_probe_rows": 2601}, "join_probe_rows 2601"),   # a row twice
    ({"join_build_rows": 299}, "join_build_rows 299"),
    ({"join_window_rows": 5}, "join_window_rows 5"),
    ({"join_matched_rows": 5}, "join_matched_rows 5"),
    ({"join_groups": 3}, "join_groups 3"),
    ({"steps": 2}, "steps 2 of 1048576 B cannot hold")])
def test_each_condition_fires(tmp_path, change, said):
    from drivers import join_inproc

    cell, job = _cell(tmp_path)
    job["pipeline_stats"]["stages"]["join"].update(change)
    (problem,) = join_inproc.job_problems(cell, job)
    assert said in problem


def test_the_other_conditions_fire(tmp_path):
    from drivers import join_inproc

    cell, job = _cell(tmp_path)
    job["log_text"] = "planrun: stage 'join': the join needs the host path"
    job["pipeline_stats"]["write_rows_dict"] = 7
    os.remove(os.path.join(job["workdir"], "mr-out-4"))
    os.remove(os.path.join(job["workdir"], "plan-top.json"))
    got = join_inproc.job_problems(cell, job)
    assert len(got) == 3 and "host path" in got[0]
    assert "write_rows_dict 7" in got[1]
    assert "['mr-out-4', 'plan-top.json'] were not committed" in got[2]
    job["pipeline_stats"] = None
    assert join_inproc.job_problems(cell, job)[-1].endswith(
        "printed no pipeline_stats")


def test_the_top_row_is_rendered_as_the_references_line(tmp_path):
    from drivers import join_inproc

    (tmp_path / "plan-top.json").write_text(json.dumps({"top": {
        "sourceIP": "1.2.3.4", "totalRevenue": "3.750001",
        "avgPageRank": "7.000000"}}))
    join_inproc._render_top(str(tmp_path))
    assert (tmp_path / "mr-out-top").read_text() == \
        "#top 1.2.3.4 3.750001 7.000000\n"
    (tmp_path / "plan-top.json").write_text('{"top": null}')
    join_inproc._render_top(str(tmp_path))
    assert (tmp_path / "mr-out-top").read_text() == ""
