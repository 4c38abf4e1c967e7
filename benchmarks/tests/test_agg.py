"""What PR 49 adds for the cell ``plan-agg-sourceip``: the plain reference
(``reference_agg.py``) against a table summed by hand, the generator
(``uservisits.py``: the row's layout, its determinism, the job's files
beside a corpus), the six readers (``layer_metrics/agg_*.py``) and the
driver ``drivers/agg_inproc``.

The readers are tried on a hand-made ``obs`` whose answer can be worked
out by eye, on what ``planrun --stats`` printed and the trace reduction
gave on the chip (``recorded/agg-pipeline-stats.json``: the jobs of one
traced ``plan-agg-sourceip`` run, with the reduction's ``modules``), and
on a program that reports no such line or key (the parent), where they
return None and do not raise.  The driver's conditions are each seen to
fire."""

import copy
import importlib
import json
import os
import re
import types
import zlib

import numpy as np
import pytest

import reference
import reference_agg
import roofline_agg
import uservisits

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "agg-pipeline-stats.json")
SPAN_READERS = ("agg_stage_s", "agg_pull_s", "agg_merge_s")
TRACE_READERS = ("agg_step_ms_per_MiB", "agg_step_roofline")
NEW = SPAN_READERS + ("agg_groups_M",) + TRACE_READERS
#: every reader the cell is listed under, new or not
LISTED = NEW + ("cache_load_s", "step_sort_share", "stream_device_idle",
                "write_s", "write_commit_s", "plan_tail_s")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "pavlo-agg-1chip.json")) as f:
        return json.load(f)


# ── the plain reference ────────────────────────────────────────────────


def test_a_table_summed_by_hand(tmp_path):
    """Two files, the second without its last newline.  ``10.0.0.1``
    comes three times: 12.5 + 0.000001 + 999.999999 = 1012.5; ``9.9.9.9``
    sorts behind ``10.0.0.1`` (a byte order, not a numeric one)."""
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"10.0.0.1|u|2009-01-01|12.5|agent|USA|en-US|word|7\n"
                  b"9.9.9.9|u|d|3|x\n"
                  b"10.0.0.1|||0.000001\n")
    b.write_bytes(b"10.0.0.1|u|d|999.999999|a|b|c|d|1\n"
                  b"10.0.0.2|u|d|007.10|z")
    paths = [str(a), str(b)]
    assert reference_agg.sums(paths) == {
        b"10.0.0.1": 1_012_500_000, b"9.9.9.9": 3_000_000,
        b"10.0.0.2": 7_100_000}
    by_hand = ["10.0.0.1 1012.500000", "10.0.0.2 7.100000",
               "9.9.9.9 3.000000"]
    assert reference_agg.lines(paths, {}) == by_hand
    assert reference_agg.lines(paths, {"passes": 2})[0] == \
        "10.0.0.1 2025.000000"
    # SUBSTR(sourceIP, 1, 7): 10.0.0.1 and 10.0.0.2 fall together
    assert reference_agg.lines(paths, {"prefix": 7}) == [
        "10.0.0. 1019.600000", "9.9.9.9 3.000000"]
    # FNV-1a 32 as the lab's ihash: of "a", 0xE40C292C
    assert reference_agg.ihash(b"a") == 0xE40C292C & 0x7FFFFFFF
    parts = reference_agg.partitions(paths, 3)
    assert sorted(b"".join(parts).decode().splitlines()) == by_hand
    for key in (b"10.0.0.1", b"10.0.0.2", b"9.9.9.9"):
        assert key in parts[reference_agg.ihash(key) % 3]
    # as the harness reads a committed job back
    for r, part in enumerate(parts):
        (tmp_path / f"mr-out-{r}").write_bytes(part)
    os.remove(a), os.remove(b)
    assert reference.read_output(str(tmp_path)) == by_hand


@pytest.mark.parametrize("field, units", [
    (b"0", 0), (b"7", 7_000_000), (b"12.5", 12_500_000),
    (b"999.999999", 999_999_999), (b"0.000001", 1), (b"001.10", 1_100_000)])
def test_a_value_is_read_by_its_digits(field, units):
    assert reference_agg.units(field) == units


@pytest.mark.parametrize("row", [
    b"k|a|b", b"", b"|a|b|1", b"k" * 17 + b"|a|b|1", b"k\x80|a|b|1",
    b"k|a|b|1234", b"k|a|b|1.", b"k|a|b|.5", b"k|a|b|1.1234567",
    b"k|a|b|1e3", b"k|a|b|-1", b"k|a|b|1.5\r"])
def test_a_row_outside_the_grammar_is_an_error(tmp_path, row):
    path = tmp_path / "bad.txt"
    path.write_bytes(b"ok|a|b|1\n" + row + b"\nok|a|b|2\n")
    with pytest.raises(ValueError, match=r"bad\.txt:2: "):
        reference_agg.lines([str(path)], {})


# ── the generator ──────────────────────────────────────────────────────

_ROW = re.compile(
    rb"(\d{1,3})\.(\d{1,3})\.(\d{1,3})\.(\d{1,3})\|http://[a-z0-9./]{12,64}"
    rb"\|\d{4}-\d{2}-\d{2}\|\d{1,3}\.\d{1,6}\|[A-Za-z0-9 /.;]{24,32}"
    rb"\|[A-Z]{3}\|[a-z]{2}-[A-Z]{2}\|[a-z]{6,12}\|[1-9]\d{0,3}")


def test_the_rows_layout():
    data = uservisits.rows(4000, np.random.default_rng(1)).tobytes()
    assert data.endswith(b"\n")
    rows = data.split(b"\n")[:-1]
    assert len(rows) == 4000
    lengths = [len(r) + 1 for r in rows]
    assert min(lengths) == uservisits.ROW_BYTES_MIN == 119
    assert max(lengths) == uservisits.ROW_BYTES_MAX == 139
    assert 128.5 < sum(lengths) / 4000 < 129.5
    for row in rows:
        m = _ROW.fullmatch(row)
        assert m, row
        assert all(int(octet) <= 255 for octet in m.groups())
        fields = row.split(b"|")
        assert len(fields) == 9 and 7 <= len(fields[0]) <= 15
        assert 1 <= int(fields[2][5:7]) <= 12 and 1 <= int(fields[2][8:]) <= 28
        assert reference_agg.units(fields[3]) < 10 ** 9
    # the same generator, the same rows; nearly every address its own
    assert uservisits.rows(4000, np.random.default_rng(1)).tobytes() == data
    assert len({r.split(b"|")[0] for r in rows}) > 3990


def test_addresses_from_a_pool():
    rows = uservisits.rows(3000, np.random.default_rng(2), pool=40)
    keys = {r.split(b"|")[0] for r in rows.tobytes().split(b"\n")[:-1]}
    assert len(keys) == 40
    # the pool's addresses are distinct: an odd multiplier modulo 2^32
    j = np.arange(uservisits.POOL, dtype=np.int64)
    assert len(np.unique((j * 2654435761 + 0x9E3779B9) % (1 << 32))) == \
        uservisits.POOL


def _corpus(tmp_path, sizes, salt=b""):
    paths = []
    for i, size in enumerate(sizes):
        path = tmp_path / f"pg-{i:02d}.txt"
        path.write_bytes((salt + b"some text %d " % i) * (size // 8 + 1))
        with open(path, "r+b") as f:
            f.truncate(size)
        paths.append(str(path))
    return paths


def test_the_jobs_files_follow_the_corpus(tmp_path):
    """One file of rows a corpus file, as many whole rows as fit its
    bytes, seeded by the first file's CRC-32; written once."""
    (tmp_path / "one").mkdir(), (tmp_path / "two").mkdir()
    (tmp_path / "salt").mkdir()
    corpus = _corpus(tmp_path / "one", (20_000, 12_345, 100))
    files = uservisits.job_files(corpus)
    sizes = [os.path.getsize(p) for p in files]
    assert all(0 <= want - got < 139 for want, got in
               zip((20_000, 12_345), sizes)) and sizes[2] == 0
    for path in files[:2]:
        data = open(path, "rb").read()
        assert data.endswith(b"\n")
        assert all(_ROW.fullmatch(r) for r in data.split(b"\n")[:-1])
    # the same corpus elsewhere: the same bytes; another first file: others
    again = uservisits.job_files(_corpus(tmp_path / "two",
                                         (20_000, 12_345, 100)))
    assert [open(p, "rb").read() for p in again] == \
        [open(p, "rb").read() for p in files]
    other = uservisits.job_files(_corpus(tmp_path / "salt",
                                         (20_000, 12_345, 100), salt=b"!"))
    assert open(other[0], "rb").read() != open(files[0], "rb").read()
    with open(corpus[0], "rb") as f:
        assert uservisits.job_seed(corpus) == zlib.crc32(f.read())
    # once a seed: a second call writes nothing
    stamp = [os.stat(p).st_mtime_ns for p in files]
    assert uservisits.job_files(corpus) == files
    assert [os.stat(p).st_mtime_ns for p in files] == stamp


def test_the_registered_reference_reads_the_row_files(tmp_path):
    from drivers import agg_inproc

    corpus = _corpus(tmp_path, (50_000, 30_000))
    assert reference.KINDS["agg"] is agg_inproc._reference_lines
    got = reference.KINDS["agg"](corpus, {"passes": 1})
    files = uservisits.job_files(corpus)
    assert got == reference_agg.lines(files, {}) == sorted(got)
    assert len(got) == agg_inproc.count_rows(files)  # every address once
    assert 600 < len(got) < 640


# ── the least bytes ────────────────────────────────────────────────────


def test_least_bytes_count_the_work():
    shapes = _config()["kernels"]["agg_step"]["shapes"]
    assert shapes == {"input_bytes": 1048576, "row_bytes": 28}
    assert roofline_agg.step_bytes(dict(shapes, steps=513,
                                        table_rows=4_161_000)) == \
        513 * 1048576 + 4_161_000 * 28


# ── the readers ────────────────────────────────────────────────────────


def _job(t_end, agg, problems=(), wall=2.0, **top):
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": dict({
                "stages": {"agg": dict({"agg_rows": 1000}, **agg)},
                "plan": {"plan_s": wall, "plan_stage_walls": {"agg": wall}},
                "write_s": 0.5, "write_commit_s": 0.2}, **top)}


def test_span_readers_are_medians_over_whole_jobs():
    obs = {"jobs": [
        _job(2.4, {"pull_s": 0.5, "merge_s": 0.1, "finalize_s": 0.2,
                   "agg_groups": 2_000_000}, wall=2.0),
        _job(3.0, {"pull_s": 0.7, "merge_s": 0.3, "finalize_s": 0.2,
                   "agg_groups": 2_000_000}, wall=2.2),
        _job(2.7, {"pull_s": 0.6, "merge_s": 0.2, "finalize_s": 0.2,
                   "agg_groups": 2_000_000}, wall=2.1),
        # a failed job counts for nothing
        _job(0.1, {"pull_s": 9.0, "merge_s": 9.0, "finalize_s": 9.0,
                   "agg_groups": 5}, ["exit code 1"], wall=99.0)]}
    assert _read("agg_stage_s", obs) == pytest.approx(2.1)
    assert _read("agg_pull_s", obs) == pytest.approx(0.6)
    assert _read("agg_merge_s", obs) == pytest.approx(0.4)
    assert _read("agg_groups_M", obs) is None  # no traced job
    obs["traced_job"] = obs["jobs"][0]
    assert _read("agg_groups_M", obs) == pytest.approx(2.0)
    # the older readers the cell lists
    assert _read("write_s", obs) == pytest.approx(0.5)
    assert _read("write_commit_s", obs) == pytest.approx(0.2)
    assert _read("plan_tail_s", obs) == pytest.approx(0.6)  # .4, .8, .6


def _traced_obs(modules, **agg):
    job = _job(2.0, dict({"steps": 4, "merge_rows_in": 32_000}, **agg))
    return {"jobs": [job], "traced_job": job, "config": _config(),
            "traffic": {"kernel": "agg_step"},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"modules": modules}}


def test_trace_readers_by_hand():
    # four steps, the first run twice (the table's widening)
    obs = _traced_obs({
        "jit__mapreduce_step_impl(123)": {"runs": 5, "seconds": 0.012},
        "jit__slice_pack(7)": {"runs": 4, "seconds": 0.001}})
    assert _read("agg_step_ms_per_MiB", obs) == pytest.approx(3.0)
    least = 4 * 1048576 + 32_000 * 28
    assert _read("agg_step_roofline", obs) == pytest.approx(
        100 * least / 819e9 / 0.012)


def test_a_trace_cut_before_the_jobs_end_reads_nothing():
    obs = _traced_obs({
        "jit__mapreduce_step_impl(123)": {"runs": 3, "seconds": 0.006}})
    for name in TRACE_READERS:
        assert _read(name, obs) is None, name
    obs["trace"]["modules"] = {
        "jit__mapreduce_step_impl(1)": {"runs": 4, "seconds": 0.008}}
    del obs["peaks"]
    assert _read("agg_step_roofline", obs) is None
    assert _read("agg_step_ms_per_MiB", obs) == pytest.approx(2.0)
    # a word count's plan job traced under the same module name
    del obs["traced_job"]["pipeline_stats"]["stages"]["agg"]["agg_rows"]
    assert _read("agg_step_ms_per_MiB", obs) is None


def _recorded():
    with open(DATA) as f:
        rec = json.load(f)
    return rec, dict(rec["obs"], config=_config(),
                     traffic={"kernel": "agg_step"},
                     peaks={"hbm_bytes_per_s": 819e9})


def test_on_what_the_chip_recorded():
    rec, obs = _recorded()
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert set(LISTED) <= set(rec["expected"])
    ps = obs["traced_job"]["pipeline_stats"]
    agg, plan = ps["stages"]["agg"], ps["plan"]
    rows = _config()["rows"]
    assert agg["steps"] == rows["steps"] == 513
    assert agg["agg_value_lanes"] == 2 and agg["replays"] == 2  # depth 2
    assert 4_100_000 < agg["agg_rows"] < 4_200_000
    assert 2_000_000 < agg["agg_groups"] < 2_050_000
    assert agg["merge_runs_unsorted"] == 0
    assert agg["merge_rows_sorted"] == agg["merge_rows_in"]
    assert ps["write_rows_packed"] == agg["agg_groups"]
    assert ps["write_rows_dict"] == 0
    assert plan["plan_handoff"] == "device"
    for name in ("agg_step_roofline", "stream_device_idle"):
        assert 0.0 < _read(name, obs) < 100.0, name


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    _, obs = _recorded()
    obs = copy.deepcopy(obs)
    jobs = obs["jobs"] + [obs["traced_job"]]
    for job in jobs:
        ps = job["pipeline_stats"]
        ps["stages"] = {"grep": {"steps": 513}, "wc": {"steps": 13}}
        ps["plan"]["plan_stage_walls"] = {"grep": 1.0, "wc": 0.1}
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


def _cell(tmp_path, rows=500):
    data = uservisits.rows(rows, np.random.default_rng(4), pool=60)
    path = tmp_path / "v000.txt"
    path.write_bytes(data.tobytes())
    lines = reference_agg.lines([str(path)], {})
    return types.SimpleNamespace(
        name="plan-agg-sourceip", config=_config(), job_bytes=len(data),
        files=[str(path)], workroot=str(tmp_path), traffic={},
        obs={"job_rows": rows}, reference_lines=lines)


def _summed_job(tmp_path, cell, **agg_over):
    """A job as a correct program leaves it."""
    workdir = tmp_path / "job-0"
    workdir.mkdir(exist_ok=True)
    for r, part in enumerate(reference_agg.partitions(cell.files, 10)):
        (workdir / f"mr-out-{r}").write_bytes(part)
    agg = {"steps": 1, "agg_rows": cell.obs["job_rows"],
           "agg_groups": len(cell.reference_lines), "agg_value_lanes": 2}
    agg.update(agg_over)
    return {"rc": 0, "log_text": "", "workdir": str(workdir),
            "pipeline_stats": {"stages": {"agg": agg}, "plan": {},
                               "write_rows_packed": agg["agg_groups"],
                               "write_rows_dict": 0}}


def test_a_summed_job_breaks_no_condition(tmp_path):
    from drivers import agg_inproc as driver

    cell = _cell(tmp_path)
    assert len(cell.reference_lines) == 60
    assert driver.count_rows(cell.files) == 500
    assert driver.job_problems(cell, _summed_job(tmp_path, cell)) == []


@pytest.mark.parametrize("over, said", [
    ({"steps": 0}, "cannot hold"),
    ({"agg_rows": 499}, "the job holds 500 rows"),
    ({"agg_groups": 59}, "the reference has 60 lines"),
    # a program that has no such counters
    ({"agg_rows": None}, "the job holds 500 rows"),
    ({"agg_groups": None}, "the reference has 60 lines"),
])
def test_a_job_whose_counters_are_off_is_a_failed_job(tmp_path, over, said):
    from drivers import agg_inproc as driver

    cell = _cell(tmp_path)
    job = _summed_job(tmp_path, cell)
    for key, value in over.items():
        if value is None:
            del job["pipeline_stats"]["stages"]["agg"][key]
        else:
            job["pipeline_stats"]["stages"]["agg"][key] = value
    problems = driver.job_problems(cell, job)
    assert len(problems) == 1 and said in problems[0], problems


def test_the_other_conditions_each_fire(tmp_path):
    from drivers import agg_inproc as driver

    cell = _cell(tmp_path)
    job = _summed_job(tmp_path, cell)
    job["log_text"] = ("planrun: stage 'agg': the aggregation needs the "
                       "host path")
    assert driver.job_problems(cell, job) == ["a stage took the host path"]
    job = _summed_job(tmp_path, cell)
    job["pipeline_stats"]["write_rows_dict"] = 60
    assert any("went through Python objects" in p
               for p in driver.job_problems(cell, job))
    job = _summed_job(tmp_path, cell)
    os.remove(os.path.join(job["workdir"], "mr-out-7"))
    assert driver.job_problems(cell, job) == [
        "partitions [7] were not committed"]
    job["pipeline_stats"] = None
    assert any("printed no pipeline_stats" in p
               for p in driver.job_problems(cell, job))


def test_a_program_without_the_chain_cannot_run_the_cell(monkeypatch,
                                                         tmp_path):
    from drivers import agg_inproc as driver, stream_inproc

    registry = importlib.import_module("dsi_tpu.obs.registry")
    monkeypatch.setattr(stream_inproc, "claim_device", lambda cell: None)
    cell = _cell(tmp_path)
    driver.claim_device(cell)   # this program's schema has the counter
    monkeypatch.setattr(registry, "SCHEMA_KEYS", tuple(
        k for k in registry.SCHEMA_KEYS if k != "agg_rows"))
    with pytest.raises(SystemExit) as e:
        driver.claim_device(cell)
    assert "has no aggregation chain" in str(e.value)
