"""What PR 45 adds for the cell ``plan-sort-gensort``: the plain reference
(``reference_sort.py``) against ten records ordered by hand, the generator
(``gensort.py``: the record's layout, its determinism, the job's files
beside a corpus), the ten readers (``layer_metrics/sort_*.py``) and the
driver ``drivers/sort_inproc``.

The readers are tried on a hand-made ``obs`` whose answer can be worked
out by eye, on what ``planrun --stats`` printed and the trace reduction
gave on the chip (``recorded/sort-pipeline-stats.json``: the jobs of one
traced ``plan-sort-gensort`` run, with the reduction's ``modules``), and
on a program that reports no such line or key (the parent), where they
return None and do not raise.  The driver's conditions are each seen to
fire."""

import copy
import importlib
import json
import os
import types
import zlib

import numpy as np
import pytest

import gensort
import reference
import reference_sort
import roofline_sort

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "sort-pipeline-stats.json")
SPAN_READERS = ("sort_sample_s", "sort_ingest_stage_s", "sort_order_s",
                "sort_pull_s")
COUNT_READERS = ("sort_partition_skew", "sort_resident_MB")
TRACE_READERS = ("sort_ingest_ms_per_MiB", "sort_ingest_roofline",
                 "sort_order_ms_per_MiB", "sort_order_roofline")
NEW = SPAN_READERS + COUNT_READERS + TRACE_READERS
#: every reader the cell is listed under, new or not
LISTED = NEW + ("cache_load_s", "step_sort_share", "stream_device_idle",
                "write_s", "plan_tail_s")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "sort-gensort-1chip.json")) as f:
        return json.load(f)


# ── the plain reference ────────────────────────────────────────────────


def _record(key: bytes, tag: int) -> bytes:
    assert len(key) == 10
    return key + b"  " + f"{tag:032X}".encode() + b"  " + b"." * 52 + b"\r\n"


def test_ten_records_ordered_by_hand(tmp_path):
    """Two files.  ``B...`` repeats three times (input order: 1, 4, 8);
    a space sorts before a digit, a digit before a letter, upper case
    before lower, and ``~`` last."""
    keys = [b"mmmmmmmmmm", b"BBBBBBBBBB", b"~~~~~~~~~~", b"          ",
            b"BBBBBBBBBB", b"0000000000", b"zzzzzzzzzz", b"MMMMMMMMMM",
            b"BBBBBBBBBB", b"BBBBBBBBBC"]
    records = [_record(key, i) for i, key in enumerate(keys)]
    a, b = tmp_path / "a.dat", tmp_path / "b.dat"
    a.write_bytes(b"".join(records[:6]))
    b.write_bytes(b"".join(records[6:]))
    by_hand = [3, 5, 1, 4, 8, 9, 7, 0, 6, 2]
    got = reference_sort.lines([str(a), str(b)], {})
    assert got == [records[i][:98].decode("ascii") for i in by_hand]
    assert all(len(line) == 98 for line in got)
    # three partitions from a three-key sample: ordinals 0, 3, 6 give the
    # keys m, space, z; sorted: space, m, z; split points m and z
    parts = reference_sort.partitions([str(a), str(b)], 3, sample=3)
    assert parts == [b"".join(records[i] for i in by_hand[:7]),
                     b"".join(records[i] for i in (0,)),
                     b"".join(records[i] for i in (6, 2))]
    # as the harness reads a committed job back
    for r, part in enumerate(parts):
        (tmp_path / f"mr-out-{r}").write_bytes(part)
    os.remove(a), os.remove(b)
    assert reference.read_output(str(tmp_path)) == sorted(got)


def test_a_file_that_is_not_whole_records_is_an_error(tmp_path):
    bad = tmp_path / "bad.dat"
    bad.write_bytes(b"x" * 150)
    with pytest.raises(ValueError, match="whole"):
        reference_sort.lines([str(bad)], {})


# ── the generator ──────────────────────────────────────────────────────


def test_the_records_layout():
    records = gensort.records(300, 0xABCDEF0123, np.random.default_rng(1))
    assert records.shape == (300, 100) and records.dtype == np.uint8
    for i, row in enumerate(bytes(r) for r in records):
        number = 0xABCDEF0123 + i
        assert all(0x20 <= c <= 0x7E for c in row[:98])
        assert row[10:12] == row[44:46] == b"  " and row[98:] == b"\r\n"
        assert row[12:44] == f"{number:032X}".encode()
        assert row[46:98] == "".join(
            f"{(number >> 4 * g) & 15:X}" * 4 for g in range(13)).encode()
    keys = records[:, :10]
    assert keys.min() >= 0x20 and keys.max() <= 0x7E
    assert len({bytes(k) for k in keys}) == 300
    # all 95 printable characters are drawn
    many = gensort.records(5000, 0, np.random.default_rng(2))[:, :10]
    assert len(np.unique(many)) == 95


def test_keys_from_a_pool(tmp_path):
    records = gensort.records(2000, 0, np.random.default_rng(3),
                              distinct_keys=50)
    assert len({bytes(k) for k in records[:, :10]}) == 50


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
    """One record file a corpus file, as many whole records as it has
    hundreds of bytes, seeded by the first file's CRC-32; written once."""
    (tmp_path / "one").mkdir(), (tmp_path / "two").mkdir()
    (tmp_path / "salt").mkdir()
    corpus = _corpus(tmp_path / "one", (20_000, 12_345, 700))
    files = gensort.job_files(corpus)
    assert [os.path.getsize(p) for p in files] == [20_000, 12_300, 700]
    whole = b"".join(open(p, "rb").read() for p in files)
    numbers = [int(whole[i + 12:i + 44], 16)
               for i in range(0, len(whole), 100)]
    assert numbers == list(range(330))
    # the same corpus elsewhere: the same bytes; another first file: others
    again = gensort.job_files(_corpus(tmp_path / "two",
                                      (20_000, 12_345, 700)))
    assert [open(p, "rb").read() for p in again] == \
        [open(p, "rb").read() for p in files]
    other = gensort.job_files(_corpus(tmp_path / "salt",
                                      (20_000, 12_345, 700), salt=b"!"))
    assert open(other[0], "rb").read() != open(files[0], "rb").read()
    with open(corpus[0], "rb") as f:
        assert gensort.job_seed(corpus) == zlib.crc32(f.read())
    # once a seed: a second call writes nothing
    stamp = [os.stat(p).st_mtime_ns for p in files]
    assert gensort.job_files(corpus) == files
    assert [os.stat(p).st_mtime_ns for p in files] == stamp


def test_the_registered_reference_reads_the_record_files(tmp_path):
    from drivers import sort_inproc

    corpus = _corpus(tmp_path, (5_000, 3_000))
    assert reference.KINDS["sort"] is sort_inproc._reference_lines
    got = reference.KINDS["sort"](corpus, {"passes": 1})
    files = gensort.job_files(corpus)
    assert got == sorted(reference_sort.lines(files, {}))
    assert got == reference_sort.lines(files, {})   # its own order
    assert len(got) == 80


# ── the least bytes ────────────────────────────────────────────────────


def test_least_bytes_count_the_work():
    k = _config()["kernels"]
    assert roofline_sort.ingest_bytes(k["sort_ingest"]["shapes"]) == \
        2 * 1048576 + 10485 * 16
    assert roofline_sort.order_bytes(k["sort_order"]["shapes"]) == \
        5368704 * 216
    assert k["sort_ingest"]["shapes"]["chunk_records"] == 1048576 // 100


# ── the readers ────────────────────────────────────────────────────────


def _job(t_end, sort, problems=(), pull_s=0.2, sample_wall=0.1,
         sort_wall=1.0, **plan):
    plan = dict({"plan_s": sample_wall + sort_wall, "plan_stage_walls": {
        "sample": sample_wall, "sort": sort_wall}}, **plan)
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": {
                "stages": {"sample": {"sample_s": sample_wall}, "sort": sort},
                "plan": plan, "pull_s": pull_s, "d2h_s": pull_s / 2,
                "write_s": 0.5}}


def test_span_readers_are_medians_over_whole_jobs():
    obs = {"jobs": [
        _job(2.0, {"order_s": 0.2}, pull_s=0.1, sample_wall=0.10,
             sort_wall=1.0),
        _job(3.0, {"order_s": 0.4}, pull_s=0.3, sample_wall=0.30,
             sort_wall=1.6),
        _job(2.5, {"order_s": 0.3}, pull_s=0.2, sample_wall=0.20,
             sort_wall=1.2),
        # a failed job counts for nothing
        _job(0.1, {"order_s": 9.0}, ["exit code 1"], pull_s=9.0,
             sample_wall=9.0, sort_wall=99.0)]}
    assert _read("sort_sample_s", obs) == pytest.approx(0.2)
    assert _read("sort_order_s", obs) == pytest.approx(0.3)
    assert _read("sort_pull_s", obs) == pytest.approx(0.2)
    assert _read("sort_ingest_stage_s", obs) == pytest.approx(0.9)
    # the older readers the cell lists
    assert _read("write_s", obs) == pytest.approx(0.5)
    assert _read("plan_tail_s", obs) == pytest.approx(1.1)  # .9, 1.1, 1.1


def test_counts_are_the_traced_jobs():
    job = _job(2.0, {"sort_partition_rows": [90, 110, 100, 100],
                     "sort_resident_bytes": 602_336_160})
    for name in COUNT_READERS:
        assert _read(name, {"jobs": [job]}) is None  # no traced job
    obs = {"jobs": [job], "traced_job": job}
    assert _read("sort_partition_skew", obs) == pytest.approx(1.1)
    assert _read("sort_resident_MB", obs) == pytest.approx(602.33616)
    job["pipeline_stats"]["stages"]["sort"]["sort_partition_rows"] = [0, 0]
    assert _read("sort_partition_skew", obs) is None


def _traced_obs(modules, **sort):
    job = _job(2.0, dict({"steps": 4, "sort_records": 41_000}, **sort))
    return {"jobs": [job], "traced_job": job, "config": _config(),
            "traffic": {"kernel": "sort_order"},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"modules": modules}}


def test_trace_readers_by_hand():
    obs = _traced_obs({
        "jit_sort_ingest_step(123)": {"runs": 4, "seconds": 0.008},
        "jit_sort_order(9)": {"runs": 1, "seconds": 0.05},
        "jit_sort_pull_block(7)": {"runs": 1, "seconds": 0.001}})
    # 4 steps of 1 MiB in 8 ms
    assert _read("sort_ingest_ms_per_MiB", obs) == pytest.approx(2.0)
    least = 4 * (2 * 1048576 + 10485 * 16)
    assert _read("sort_ingest_roofline", obs) == pytest.approx(
        100 * least / 819e9 / 0.008)
    # 41,000 records of 100 B in 50 ms
    assert _read("sort_order_ms_per_MiB", obs) == pytest.approx(
        50.0 / (4_100_000 / 2 ** 20))
    assert _read("sort_order_roofline", obs) == pytest.approx(
        100 * 41_000 * 216 / 819e9 / 0.05)


def test_a_trace_cut_before_the_jobs_end_reads_nothing():
    obs = _traced_obs({
        "jit_sort_ingest_step(123)": {"runs": 3, "seconds": 0.006}})
    for name in TRACE_READERS:
        assert _read(name, obs) is None, name
    del obs["peaks"]
    obs["trace"]["modules"] = {
        "jit_sort_ingest_step(1)": {"runs": 4, "seconds": 0.008},
        "jit_sort_order(2)": {"runs": 1, "seconds": 0.05}}
    assert _read("sort_ingest_roofline", obs) is None
    assert _read("sort_order_roofline", obs) is None
    assert _read("sort_order_ms_per_MiB", obs) is not None


def _recorded():
    with open(DATA) as f:
        rec = json.load(f)
    return rec, dict(rec["obs"], config=_config(),
                     traffic={"kernel": "sort_order"},
                     peaks={"hbm_bytes_per_s": 819e9})


def test_on_what_the_chip_recorded():
    rec, obs = _recorded()
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert set(LISTED) <= set(rec["expected"])
    ps = obs["traced_job"]["pipeline_stats"]
    sort, sample, plan = ps["stages"]["sort"], ps["stages"]["sample"], \
        ps["plan"]
    config = _config()
    assert sort["sort_records"] == config["records"]["records"] == 5368704
    assert sort["steps"] == 513 and sort["bytes_in"] == 536870400
    assert sort["sort_resident_bytes"] >= 536870400
    assert sum(sort["sort_partition_rows"]) == 5368704
    assert len(sort["sort_partition_rows"]) == 10
    assert sample["sort_sample_keys"] == 100000
    assert plan["plan_handoff"] == "device"
    assert plan["plan_intermediate_bytes"] == 0
    assert 1.0 <= _read("sort_partition_skew", obs) < 1.05
    for name in ("sort_ingest_roofline", "sort_order_roofline",
                 "stream_device_idle"):
        assert 0.0 < _read(name, obs) < 100.0, name


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    _, obs = _recorded()
    obs = copy.deepcopy(obs)
    jobs = obs["jobs"] + [obs["traced_job"]]
    for job in jobs:
        ps = job["pipeline_stats"]
        ps.pop("pull_s", None)
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


def _cell(tmp_path, records=330):
    return types.SimpleNamespace(
        name="plan-sort-gensort", config=_config(),
        job_bytes=records * 100, files=[], workroot=str(tmp_path),
        traffic={}, obs={})


def _sorted_job(tmp_path, cell, n_reduce=10, **sort_over):
    """A job as a correct program leaves it: the records of a small
    generated input, ordered, in ten partitions."""
    records = cell.job_bytes // 100
    rows = gensort.records(records, 0, np.random.default_rng(4))
    rows = rows[np.lexsort(tuple(rows[:, j] for j in reversed(range(10))))]
    workdir = tmp_path / "job-0"
    workdir.mkdir(exist_ok=True)
    cuts = [records * r // n_reduce for r in range(n_reduce + 1)]
    for r in range(n_reduce):
        (workdir / f"mr-out-{r}").write_bytes(
            rows[cuts[r]:cuts[r + 1]].tobytes())
    sort = {"steps": 1, "sort_records": records,
            "sort_resident_bytes": 1_174_320,
            "sort_partition_rows": np.diff(cuts).tolist()}
    sort.update(sort_over)
    return {"rc": 0, "log_text": "", "workdir": str(workdir),
            "pipeline_stats": {
                "stages": {"sample": {}, "sort": sort},
                "plan": {"plan_handoff": "device",
                         "plan_intermediate_bytes": 0}}}


def test_a_sorted_job_breaks_no_condition(tmp_path):
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path)
    assert driver.job_problems(cell, _sorted_job(tmp_path, cell)) == []


@pytest.mark.parametrize("over, said", [
    ({"steps": 0}, "cannot hold"),
    ({"sort_records": 329}, "the job holds 330"),
    ({"sort_resident_bytes": 32_999}, "did not hold the job"),
    # a program that has no such counters
    ({"sort_records": None, "sort_resident_bytes": None}, "did not hold"),
])
def test_a_job_whose_counters_are_off_is_a_failed_job(tmp_path, over, said):
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell, **{k: v for k, v in over.items()
                                         if v is not None})
    for key, value in over.items():
        if value is None:
            del job["pipeline_stats"]["stages"]["sort"][key]
    problems = driver.job_problems(cell, job)
    assert any(said in p for p in problems), problems


@pytest.mark.parametrize("plan, said", [
    ({"plan_handoff": "host"}, "left the device"),
    ({"plan_intermediate_bytes": 100}, "left the device"),
    ({"plan_spilled_bytes": 5}, "left the device"),
])
def test_records_that_left_the_device_fail_the_job(tmp_path, plan, said):
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell)
    job["pipeline_stats"]["plan"].update(plan)
    assert any(said in p for p in driver.job_problems(cell, job))


def test_the_host_path_and_a_silent_program_fail_the_job(tmp_path):
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell)
    job["log_text"] = "planrun: stage 'sort': the sort needs the host path"
    assert "a stage took the host path" in driver.job_problems(cell, job)
    job = _sorted_job(tmp_path, cell)
    job["pipeline_stats"] = None
    assert any("printed no pipeline_stats" in p
               for p in driver.job_problems(cell, job))


def _swap(path, i, j):
    data = bytearray(open(path, "rb").read())
    data[i * 100:(i + 1) * 100], data[j * 100:(j + 1) * 100] = \
        data[j * 100:(j + 1) * 100], data[i * 100:(i + 1) * 100]
    open(path, "wb").write(bytes(data))


def test_the_order_itself_is_a_condition(tmp_path):
    """What the harness's comparison of sorted lines cannot see."""
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell)
    wd = job["workdir"]
    assert driver.order_problems(wd, 10, cell.job_bytes) == []
    # two records of one partition change places
    _swap(os.path.join(wd, "mr-out-4"), 3, 9)
    got = driver.job_problems(cell, job)
    assert len(got) == 1 and "partition 4" in got[0] \
        and "less than the key before" in got[0]
    _swap(os.path.join(wd, "mr-out-4"), 3, 9)
    # two whole partitions change places: each in order, the whole not
    a, b = os.path.join(wd, "mr-out-2"), os.path.join(wd, "mr-out-3")
    os.rename(a, a + ".x"), os.rename(b, a), os.rename(a + ".x", b)
    got = driver.job_problems(cell, job)
    assert got and all("less than the key before" in p for p in got)
    os.rename(a, a + ".x"), os.rename(b, a), os.rename(a + ".x", b)
    # a partition that was not committed, one cut mid-record
    os.remove(os.path.join(wd, "mr-out-9"))
    got = driver.job_problems(cell, job)
    assert any("partition 9 was not committed" in p for p in got)
    assert any("the partitions hold" in p for p in got)
    with open(os.path.join(wd, "mr-out-0"), "ab") as f:
        f.write(b"half a record")
    assert any("not whole records" in p
               for p in driver.job_problems(cell, job))


def test_key_bytes_over_0x7f_are_compared_unsigned(tmp_path):
    from drivers import sort_inproc as driver

    wd = tmp_path / "job"
    wd.mkdir()
    low = b"\x7f" * 10 + b"." * 90
    high = b"\x80" + b"\x00" * 9 + b"." * 90
    last = b"\x80" + b"\x00" * 8 + b"\x01" + b"." * 90
    (wd / "mr-out-0").write_bytes(low + high)
    (wd / "mr-out-1").write_bytes(last)
    assert driver.order_problems(str(wd), 2, 300) == []
    (wd / "mr-out-1").write_bytes(low)
    assert driver.order_problems(str(wd), 2, 300) != []


def test_a_program_without_the_chain_cannot_run_the_cell(monkeypatch,
                                                         tmp_path):
    from drivers import sort_inproc as driver, stream_inproc

    registry = importlib.import_module("dsi_tpu.obs.registry")
    monkeypatch.setattr(stream_inproc, "claim_device", lambda cell: None)
    cell = _cell(tmp_path)
    driver.claim_device(cell)   # this program's schema has the counter
    monkeypatch.setattr(registry, "SCHEMA_KEYS", tuple(
        k for k in registry.SCHEMA_KEYS if k != "sort_records"))
    with pytest.raises(SystemExit) as e:
        driver.claim_device(cell)
    assert "has no sort chain" in str(e.value)


def test_the_disk_touch_writes_the_windows_bytes_and_leaves_nothing(
        tmp_path, monkeypatch):
    from drivers import sort_inproc as driver

    cell = _cell(tmp_path, records=1000)
    cell.traffic = {"max_jobs": 3}
    written = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (
        written.append(os.fstat(fd).st_size), real_fsync(fd)))
    driver._touch_disk(cell)
    # the window's three jobs and two more: files of at least the job's
    # bytes each, every one synced
    assert len(written) == 5 and min(written) >= cell.job_bytes
    assert os.listdir(str(tmp_path)) == []
