"""What PR 53 adds for the cell ``plan-sort-mesh4``: the least work of the
exchange step (``roofline_sortmesh.py``), the five readers
(``layer_metrics/sortmesh_*.py``) and the driver ``drivers/sortmesh_inproc``.

The readers are tried on a hand-made ``obs`` whose answer can be worked
out by eye and on a program that reports no such line or key (the parent,
a one-device sort), where they return None and do not raise.  The
driver's conditions are each seen to fire: a job that used three devices,
one whose records left the devices, one whose records did not cross the
mesh, and one that is right."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import gensort
import roofline_sort
import roofline_sortmesh

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("sortmesh_exchange_ms_per_MiB", "sortmesh_exchange_roofline",
           "sortmesh_order_roofline", "sortmesh_exchange_MB",
           "sortmesh_device_skew")
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9}


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config(name="sort-gensort-mesh4"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


# ── the configuration and the entries ──────────────────────────────────


def test_the_configuration_is_one_chips_share_on_four():
    mesh, one = _config(), _config("sort-gensort-1chip")
    for key in ("corpus", "records", "partitions", "replicas", "rehearsal"):
        assert mesh[key] == one[key], key
    assert mesh["devices"] == 4 and mesh["chunk_bytes"] == 1048576
    assert mesh["argv"] == ["--chain", "sort", "--devices", "4",
                            "--nreduce", "10", "--stats", "--workdir",
                            "{workdir}"]
    assert mesh["guarantees"][:6] == one["guarantees"]
    assert len(mesh["guarantees"]) == 8
    assert set(mesh["reduced"]) == set(one["reduced"])
    assert set(mesh["assumed"]) == set(one["assumed"]) | {
        "device_splits", "store_slack"}
    assert mesh["kernels"]["sort_exchange"]["module"] == "sort_exchange_step"
    assert mesh["kernels"]["sort_order"]["module"] == "sort_order"
    # a device's share of the job
    assert mesh["kernels"]["sort_order"]["shapes"]["records"] * 4 == \
        mesh["records"]["records"]


def test_the_cell_stands_in_the_lists_it_should_and_in_no_other():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "plan-sort-mesh4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sort-gensort-mesh4", "gensort-1pass", 4)
    listed = {m["name"] for m in bench["per_layer"]
              if "plan-sort-mesh4" in m.get("workloads", ())}
    assert listed == set(READERS) | {
        "a2a_share", "a2a_exposed", "cache_load_s", "step_sort_share",
        "stream_device_idle", "write_s", "plan_tail_s",
        "plan_unspanned_share", "sort_sample_s", "sort_ingest_stage_s",
        "sort_order_s", "sort_pull_s", "sort_partition_skew",
        "sort_resident_MB"}
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == ["plan-sort-mesh4"]
            assert m["moves"] == "stream_MBps"


# ── the least work ─────────────────────────────────────────────────────


def test_least_work_of_a_step_is_the_larger_of_memory_and_interconnect():
    shapes = _config()["kernels"]["sort_exchange"]["shapes"]
    assert roofline_sortmesh.exchange_hbm_bytes(shapes) == \
        2 * 1048576 + 10485 * 16 == roofline_sort.ingest_bytes(shapes)
    assert roofline_sortmesh.exchange_ici_bytes(shapes) == 786432.0
    hbm_s, ici_s = 2264912 / 819e9, 786432 / 200e9
    assert hbm_s == pytest.approx(2.77e-6, rel=0.01)
    assert ici_s == pytest.approx(3.93e-6, rel=0.01)
    # at the published peaks the interconnect bounds the step
    assert roofline_sortmesh.exchange_least_s(shapes, PEAKS) == \
        pytest.approx(ici_s)
    # with a link five times as fast, the memory does
    assert roofline_sortmesh.exchange_least_s(
        shapes, dict(PEAKS, ici_bits_per_s=8000e9)) == pytest.approx(hbm_s)
    # two devices keep half of what they read
    assert roofline_sortmesh.exchange_ici_bytes(
        dict(shapes, devices=2)) == 524288.0
    assert roofline_sortmesh.exchange_ici_bytes(
        dict(shapes, devices=1)) == 0.0


# ── the readers ────────────────────────────────────────────────────────


def _job(sort):
    return {"t_start": 0.0, "t_end": 1.0, "problems": [],
            "pipeline_stats": {
                "stages": {"sample": {"sample_s": 0.1}, "sort": sort},
                "plan": {"plan_s": 0.9, "plan_stage_walls": {
                    "sample": 0.1, "sort": 0.8}},
                "pull_s": 0.01, "write_s": 0.2}}


MESH_SORT = {"steps": 5, "sort_records": 200_000, "sort_devices": 4,
             "device_rows": [50_000, 52_000, 49_000, 49_000],
             "sort_device_capacity": 66_048, "sort_exchange_rows": 150_100,
             "sort_exchange_bytes": 15_010_000,
             "sort_resident_bytes": 29_589_504}


def _obs(modules, sort=MESH_SORT, config="sort-gensort-mesh4"):
    job = _job(dict(sort))
    return {"jobs": [job], "traced_job": job, "config": _config(config),
            "traffic": {"kernel": "sort_order"}, "peaks": dict(PEAKS),
            "trace": {"modules": modules}}


def test_the_readers_by_hand():
    obs = _obs({"jit_sort_exchange_step(11)": {"runs": 5.0, "seconds": 0.01},
                "jit_sort_order(3)": {"runs": 1.0, "seconds": 0.002},
                "jit_sort_pull_block(7)": {"runs": 1.0, "seconds": 0.001}})
    # 5 steps of 1 MiB a device in 10 ms of a device's time
    assert _read("sortmesh_exchange_ms_per_MiB", obs) == pytest.approx(2.0)
    assert _read("sortmesh_exchange_roofline", obs) == pytest.approx(
        100 * 5 * (786432 / 200e9) / 0.01)
    # a device orders the mean of the four: 50,000 records of 216 B
    assert _read("sortmesh_order_roofline", obs) == pytest.approx(
        100 * 50_000 * 216 / 819e9 / 0.002)
    assert _read("sortmesh_exchange_MB", obs) == pytest.approx(15.01)
    assert _read("sortmesh_device_skew", obs) == pytest.approx(1.04)
    for name in ("sortmesh_exchange_roofline", "sortmesh_order_roofline"):
        assert 0.0 < _read(name, obs) < 100.0


def test_a_share_of_a_roofline_cannot_pass_100():
    """The fastest a step can be is its least time: at that speed the
    share reads 100."""
    least = roofline_sortmesh.exchange_least_s(
        _config()["kernels"]["sort_exchange"]["shapes"], PEAKS)
    obs = _obs({"jit_sort_exchange_step(1)": {"runs": 5.0,
                                              "seconds": 5 * least},
                "jit_sort_order(3)": {"runs": 1.0,
                                      "seconds": 50_000 * 216 / 819e9}})
    assert _read("sortmesh_exchange_roofline", obs) == pytest.approx(100.0)
    assert _read("sortmesh_order_roofline", obs) == pytest.approx(100.0)


def test_a_trace_cut_before_the_jobs_end_or_no_trace_reads_nothing():
    obs = _obs({"jit_sort_exchange_step(11)": {"runs": 4.0,
                                               "seconds": 0.008}})
    for name in READERS[:3]:
        assert _read(name, obs) is None, name
    # the counts do not need the trace, only the traced job
    assert _read("sortmesh_exchange_MB", obs) == pytest.approx(15.01)
    del obs["traced_job"]
    for name in READERS:
        assert _read(name, obs) is None, name
    obs = _obs({"jit_sort_exchange_step(11)": {"runs": 5.0, "seconds": 0.01},
                "jit_sort_order(3)": {"runs": 1.0, "seconds": 0.002}})
    del obs["peaks"]
    assert _read("sortmesh_exchange_roofline", obs) is None
    assert _read("sortmesh_order_roofline", obs) is None
    assert _read("sortmesh_exchange_ms_per_MiB", obs) is not None


@pytest.mark.parametrize("name", READERS)
def test_none_on_a_program_that_sorts_on_one_device_or_not_at_all(name):
    """The parent: its sort scope has no exchange, its trace no such
    module, its configuration no such kernel block."""
    one = {"steps": 513, "sort_records": 5368704, "device_rows": [5368704],
           "sort_resident_bytes": 602_427_392}
    mods = {"jit_sort_ingest_step(1)": {"runs": 513.0, "seconds": 0.015},
            "jit_sort_order(2)": {"runs": 1.0, "seconds": 0.2}}
    if name != "sortmesh_order_roofline":   # one device orders its records
        assert _read(name, _obs(mods, one)) is None
        assert _read(name, _obs(mods, one, "sort-gensort-1chip")) is None
    for stages in ({"grep": {"steps": 5}}, {}):
        obs = _obs(mods)
        obs["traced_job"]["pipeline_stats"]["stages"] = stages
        assert _read(name, obs) is None
    obs = _obs(mods)
    obs["traced_job"]["pipeline_stats"] = None
    assert _read(name, obs) is None
    assert _read(name, {"jobs": [], "config": _config(), "traffic": {}}) \
        is None
    assert _read(name, {"jobs": [], "config": {}, "traffic": {},
                        "trace": {"modules": mods}}) is None


# ── the driver ─────────────────────────────────────────────────────────


def _cell(tmp_path, records=400):
    return types.SimpleNamespace(
        name="plan-sort-mesh4", config=dict(_config(), chunk_bytes=10_000),
        job_bytes=records * 100, files=[], workroot=str(tmp_path),
        traffic={}, obs={})


def _sorted_job(tmp_path, cell, **sort_over):
    """A job as a correct program leaves it: the records ordered, in ten
    partitions, a quarter on every device, three in four exchanged."""
    records = cell.job_bytes // 100
    rows = gensort.records(records, 0, np.random.default_rng(4))
    rows = rows[np.lexsort(tuple(rows[:, j] for j in reversed(range(10))))]
    workdir = tmp_path / "job-0"
    workdir.mkdir(exist_ok=True)
    cuts = [records * r // 10 for r in range(11)]
    for r in range(10):
        (workdir / f"mr-out-{r}").write_bytes(
            rows[cuts[r]:cuts[r + 1]].tobytes())
    sort = {"steps": 1, "sort_records": records, "sort_devices": 4,
            "device_rows": [records // 4] * 4,
            "sort_exchange_rows": 3 * records // 4,
            "sort_exchange_bytes": 75 * records,
            "sort_resident_bytes": 4 * 256 * 112,
            "sort_partition_rows": np.diff(cuts).tolist()}
    sort.update(sort_over)
    return {"rc": 0, "log_text": "", "workdir": str(workdir),
            "pipeline_stats": {
                "stages": {"sample": {}, "sort": sort},
                "plan": {"plan_handoff": "device",
                         "plan_intermediate_bytes": 0}}}


def test_a_job_that_is_right_breaks_no_condition(tmp_path):
    from drivers import sortmesh_inproc as driver

    cell = _cell(tmp_path)
    assert driver.job_problems(cell, _sorted_job(tmp_path, cell)) == []
    # what sort_inproc holds a one-device job to would fail it: one step
    # of one chunk does not hold the job, four do
    from drivers import sort_inproc

    assert any("cannot hold" in p for p in sort_inproc.job_problems(
        cell, _sorted_job(tmp_path, cell)))


@pytest.mark.parametrize("over, said", [
    # three devices took part
    ({"device_rows": [134, 133, 133]}, "not 4 devices"),
    ({"device_rows": [200, 200, 0, 0]}, "not 4 devices"),
    ({"device_rows": [100, 100, 100, 99]}, "together all of them"),
    # the records stayed where they were read
    ({"sort_exchange_rows": 200}, "did not cross the mesh"),
    ({"sort_exchange_rows": 0}, "did not cross the mesh"),
    ({"steps": 0}, "cannot hold"),
    ({"sort_records": 399}, "the job holds 400"),
    ({"sort_resident_bytes": 39_999}, "did not hold the job"),
])
def test_a_job_whose_counters_are_off_is_a_failed_job(tmp_path, over, said):
    from drivers import sortmesh_inproc as driver

    cell = _cell(tmp_path)
    problems = driver.job_problems(cell, _sorted_job(tmp_path, cell, **over))
    assert any(said in p for p in problems), problems


def test_a_one_device_programs_line_fails_every_mesh_condition(tmp_path):
    from drivers import sortmesh_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell, steps=4, device_rows=[400])
    for key in ("sort_devices", "sort_exchange_rows", "sort_exchange_bytes"):
        del job["pipeline_stats"]["stages"]["sort"][key]
    problems = driver.job_problems(cell, job)
    assert any("not 4 devices" in p for p in problems)
    assert any("did not cross the mesh" in p for p in problems)


@pytest.mark.parametrize("plan", [
    {"plan_handoff": "host"}, {"plan_intermediate_bytes": 100},
    {"plan_spilled_bytes": 5}])
def test_records_that_left_the_devices_fail_the_job(tmp_path, plan):
    from drivers import sortmesh_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell)
    job["pipeline_stats"]["plan"].update(plan)
    assert any("left the device" in p
               for p in driver.job_problems(cell, job))
    job = _sorted_job(tmp_path, cell)
    job["log_text"] = "planrun: stage 'sort': the sort needs the host path"
    assert "a stage took the host path" in driver.job_problems(cell, job)
    job["pipeline_stats"] = None
    assert any("printed no pipeline_stats" in p
               for p in driver.job_problems(cell, job))


def test_the_order_itself_is_a_condition(tmp_path):
    from drivers import sortmesh_inproc as driver

    cell = _cell(tmp_path)
    job = _sorted_job(tmp_path, cell)
    path = os.path.join(job["workdir"], "mr-out-3")
    data = bytearray(open(path, "rb").read())
    data[0:100], data[500:600] = data[500:600], data[0:100]
    open(path, "wb").write(bytes(data))
    assert any("less than the key before" in p
               for p in driver.job_problems(cell, job))
    os.remove(path)
    assert any("partition 3 was not committed" in p
               for p in driver.job_problems(cell, job))


def test_a_program_without_the_exchange_cannot_run_the_cell(monkeypatch):
    """The parent: the run ends in ``claim_device``, before any input."""
    from drivers import sortmesh_inproc as driver
    from drivers import sort_inproc

    fake = types.SimpleNamespace(SCHEMA_KEYS=("sort_records", "stage_stats"))
    monkeypatch.setattr(sort_inproc, "claim_device", lambda cell: None)
    monkeypatch.setattr(driver.importlib, "import_module",
                        lambda name: fake)
    cell = types.SimpleNamespace(name="plan-sort-mesh4",
                                 config={"entry": "dsi_tpu.cli.planrun"})
    with pytest.raises(SystemExit) as e:
        driver.claim_device(cell)
    assert "no sort_exchange_rows" in str(e.value.code)
    fake.SCHEMA_KEYS += ("sort_exchange_rows",)
    driver.claim_device(cell)   # and with it, it runs
