"""What PR 41 adds for the cell ``plan-index-pages``: the three readers
(``layer_metrics/index_docs_per_wave.py``, ``index_pack_ms.py``,
``index_read_s.py``) and the driver ``drivers/indexpack_inproc``.

The readers are tried on a hand-made ``obs`` whose answer can be worked out
by eye, on what ``planrun --stats`` printed and the trace reduction gave on
the chip (``recorded/indexpack-pipeline-stats.json``: the jobs of one traced
``plan-index-pages`` run, with the reduction's ``modules``), and on a
program that reports no such line or key (the parent, or a walk that does
not pack), where they return None and do not raise.  The driver's
conditions are tried on a job that did not pack, and its warm-up on the
program's own plan."""

import copy
import importlib
import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "recorded", "indexpack-pipeline-stats.json")
NEW = ("index_docs_per_wave", "index_pack_ms", "index_read_s")
#: every reader the cell is listed under, new or not
LISTED = NEW + ("cache_load_s", "step_sort_share", "stream_device_idle",
                "write_s", "plan_tail_s", "plan_index_stage_s",
                "plan_join_stage_s", "index_wave_ms", "index_wave_fill",
                "index_group_s", "index_postings_M", "index_wave_ms_per_MiB",
                "index_wave_roofline")


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "plan-index-pages-1chip.json")) as f:
        return json.load(f)


def _job(t_end, walk, problems=(), read_s=None, **plan):
    ps = {"stages": {"indexer": walk}, "plan": plan, "write_s": 0.5}
    if read_s is not None:
        ps["read_s"] = read_s
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": ps}


def test_span_readers_are_medians_over_whole_jobs():
    obs = {"jobs": [
        _job(6.0, {"waves": 64, "docs": 12000, "pack_s": 0.064}, read_s=0.2),
        _job(7.0, {"waves": 64, "docs": 12000, "pack_s": 0.128}, read_s=0.4),
        _job(6.5, {"waves": 64, "docs": 12000, "pack_s": 0.096}, read_s=0.3),
        # a failed job counts for nothing
        _job(1.0, {"waves": 1, "docs": 1, "pack_s": 9.0}, ["exit code 1"],
             read_s=9.0)]}
    assert _read("index_pack_ms", obs) == pytest.approx(1.5)   # 1, 2, 1.5
    assert _read("index_read_s", obs) == pytest.approx(0.3)
    # a count of the traced job: nothing without one
    assert _read("index_docs_per_wave", obs) is None
    obs["traced_job"] = obs["jobs"][0]
    assert _read("index_docs_per_wave", obs) == pytest.approx(187.5)


def test_a_walk_that_gives_a_document_a_wave_reads_one():
    """``plan-index-books`` lists ``index_docs_per_wave`` too, and has no
    ``pack_s`` and (on the parent) no ``read_s``."""
    job = _job(6.0, {"waves": 313, "docs": 313, "pack_docs": False})
    obs = {"jobs": [job], "traced_job": job}
    assert _read("index_docs_per_wave", obs) == pytest.approx(1.0)
    assert _read("index_pack_ms", obs) is None
    assert _read("index_read_s", obs) is None


def _recorded():
    with open(DATA) as f:
        rec = json.load(f)
    return rec, dict(rec["obs"], config=_config(),
                     traffic={"kernel": "idx_wave"},
                     peaks={"hbm_bytes_per_s": 819e9})


def test_on_what_the_chip_recorded():
    rec, obs = _recorded()
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert set(LISTED) <= set(rec["expected"])
    ps = obs["traced_job"]["pipeline_stats"]
    walk, plan = ps["stages"]["indexer"], ps["plan"]
    # every document in a packed wave of one size, the handoff on the
    # device, the whole index written from the arrays
    assert walk["pack_docs"] is True
    assert walk["docs"] == walk["wave_docs"] > 11_000
    assert len(walk["waves_by_size"]) == 1
    assert sum(walk["waves_by_size"].values()) == walk["waves"] < 300
    assert walk["wave_doc_bytes"] == walk["bytes_in"] == 67108608
    assert plan["plan_handoff"] == "device"
    assert walk["postings_rows"] > 5e6 and walk["index_terms"] > 1e5
    assert ps["write_rows_packed"] == walk["index_terms"]
    assert ps["write_rows_dict"] == 0
    assert _read("index_wave_fill", obs) > 95.0
    assert _read("index_docs_per_wave", obs) > 40.0
    assert 0.0 < _read("index_wave_roofline", obs) < 100.0
    # the table rung the configuration's kernel block states is the one
    # the job's waves ran at (the enqueue spans' cap)
    assert rec["settled_rung"] == _config()["kernels"]["idx_wave"][
        "shapes"]["table_rows"]


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    _, obs = _recorded()
    obs = copy.deepcopy(obs)
    jobs = obs["jobs"] + [obs["traced_job"]]
    for job in jobs:
        ps = job["pipeline_stats"]
        ps.pop("read_s", None)
        ps["stages"]["indexer"] = {"waves": 0, "replays": 2}
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


def _cell(tmp_path, n_docs=100, job_bytes=800_000, chunk_bytes=None):
    config = _config()
    if chunk_bytes is not None:      # in place of the argv's own
        at = config["argv"].index("--chunk-bytes")
        config["argv"][at + 1] = str(chunk_bytes)
    return types.SimpleNamespace(
        name="plan-index-pages", config=config, job_bytes=job_bytes,
        files=[f"d{i:05d}.txt" for i in range(n_docs)],
        workroot=str(tmp_path), traffic={}, obs={})


def _packed_job(tmp_path, cell, **walk_over):
    workdir = tmp_path / "job-0"
    workdir.mkdir(exist_ok=True)
    for r in range(10):
        (workdir / f"mr-out-{r}").write_text("")
    (workdir / "mr-out-join").write_text("")
    walk = {"docs": len(cell.files), "wave_docs": len(cell.files),
            "pack_docs": True, "waves": 1, "wave_chunk_bytes": 1 << 20}
    walk.update(walk_over)
    return {"log_text": "", "workdir": str(workdir), "pipeline_stats": {
        "stages": {"indexer": walk}, "plan": {"plan_handoff": "device"}}}


def test_a_packed_job_breaks_no_condition(tmp_path):
    from drivers import indexpack_inproc as driver

    cell = _cell(tmp_path)
    assert driver.job_problems(cell, _packed_job(tmp_path, cell)) == []


@pytest.mark.parametrize("over, said", [
    # the walk of one document a wave: the flag did not reach the engine
    ({"pack_docs": False, "waves": 100,
      "wave_chunk_bytes": 100 * 8192 + (1 << 20)}, "did not pack"),
    ({"pack_docs": False, "waves": 100,
      "wave_chunk_bytes": 100 * 8192 + (1 << 20)}, "not filled"),
    # a program that has no such counters
    ({"pack_docs": None, "wave_docs": None, "wave_chunk_bytes": None},
     "did not pack"),
    ({"wave_docs": 99}, "not every one"),
    ({"docs": 99}, "were handed over"),
])
def test_a_job_that_did_not_pack_is_a_failed_job(tmp_path, over, said):
    from drivers import indexpack_inproc as driver

    cell = _cell(tmp_path)
    job = _packed_job(tmp_path, cell, **{k: v for k, v in over.items()
                                         if v is not None})
    for key, value in over.items():
        if value is None:
            del job["pipeline_stats"]["stages"]["indexer"][key]
    problems = driver.job_problems(cell, job)
    assert any(said in p for p in problems), problems


def test_the_other_conditions_are_index_inprocs(tmp_path):
    from drivers import indexpack_inproc as driver

    cell = _cell(tmp_path)
    job = _packed_job(tmp_path, cell)
    job["pipeline_stats"]["plan"]["plan_handoff"] = "host"
    os.remove(os.path.join(job["workdir"], "mr-out-3"))
    job["log_text"] = "planrun: stage 'indexer': needs the host path"
    problems = driver.job_problems(cell, job)
    assert len(problems) == 3
    job["pipeline_stats"] = None
    assert len(driver.job_problems(cell, job)) == 2


def test_the_chunk_size_is_the_argvs_or_the_configurations(tmp_path):
    from drivers import indexpack_inproc as driver

    assert driver._chunk_bytes(_cell(tmp_path)) == \
        _config()["chunk_bytes"] == 524288
    assert driver._chunk_bytes(_cell(tmp_path, chunk_bytes=65536)) == 65536
    cell = _cell(tmp_path)           # the flag left out: the default's
    at = cell.config["argv"].index("--chunk-bytes")
    del cell.config["argv"][at:at + 2]
    cell.config["chunk_bytes"] = 1048576
    assert driver._chunk_bytes(cell) == 1048576


def test_the_warm_up_walks_the_jobs_last_waves(tmp_path, monkeypatch):
    """The documents from where the job's ``pipeline_depth`` + 2 last
    chunks begin pack, alone, into those same chunks."""
    root = os.path.dirname(os.path.dirname(HERE))
    monkeypatch.syspath_prepend(root)
    from drivers import indexpack_inproc as driver

    plan = importlib.import_module(
        "dsi_tpu.parallel.grepstream").plan_packed_waves
    cell = _cell(tmp_path, chunk_bytes=65536)
    sizes = [1024 + (i * 7919) % 15000 for i in range(400)]
    first = driver._last_waves(cell, sizes, 4)
    whole = plan(sizes, 1, 65536)
    tail = plan(sizes[first:], 1, 65536)
    assert len(tail) == 4 and len(whole) > 8
    assert [[[i + first for i in slot] for slot in slots]
            for slots, _ in tail] == [slots for slots, _ in whole[-4:]]
    # fewer waves than asked for: the whole job
    assert driver._last_waves(cell, sizes[:3], 4) == 0
    assert driver._last_waves(cell, [], 4) == 0
