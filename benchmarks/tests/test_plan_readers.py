"""The readers PR 30 adds for a plan job (``layer_metrics/plan_*.py``,
``relay_*.py``): on a hand-made ``obs`` whose answer can be worked out by
eye, on what ``planrun --stats`` printed and the trace reduction gave on the
chip (``recorded/plan-pipeline-stats.json``: the jobs of one traced
``plan-grepwc-1pct`` run, with the reduction's ``modules``), and on a
program that reports no such line or key, where they have to return None
and must not raise.  The older readers the cell lists are read over the
same record."""

import copy
import importlib
import json
import os

import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded",
                    "plan-pipeline-stats.json")
SPAN_READERS = ("plan_grep_stage_s", "plan_wc_stage_s", "relay_append_ms",
                "plan_tail_s")
TRACE_READERS = ("relay_pack_ms_per_MiB", "relay_pack_roofline",
                 "plan_wc_step_ms_per_MiB")
NEW = SPAN_READERS + ("plan_handoff_MB",) + TRACE_READERS


def _read(name, obs):
    return importlib.import_module(f"layer_metrics.{name}").read(obs)


def _job(t_end, problems=(), **plan):
    return {"t_start": 0.0, "t_end": t_end, "problems": list(problems),
            "pipeline_stats": {"stages": {}, "plan": plan, "write_s": 0.1}}


def _config():
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "..",
                           "configs", "plan-grepwc-1chip.json")) as f:
        return json.load(f)


def test_span_readers_are_medians_over_whole_jobs():
    walls = lambda g, w: {"grep": g, "wc": w}  # noqa: E731
    obs = {"jobs": [
        _job(10.0, plan_stage_walls=walls(2.0, 7.0), plan_s=9.0,
             relay_append_s=0.5, relay_appends=500),
        _job(12.0, plan_stage_walls=walls(3.0, 8.0), plan_s=11.5,
             relay_append_s=1.0, relay_appends=500),
        _job(11.0, plan_stage_walls=walls(2.5, 7.5), plan_s=10.2,
             relay_append_s=0.75, relay_appends=500),
        # a failed job counts for nothing
        _job(1.0, ["exit code 1"], plan_stage_walls=walls(0.1, 0.1),
             plan_s=0.2, relay_append_s=9.0, relay_appends=1)]}
    assert _read("plan_grep_stage_s", obs) == pytest.approx(2.5)
    assert _read("plan_wc_stage_s", obs) == pytest.approx(7.5)
    assert _read("relay_append_ms", obs) == pytest.approx(1.5)  # 1, 2, 1.5
    assert _read("plan_tail_s", obs) == pytest.approx(0.8)  # 1.0, 0.5, 0.8


def test_handoff_is_the_traced_jobs_count():
    job = _job(10.0, plan_handoff_bytes=13_100_000)
    assert _read("plan_handoff_MB", {"jobs": [job]}) is None  # no trace
    assert _read("plan_handoff_MB", {"jobs": [job], "traced_job": job}) \
        == pytest.approx(13.1)


def test_trace_readers_take_their_own_kernel_block():
    """Three kernels in one trace: each reader finds its module by its own
    block of the configuration, whatever the mix's ``kernel`` says."""
    obs = {"config": _config(), "traffic": {"kernel": "emit_step"},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"modules": {
               "jit_grep_stream_step(1)": {"runs": 10, "seconds": 0.03},
               "jit_relay_pack(2)": {"runs": 8, "seconds": 0.0004},
               "jit__mapreduce_step_impl(3)": {"runs": 2, "seconds": 0.4},
           }}}
    assert _read("step_kernel_ms_per_MiB", obs) == pytest.approx(3.0)
    assert _read("relay_pack_ms_per_MiB", obs) == pytest.approx(0.05)
    assert _read("plan_wc_step_ms_per_MiB", obs) == pytest.approx(200.0)
    # 3 MiB + 4 B at 819 GB/s = 3.841 us of the 50 us a run
    assert _read("relay_pack_roofline", obs) == pytest.approx(
        100 * (3 * 1048576 + 4) / 819e9 / 50e-6)
    # 2 MiB + 388 B = 2.561 us of the 3 ms a run
    assert _read("stream_step_roofline", obs) == pytest.approx(
        100 * (2 * 1048576 + 388) / 819e9 / 3e-3)
    del obs["trace"]["modules"]["jit_relay_pack(2)"]
    assert _read("relay_pack_ms_per_MiB", obs) is None
    assert _read("relay_pack_roofline", obs) is None


def test_on_what_the_chip_recorded():
    with open(DATA) as f:
        rec = json.load(f)
    obs = dict(rec["obs"], config=_config(),
               traffic={"kernel": "emit_step"},
               peaks={"hbm_bytes_per_s": 819e9})
    for name, want in rec["expected"].items():
        assert _read(name, obs) == pytest.approx(want), name
    assert set(NEW) <= set(rec["expected"])
    job = obs["jobs"][0]
    ps = job["pipeline_stats"]
    plan, grep, wc = ps["plan"], ps["stages"]["grep"], ps["stages"]["wc"]
    # every step of both stages on the device, the handoff never off it
    assert grep["steps"] * 1048576 >= grep["bytes_in"] > 500e6
    assert plan["plan_handoff"] == "device"
    assert plan["plan_intermediate_bytes"] == plan["plan_spilled_bytes"] == 0
    assert wc["steps"] == plan["plan_relay_buffers"] == plan["relay_seals"]
    assert wc["bytes_in"] == plan["plan_handoff_bytes"]
    assert plan["relay_appends"] == grep["steps"]
    # about one line in a hundred passes, about 2.4 % of the bytes
    assert 0.02 < plan["plan_handoff_bytes"] / grep["bytes_in"] < 0.03
    # the stages are most of the job, the commit and the rest a tail
    walls = sorted(j["t_end"] - j["t_start"] for j in obs["jobs"])
    assert _read("plan_grep_stage_s", obs) + _read("plan_wc_stage_s", obs) \
        > 0.9 * walls[len(walls) // 2]
    assert 0.0 < _read("relay_pack_roofline", obs) < 100.0
    assert 0.0 < _read("stream_step_roofline", obs) < 100.0


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_reports_no_such_line_or_key(name):
    """The parent's ``planrun`` prints no ``pipeline_stats``; an older
    stream command prints a flat one; a plan scope may lack a key."""
    with open(DATA) as f:
        rec = json.load(f)
    obs = dict(copy.deepcopy(rec["obs"]), config=_config(),
               traffic={"kernel": "emit_step"})
    obs.pop("trace", None)
    obs.pop("traced_job", None)
    for job in obs["jobs"]:
        job["pipeline_stats"]["plan"] = {"plan_stages": 2}
    assert _read(name, obs) is None
    for job in obs["jobs"]:
        job["pipeline_stats"] = {"steps": 513, "upload_s": 0.3}
    assert _read(name, obs) is None
    for job in obs["jobs"]:
        job["pipeline_stats"] = None
    assert _read(name, obs) is None
    assert _read(name, {"jobs": [], "config": _config(),
                        "traffic": {}}) is None
