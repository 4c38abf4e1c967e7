"""``planrun --chain agg``: ``SELECT f0, SUM(f3) ... GROUP BY f0`` over
files of ``|``-delimited rows, read and grouped on the device by the word
count's engine with ``ops/fieldsum.FieldSum`` as its map, committed as
``mr-out-<r>``.

The committed partitions must equal, byte for byte and partition by
partition, what ``benchmarks/reference_agg.py`` gives (plain Python over
the same files: ``split``, integer arithmetic, a ``dict``), for both of
the task's queries, at both pipeline depths, on one device and on several,
through the table's widening and the row buffer's; a sum that passes 2^32
inside a step and 2^40 over a job comes out exact; every kind of bad row
fails the job with its file and line and commits nothing.
"""

import ast
import contextlib
import io
import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_agg  # noqa: E402
import uservisits  # noqa: E402

from dsi_tpu.cli import planrun as cli  # noqa: E402
from dsi_tpu.obs import registry  # noqa: E402
from dsi_tpu.ops.fieldsum import BadRow, FieldSum  # noqa: E402
from dsi_tpu.parallel.shuffle import default_mesh  # noqa: E402
from dsi_tpu.parallel.streaming import WordcountStep, stream_rows  # noqa: E402
from dsi_tpu.plan import (PlanHostPath, STAGE_KINDS, agg_plan,  # noqa: E402
                          run_plan)

CHUNK = 8192  # ~60 rows a step: every file is cut many times


def _write(directory, blobs):
    os.makedirs(directory, exist_ok=True)
    paths = []
    for i, blob in enumerate(blobs):
        paths.append(os.path.join(directory, f"v{i:03d}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(bytes(blob))
    return paths


def _visits(seed, counts, pool=400):
    """Files of ``UserVisits`` rows over a pool small enough for keys to
    repeat, within a step and across steps."""
    return [uservisits.rows(n, np.random.default_rng([seed, i]),
                            pool=pool).tobytes()
            for i, n in enumerate(counts)]


def _planrun(paths, workdir, *flags):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        try:
            rc = cli.main(["--chain", "agg", "--nreduce", "10", "--stats",
                           "--chunk-bytes", str(CHUNK), "--workdir", workdir,
                           *flags, *paths])
        except SystemExit as e:
            rc = e.code
    text = err.getvalue()
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", text, re.M)
    return rc, (ast.literal_eval(m.group(1)) if m else None), text


def _committed(workdir, n_reduce=10):
    return [open(os.path.join(workdir, f"mr-out-{r}"), "rb").read()
            for r in range(n_reduce)]


def _no_output(workdir):
    return not os.path.isdir(workdir) or not [
        name for name in os.listdir(workdir) if name.startswith("mr-out")]


@pytest.mark.parametrize("prefix", [0, 7])
@pytest.mark.parametrize("depth", [1, 2])
def test_both_queries_byte_equal_to_the_reference(tmp_path, depth, prefix):
    paths = _write(tmp_path / "in", _visits(1, [700, 40, 1300]))
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", "1", "--pipeline-depth",
                            str(depth), "--agg-prefix", str(prefix))
    assert rc == 0, text
    want = reference_agg.partitions(paths, 10, prefix)
    assert _committed(wd) == want
    agg = ps["stages"]["agg"]
    assert agg["depth"] == depth and agg["agg_value_lanes"] == 2
    assert agg["agg_rows"] == 2040
    assert agg["agg_groups"] == sum(p.count(b"\n") for p in want)
    assert agg["steps"] * CHUNK >= sum(map(os.path.getsize, paths))
    assert ps["write_rows_packed"] == agg["agg_groups"]
    assert ps["write_rows_dict"] == 0
    assert agg["finalize_decoded_keys"] == 0
    assert agg["merge_runs_unsorted"] == 0  # a step's table is a run
    assert ps["plan"]["plan_handoff"] == "device"
    assert "needs the host path" not in text
    if prefix:
        assert max(len(line.split(b" ")[0]) for p in want
                   for line in p.splitlines()) == 7


@pytest.mark.parametrize("devices", [2, 4])
def test_several_devices_commit_the_same_bytes(tmp_path, devices):
    paths = _write(tmp_path / "in", _visits(2, [900, 500]))
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", str(devices))
    assert rc == 0, text
    assert _committed(wd) == reference_agg.partitions(paths, 10)
    rows = ps["stages"]["agg"]["device_rows"]
    assert len(rows) == devices and min(rows) > 0


def test_sums_past_2_32_in_a_step_and_2_40_in_a_job(tmp_path):
    hot = b"10.0.0.1|u|2000-01-01|999.999999|a|b|c|d|1\n"
    rng = np.random.default_rng(4)
    blobs = []
    for i in range(3):
        cold = uservisits.rows(300, np.random.default_rng([4, i]),
                               pool=50).tobytes().splitlines(keepends=True)
        rows = cold + [hot] * 400
        blobs.append(b"".join(rows[j] for j in rng.permutation(len(rows))))
    paths = _write(tmp_path / "in", blobs)
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", "1")
    assert rc == 0, text
    total = reference_agg.sums(paths)[b"10.0.0.1"]
    assert total > 1 << 40 and total // 20 > 1 << 32  # ~20 steps hold it
    assert _committed(wd) == reference_agg.partitions(paths, 10)
    line = (f"10.0.0.1 {total // 10 ** 6}.{total % 10 ** 6:06d}\n").encode()
    assert line in b"".join(_committed(wd))


def test_the_table_and_the_row_buffer_widen(tmp_path):
    # 1,000 short rows a step of 8 KiB: the row buffer of n/64 + 1 rows
    # overflows (a replay at n/4 + 1), and so does a table of 64 keys
    rows = [b"k%05d|||%d.%d\n" % (i % 3000, i % 1000, i % 10)
            for i in range(9000)]
    paths = _write(tmp_path / "in", [b"".join(rows[:5000]),
                                     b"".join(rows[5000:])])
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", "1", "--u-cap", "64")
    assert rc == 0, text
    assert _committed(wd) == reference_agg.partitions(paths, 10)
    agg = ps["stages"]["agg"]
    assert agg["replays"] >= 1 and agg["agg_rows"] == 9000
    assert agg["agg_groups"] == 3000


def test_a_last_row_without_a_newline(tmp_path):
    a, b = _visits(3, [90, 70])
    paths = _write(tmp_path / "in", [a[:-1], b[:-1], b""])
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", "1")
    assert rc == 0, text
    assert ps["stages"]["agg"]["agg_rows"] == 160
    assert _committed(wd) == reference_agg.partitions(paths, 10)
    assert b"".join(stream_rows(paths)) == a + b


BAD_ROWS = {
    "three fields": b"10.0.0.9|u|d",
    "empty row": b"",
    "empty key": b"|u|d|1.5|a",
    "key of 17 bytes": b"12345678901234567|u|d|1.5|a",
    "key with a high byte": b"10.0.\xc3\xa9|u|d|1.5|a",
    "four integer digits": b"10.0.0.9|u|d|1234|a",
    "seven fraction digits": b"10.0.0.9|u|d|1.1234567|a",
    "no fraction digit": b"10.0.0.9|u|d|1.|a",
    "a float's exponent": b"10.0.0.9|u|d|1e3|a",
    "a negative value": b"10.0.0.9|u|d|-1.5|a",
}


@pytest.mark.parametrize("kind", sorted(BAD_ROWS))
@pytest.mark.parametrize("depth", [1, 2])
def test_a_bad_row_fails_the_job_and_commits_nothing(tmp_path, kind, depth):
    good = _visits(5, [300, 300])
    rows = good[1].splitlines(keepends=True)
    rows[211] = BAD_ROWS[kind] + b"\n"
    paths = _write(tmp_path / "in", [good[0], b"".join(rows)])
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, "--devices", "1",
                            "--pipeline-depth", str(depth))
    assert rc == 1 and ps is None
    assert _no_output(wd)
    assert f"{paths[1]}:212: bad row" in text, text
    with pytest.raises(ValueError, match=r"v001\.txt:212"):
        reference_agg.sums(paths)


def test_a_bad_row_on_another_device_is_named_too(tmp_path):
    good = _visits(6, [500])
    rows = good[0].splitlines(keepends=True)
    rows[333] = b"10.0.0.9|u|d|x|a\n"
    paths = _write(tmp_path / "in", [b"".join(rows)])
    wd = str(tmp_path / "wd")
    rc, _, text = _planrun(paths, wd, "--devices", "4")
    assert rc == 1 and _no_output(wd)
    assert f"{paths[0]}:334: bad row" in text, text


def test_a_row_no_chunk_can_hold_fails_the_job(tmp_path):
    paths = _write(tmp_path / "in",
                   [b"k|u|d|1.5|" + b"x" * (2 * CHUNK) + b"\n"])
    wd = str(tmp_path / "wd")
    rc, _, text = _planrun(paths, wd, "--devices", "1")
    assert rc == 1 and _no_output(wd)
    assert "longer than a chunk" in text


@pytest.mark.parametrize("flags", [
    ("--staged",), ("--check",), ("--hosts",),
    ("--checkpoint-dir", "ck"), ("--pipeline",), ("--stage-shards", "2"),
    ("--device-accumulate",), ("--mesh-shards", "2"), ("--aot",),
    ("--agg-prefix", "-1")])
def test_flags_that_are_not_the_chains_are_refused(tmp_path, flags):
    paths = _write(tmp_path / "in", _visits(7, [20]))
    wd = str(tmp_path / "wd")
    rc, ps, text = _planrun(paths, wd, *flags)
    assert rc == 2 and ps is None and _no_output(wd)
    assert "--chain agg" in text or "--agg-prefix" in text


def test_the_prefix_is_the_aggregations_flag(tmp_path):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), pytest.raises(SystemExit):
        cli.main(["--chain", "wc-topk", "--agg-prefix", "7", "x.txt"])
    assert "--agg-prefix cuts the key of --chain agg" in err.getvalue()


def test_no_host_path_commits_an_aggregation(tmp_path):
    paths = _write(tmp_path / "in", _visits(8, [50]))
    with pytest.raises(PlanHostPath, match="host path"):
        run_plan(agg_plan(paths, chunk_bytes=CHUNK), mesh=default_mesh(1),
                 staged=True)


@pytest.mark.parametrize("option", [
    {"aot": True}, {"device_accumulate": True}, {"mesh_shards": 2},
    {"checkpoint_dir": "ck"}, {"wire_upload": True},
    {"device_batches": []}])
def test_the_engine_refuses_a_map_beside_what_it_cannot_carry(option):
    with pytest.raises(ValueError, match="host-merge path alone"):
        WordcountStep([], mesh=default_mesh(1), map=FieldSum(), **option)


def test_the_stage_the_plan_and_the_counters_are_registered():
    assert STAGE_KINDS[8] == "aggregate"
    plan = agg_plan(["a", "b"], prefix=7, chunk_bytes=CHUNK)
    (stage,) = plan.ordered()
    assert (stage.name, stage.kind, stage.deps) == ("agg", "aggregate", ())
    assert plan.signature()["stages"][0]["prefix"] == 7
    for key in ("agg_rows", "agg_groups", "agg_value_lanes"):
        assert key in registry.SCHEMA_KEYS and key in registry.COUNTER_KEYS


def test_a_bad_row_is_named_by_file_and_line(tmp_path):
    paths = _write(tmp_path / "in", [b"a\nb\n", b"", b"c\nd", b"e\n"])
    assert str(BadRow("x", 0).at(paths)).startswith(f"{paths[0]}:1: x")
    assert str(BadRow("x", 3).at(paths)).startswith(f"{paths[2]}:2: x")
    assert str(BadRow("x", 4).at(paths)).startswith(f"{paths[3]}:1: x")
    assert str(BadRow("x", 9).at(paths)) == "x"
