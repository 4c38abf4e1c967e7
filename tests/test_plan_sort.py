"""``planrun --chain sort``: files of 100-byte records ordered by their
10-byte key on the device, range-partitioned from a key sample, committed
as totally ordered ``mr-out-<r>``.

The committed partitions must equal, byte for byte and partition by
partition, what ``benchmarks/reference_sort.py`` gives (plain Python over
the same files, its own sampler and ``bisect``), over the inputs a sort
gets wrong: keys that repeat (ties keep input order), keys that are all
equal, an input already in order and one in reverse, key bytes of 0x80
and over, a sample smaller than the partitions' number, a chunk that is
cut across a file boundary, a last chunk that is short, no record at
all.  The kernels are held to ``int.from_bytes`` and ``bisect``.
"""

import ast
import bisect
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

import gensort  # noqa: E402
import reference_sort  # noqa: E402

from dsi_tpu.cli import planrun as cli  # noqa: E402
from dsi_tpu.obs import registry  # noqa: E402
from dsi_tpu.ops import sortk  # noqa: E402
from dsi_tpu.parallel import sortstream  # noqa: E402
from dsi_tpu.parallel.shuffle import default_mesh  # noqa: E402
from dsi_tpu.plan import PlanHostPath, STAGE_KINDS, run_plan, sort_plan  # noqa: E402

CHUNK = 4096  # 40 records a step: every file is cut many times


def _write(directory, blobs):
    os.makedirs(directory, exist_ok=True)
    paths = []
    for i, blob in enumerate(blobs):
        paths.append(os.path.join(directory, f"r{i:03d}.dat"))
        with open(paths[-1], "wb") as f:
            f.write(bytes(blob))
    return paths


def _generated(seed, counts):
    """Files of ``gensort`` records, numbered on from file to file."""
    blobs, first = [], 0
    for i, n in enumerate(counts):
        rng = np.random.default_rng([seed, i])
        blobs.append(gensort.records(n, first, rng).tobytes())
        first += n
    return blobs


def _run(paths, workdir, *flags, n_reduce=10, chunk=CHUNK, devices=1):
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        try:
            rc = cli.main(["--chain", "sort", "--devices", str(devices),
                           "--nreduce", str(n_reduce), "--chunk-bytes",
                           str(chunk), "--stats", "--workdir", workdir,
                           *flags, *paths])
        except SystemExit as e:   # argparse
            rc = e.code
    text = err.getvalue()
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", text, re.M)
    return rc, text, ast.literal_eval(m.group(1)) if m else None


def _committed(workdir, n_reduce=10):
    out = []
    for r in range(n_reduce):
        with open(os.path.join(workdir, f"mr-out-{r}"), "rb") as f:
            out.append(f.read())
    return out


def _keys(blob):
    return [blob[i:i + 10] for i in range(0, len(blob), 100)]


@pytest.fixture(scope="module")
def three_files(tmp_path_factory):
    """3 files of a few thousand generated records; none a whole number of
    steps, so chunks are cut across both file boundaries."""
    directory = str(tmp_path_factory.mktemp("records"))
    return _write(directory, _generated(7, (2003, 1517, 2750)))


@pytest.mark.parametrize("n_reduce", [1, 4, 10])
def test_committed_partitions_equal_the_reference(three_files, tmp_path,
                                                  n_reduce):
    rc, text, ps = _run(three_files, str(tmp_path), n_reduce=n_reduce)
    assert rc == 0, text[-2000:]
    want = reference_sort.partitions(three_files, n_reduce)
    got = _committed(str(tmp_path), n_reduce)
    assert [len(g) for g in got] == [len(w) for w in want]
    assert got == want
    assert sorted(os.listdir(str(tmp_path))) == sorted(
        f"mr-out-{r}" for r in range(n_reduce))
    sort = ps["stages"]["sort"]
    assert sort["sort_records"] == 6270
    assert sort["sort_partition_rows"] == [len(w) // 100 for w in want]
    assert sort["steps"] == -(-6270 // 40)


def test_total_order_within_and_across_partitions(three_files, tmp_path):
    rc, _, _ = _run(three_files, str(tmp_path))
    assert rc == 0
    parts = _committed(str(tmp_path))
    keys = _keys(b"".join(parts))
    assert len(keys) == 6270 and keys == sorted(keys)
    filled = [p for p in parts if p]
    for before, after in zip(filled, filled[1:]):
        assert _keys(before)[-1] <= _keys(after)[0]
    # every record of the input, exactly once
    whole = b"".join(open(p, "rb").read() for p in three_files)
    records = [whole[i:i + 100] for i in range(0, len(whole), 100)]
    joined = b"".join(parts)
    assert sorted(records) == sorted(
        joined[i:i + 100] for i in range(0, len(joined), 100))


def test_duplicate_keys_keep_input_order(tmp_path):
    """Keys drawn from 50 values: a tie is broken by file order, then
    offset, which for generated records is the record number."""
    records = gensort.records(4000, 0, np.random.default_rng(11),
                              distinct_keys=50)
    paths = _write(str(tmp_path / "in"),
                   [records[:1800].tobytes(), records[1800:2700].tobytes(),
                    records[2700:].tobytes()])
    rc, _, _ = _run(paths, str(tmp_path / "wd"), n_reduce=4)
    assert rc == 0
    got = _committed(str(tmp_path / "wd"), 4)
    assert got == reference_sort.partitions(paths, 4)
    joined = b"".join(got)
    rows = [(joined[i:i + 10], int(joined[i + 12:i + 44], 16))
            for i in range(0, len(joined), 100)]
    assert len({key for key, _ in rows}) == 50
    assert rows == sorted(rows)


def _arranged(kind):
    records = gensort.records(3000, 0, np.random.default_rng(5))
    if kind == "all_equal":
        records[:, :10] = np.frombuffer(b"samesame!!", np.uint8)
        return records
    order = np.lexsort(tuple(records[:, j] for j in reversed(range(10))))
    return records[order if kind == "sorted" else order[::-1]]


@pytest.mark.parametrize("kind", ["all_equal", "sorted", "reversed"])
def test_degenerate_inputs(tmp_path, kind):
    records = _arranged(kind)
    paths = _write(str(tmp_path / "in"),
                   [records[:1100].tobytes(), records[1100:].tobytes()])
    rc, _, ps = _run(paths, str(tmp_path / "wd"))
    assert rc == 0
    got = _committed(str(tmp_path / "wd"))
    assert got == reference_sort.partitions(paths, 10)
    if kind == "all_equal":
        # every split point is the one key: all records in the last
        # partition, in input order
        assert got[-1] == records.tobytes() and not any(got[:-1])
        assert ps["stages"]["sort"]["sort_partition_rows"][-1] == 3000
    if kind == "sorted":
        assert b"".join(got) == records.tobytes()


def test_key_bytes_of_0x80_and_over_compare_unsigned(tmp_path):
    rng = np.random.default_rng(23)
    records = rng.integers(0, 256, (2500, 100), dtype=np.uint8)
    records[:600, :10] |= 0x80
    records[600:700, :9] = 0xFF      # differ in the last key byte alone
    records[700:720, :10] = 0        # the least key there is
    records[720:740, :10] = 0xFF     # and the greatest
    paths = _write(str(tmp_path / "in"), [records.tobytes()])
    rc, _, _ = _run(paths, str(tmp_path / "wd"), n_reduce=7)
    assert rc == 0
    got = _committed(str(tmp_path / "wd"), 7)
    assert got == reference_sort.partitions(paths, 7)
    keys = _keys(b"".join(got))
    assert keys == sorted(keys)


def test_a_sample_smaller_than_the_partitions(three_files, tmp_path):
    rc, _, ps = _run(three_files, str(tmp_path), "--sort-sample", "3")
    assert rc == 0
    assert ps["stages"]["sample"]["sort_sample_keys"] == 3
    got = _committed(str(tmp_path))
    assert got == reference_sort.partitions(three_files, 10, sample=3)
    # 3 keys give at most 4 partitions anything
    assert sum(1 for g in got if g) <= 4
    keys = _keys(b"".join(got))
    assert len(keys) == 6270 and keys == sorted(keys)


def test_a_file_of_150_bytes_is_refused(three_files, tmp_path):
    bad = _write(str(tmp_path / "in"), [b"x" * 150])
    rc, text, ps = _run(three_files + bad, str(tmp_path / "wd"))
    assert rc == 1 and ps is None
    assert "not a whole number of 100-byte records" in text
    assert not os.path.exists(str(tmp_path / "wd"))


def test_no_record_at_all_commits_empty_partitions(tmp_path):
    paths = _write(str(tmp_path / "in"), [b"", b""])
    rc, _, ps = _run(paths, str(tmp_path / "wd"), n_reduce=3)
    assert rc == 0
    assert _committed(str(tmp_path / "wd"), 3) == [b"", b"", b""]
    assert ps["stages"]["sort"]["sort_records"] == 0


def test_split_points_are_a_function_of_the_input_alone(three_files,
                                                        tmp_path):
    first = sortstream.sample_splits(three_files, 10).partitions
    again = sortstream.sample_splits(three_files, 10).partitions
    assert first.dtype == np.uint32 and first.shape == (9, 3)
    assert first.tobytes() == again.tobytes()
    # as the reference's sampler has them
    whole = reference_sort.read_records(three_files)
    want = reference_sort.split_points(whole, 10)
    assert [bytes(row.astype(">u4").tobytes()[:10]) for row in first] == want
    # and two runs commit the same bytes and sign the same plan
    mesh = default_mesh(1)
    sigs, outs = [], []
    for name in ("a", "b"):
        plan = sort_plan(three_files, chunk_bytes=CHUNK, n_reduce=10)
        res = run_plan(plan, mesh=mesh)
        os.makedirs(str(tmp_path / name))
        sortstream.write_sorted_output(res.final, str(tmp_path / name))
        sigs.append(plan.signature())
        outs.append(_committed(str(tmp_path / name)))
    assert outs[0] == outs[1]
    assert sigs[0] == sigs[1]
    import zlib
    assert sigs[0]["stages"][1]["splits"] == {
        "bytes": 108, "crc32": zlib.crc32(first.tobytes())}


def test_key_lanes_against_int_from_bytes():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 256, (512, 100), dtype=np.uint8)
    rows[:256, :10] |= 0x80
    words = jax.numpy.asarray(rows.view("<u4"))
    lanes = [np.asarray(lane) for lane in sortk.key_lanes(words)]
    host = sortstream.host_lanes(rows[:, :10])
    for i, row in enumerate(rows):
        key = bytes(row[:10])
        want = (int.from_bytes(key[0:4], "big"),
                int.from_bytes(key[4:8], "big"),
                int.from_bytes(key[8:10] + b"\0\0", "big"))
        assert tuple(int(lane[i]) for lane in lanes) == want
        assert tuple(int(x) for x in host[i]) == want
    # the lanes' order is the keys' order
    order = np.lexsort((lanes[2], lanes[1], lanes[0]))
    keys = [bytes(r[:10]) for r in rows]
    assert [keys[i] for i in order] == sorted(keys)


def test_partition_kernel_against_bisect_on_the_boundary_keys():
    rng = np.random.default_rng(9)
    bounds = sorted(bytes(k) for k in rng.integers(0, 256, (9, 10),
                                                   dtype=np.uint8))
    bounds[4] = bounds[3]            # a repeated split point
    bounds.sort()

    def nudge(key, by):
        n = int.from_bytes(key, "big") + by
        return min(max(n, 0), (1 << 80) - 1).to_bytes(10, "big")

    keys = list(bounds) + [nudge(b, -1) for b in bounds] \
        + [nudge(b, 1) for b in bounds] + [b"\0" * 10, b"\xff" * 10] \
        + [bytes(k) for k in rng.integers(0, 256, (200, 10), dtype=np.uint8)]
    as_rows = np.zeros((len(keys), 10), np.uint8)
    for i, key in enumerate(keys):
        as_rows[i] = np.frombuffer(key, np.uint8)
    lanes = sortstream.host_lanes(as_rows)
    splits = sortstream.host_lanes(np.frombuffer(
        b"".join(bounds), np.uint8).reshape(9, 10))
    got = np.asarray(sortk.partition_of(
        tuple(jax.numpy.asarray(lanes[:, j]) for j in range(3)),
        jax.numpy.asarray(splits)))
    assert got.tolist() == [bisect.bisect_right(bounds, k) for k in keys]


def test_there_is_no_host_path(three_files, tmp_path):
    """``--staged`` would materialize on the host: the job fails and
    commits nothing.  A mesh of more than one device is no host path: it
    sorts (``tests/test_plan_sort_mesh.py``)."""
    rc, text, ps = _run(three_files, str(tmp_path / "wd"), "--staged")
    assert rc == 1 and ps is None
    assert "needs the host path" in text
    assert not os.path.exists(str(tmp_path / "wd"))
    with pytest.raises(PlanHostPath):
        run_plan(sort_plan(three_files, chunk_bytes=CHUNK),
                 mesh=default_mesh(2), staged=True)
    res = run_plan(sort_plan(three_files, chunk_bytes=CHUNK),
                   mesh=default_mesh(2))
    assert res.final.records == 6270 and len(res.final.stores) == 2


@pytest.mark.parametrize("flag", [("--check",), ("--hosts",),
                                  ("--checkpoint-dir", "ck"),
                                  ("--pipeline",), ("--stage-shards", "2")])
def test_flags_that_are_not_this_chains_are_refused(three_files, tmp_path,
                                                    flag):
    rc, text, ps = _run(three_files, str(tmp_path / "wd"), *flag)
    assert rc == 2 and ps is None, text[-500:]
    assert not os.path.exists(str(tmp_path / "wd"))


def test_the_records_stay_on_the_device_and_the_stats_say_so(three_files,
                                                             tmp_path):
    rc, text, ps = _run(three_files, str(tmp_path))
    assert rc == 0
    plan, sort, sample = ps["plan"], ps["stages"]["sort"], \
        ps["stages"]["sample"]
    assert plan["plan_handoff"] == "device"
    assert plan["plan_intermediate_bytes"] == 0
    assert plan.get("plan_spilled_bytes", 0) == 0
    assert list(plan["plan_stage_walls"]) == ["sample", "sort"]
    assert sample["sort_sample_keys"] == 6270   # fewer than 100,000
    assert sort["sort_resident_bytes"] >= 627000
    assert sort["sort_order_passes"] == sortk.ORDER_PASSES == 3
    assert sum(sort["sort_partition_rows"]) == sort["sort_records"] == 6270
    assert sort["bytes_in"] == 627000 and sort["depth"] == 2
    assert ps["pull_bytes"] >= 627000
    for key in ("sample_s", "order_s", "sort_records", "sort_sample_keys",
                "sort_resident_bytes", "sort_partition_rows",
                "sort_order_passes"):
        assert key in registry.SCHEMA_KEYS, key
    for key in ("order_s", "upload_s", "kernel_s", "batch_s",
                "dispatch_s", "retire_s"):
        assert key in sort, key
    for key in ("write_s", "write_commit_s", "pull_s", "d2h_s"):
        assert key in ps, key
    assert "records in key order" in text


def test_eight_stage_kinds_and_the_plans_shape(three_files):
    assert STAGE_KINDS[6:8] == ("sample", "range_sort")
    plan = sort_plan(three_files, sample=500, n_reduce=4)
    sample, sort = plan.ordered()
    assert (sample.kind, sort.kind, sort.deps) == ("sample", "range_sort",
                                                   ("sample",))
    assert plan.param(sample, "sample") == 500


def test_a_step_deeper_or_shallower_commits_the_same(three_files, tmp_path):
    """Pipeline depth 1 (no reader thread) and 3, and a chunk of one
    record: the same bytes."""
    want = reference_sort.partitions(three_files, 10)
    for name, flags, chunk in (("d1", ("--pipeline-depth", "1"), CHUNK),
                               ("d3", ("--pipeline-depth", "3"), 8192),
                               ("big", (), 1 << 20)):
        rc, _, _ = _run(three_files, str(tmp_path / name), *flags,
                        chunk=chunk)
        assert rc == 0
        assert _committed(str(tmp_path / name)) == want
