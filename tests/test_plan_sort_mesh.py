"""``planrun --chain sort --devices N``: the sort across a mesh.  Every
record goes to the device that owns its key range through the mesh's
``all_to_all``, is ordered there and pulled from there, device 0's records
first.

On the CPU's virtual devices, seeded, against the sequential reference
(``benchmarks/reference_sort.py``) and against ``--devices 1``: the
committed bytes are the same, file for file, whatever the devices' number,
over the inputs ``tests/test_plan_sort.py`` holds one device to (ties, one
key, order and reverse order, key bytes of 0x80 and over, ten bytes of
0xFF, a tiny sample, no record) and the mesh's own: fewer records than
devices, a device's share by its committed range, the count of records
that crossed the mesh reckoned by hand, a key range the sample did not
foresee.
"""

import bisect
import glob
import gzip
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from test_plan_sort import (CHUNK, _arranged, _committed, _generated,  # noqa: E402
                            _keys, _run, _write)

import gensort  # noqa: E402  (test_plan_sort put benchmarks/ on sys.path)
import reference_sort  # noqa: E402

from dsi_tpu.obs import registry  # noqa: E402
from dsi_tpu.ops import sortk  # noqa: E402
from dsi_tpu.parallel import sortstream  # noqa: E402
from dsi_tpu.parallel.shuffle import default_mesh  # noqa: E402
from dsi_tpu.plan import run_plan, sort_plan  # noqa: E402

PER_STEP = CHUNK // 100  # records a device a step


def _mesh(paths, workdir, devices, *flags, **kw):
    return _run(paths, workdir, *flags, devices=devices, **kw)


def _key_bytes(lanes):
    """``uint32[n, 3]`` split points as the keys' ten bytes."""
    return [row.astype(">u4").tobytes()[:10] for row in lanes]


def _records(paths):
    whole = b"".join(open(p, "rb").read() for p in paths)
    return [whole[i:i + 100] for i in range(0, len(whole), 100)]


@pytest.fixture(scope="module")
def three_files(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("records"))
    return _write(directory, _generated(7, (2003, 1517, 2750)))


@pytest.mark.parametrize("n_reduce", [1, 4, 10])
@pytest.mark.parametrize("devices", [2, 4])
def test_committed_partitions_equal_the_reference(three_files, tmp_path,
                                                  devices, n_reduce):
    rc, text, ps = _mesh(three_files, str(tmp_path), devices,
                         n_reduce=n_reduce)
    assert rc == 0, text[-2000:]
    want = reference_sort.partitions(three_files, n_reduce)
    assert _committed(str(tmp_path), n_reduce) == want
    assert sorted(os.listdir(str(tmp_path))) == sorted(
        f"mr-out-{r}" for r in range(n_reduce))
    sort = ps["stages"]["sort"]
    assert sort["sort_records"] == 6270 and sort["sort_devices"] == devices
    assert sort["sort_partition_rows"] == [len(w) // 100 for w in want]
    assert sort["steps"] == -(-6270 // (PER_STEP * devices))


@pytest.mark.parametrize("devices", [2, 4])
def test_the_committed_bytes_are_one_devices_file_for_file(three_files,
                                                           tmp_path, devices):
    assert _run(three_files, str(tmp_path / "one"))[0] == 0
    assert _mesh(three_files, str(tmp_path / "mesh"), devices)[0] == 0
    for r in range(10):
        with open(str(tmp_path / "one" / f"mr-out-{r}"), "rb") as a, \
                open(str(tmp_path / "mesh" / f"mr-out-{r}"), "rb") as b:
            assert a.read() == b.read(), r


def test_total_order_within_and_across_partitions(three_files, tmp_path):
    rc, _, _ = _mesh(three_files, str(tmp_path), 4)
    assert rc == 0
    parts = _committed(str(tmp_path))
    keys = _keys(b"".join(parts))
    assert len(keys) == 6270 and keys == sorted(keys)
    filled = [p for p in parts if p]
    for before, after in zip(filled, filled[1:]):
        assert _keys(before)[-1] <= _keys(after)[0]
    joined = b"".join(parts)
    assert sorted(_records(three_files)) == sorted(
        joined[i:i + 100] for i in range(0, len(joined), 100))


@pytest.mark.parametrize("devices", [2, 4])
def test_duplicate_keys_keep_input_order_across_devices_and_steps(
        tmp_path, devices):
    """50 keys over 4,000 records in three files and 25 or 13 steps: every
    key's records reach their owner from every device and many steps, and
    still stand in input order (the record number)."""
    records = gensort.records(4000, 0, np.random.default_rng(11),
                              distinct_keys=50)
    paths = _write(str(tmp_path / "in"),
                   [records[:1800].tobytes(), records[1800:2700].tobytes(),
                    records[2700:].tobytes()])
    rc, text, _ = _mesh(paths, str(tmp_path / "wd"), devices, n_reduce=4)
    assert rc == 0, text[-2000:]
    got = _committed(str(tmp_path / "wd"), 4)
    assert got == reference_sort.partitions(paths, 4)
    joined = b"".join(got)
    rows = [(joined[i:i + 10], int(joined[i + 12:i + 44], 16))
            for i in range(0, len(joined), 100)]
    assert len({key for key, _ in rows}) == 50
    assert rows == sorted(rows)


@pytest.mark.parametrize("kind", ["all_equal", "sorted", "reversed"])
def test_degenerate_inputs(tmp_path, kind):
    """One key for every record sorts right: the sample says that the
    last device owns them all (a device owns a split point's key from the
    first of its equals), and its store is sized for that."""
    records = _arranged(kind)
    paths = _write(str(tmp_path / "in"),
                   [records[:1100].tobytes(), records[1100:].tobytes()])
    rc, text, ps = _mesh(paths, str(tmp_path / "wd"), 4)
    assert rc == 0, text[-2000:]
    got = _committed(str(tmp_path / "wd"))
    assert got == reference_sort.partitions(paths, 10)
    sort = ps["stages"]["sort"]
    if kind == "all_equal":
        assert got[-1] == records.tobytes() and not any(got[:-1])
        assert sort["device_rows"] == [0, 0, 0, 3000]
        # three of four chunks were read by another device
        assert sort["sort_exchange_rows"] == 3000 - sum(
            min(PER_STEP, 3000 - c * PER_STEP)
            for c in range(3, -(-3000 // PER_STEP), 4))
    else:
        assert sort["device_rows"] == [750] * 4
    if kind == "sorted":
        assert b"".join(got) == records.tobytes()


def test_key_bytes_of_0x80_and_over_and_ten_bytes_of_0xff(tmp_path):
    """A key of ten 0xFF bytes is a record like any other: the exchange's
    pad rows, whose key lanes are 0xFFFFFFFF, are told from it by the low
    half of the third lane and not by the key."""
    rng = np.random.default_rng(23)
    records = rng.integers(0, 256, (2500, 100), dtype=np.uint8)
    records[:600, :10] |= 0x80
    records[600:700, :9] = 0xFF      # differ in the last key byte alone
    records[700:720, :10] = 0        # the least key there is
    records[720:900, :10] = 0xFF     # and the greatest, many times
    paths = _write(str(tmp_path / "in"),
                   [records[:1300].tobytes(), records[1300:].tobytes()])
    rc, text, ps = _mesh(paths, str(tmp_path / "wd"), 4, n_reduce=7)
    assert rc == 0, text[-2000:]
    got = _committed(str(tmp_path / "wd"), 7)
    assert got == reference_sort.partitions(paths, 7)
    keys = _keys(b"".join(got))
    assert keys == sorted(keys) and keys[-180:] == [b"\xff" * 10] * 180
    assert sum(ps["stages"]["sort"]["device_rows"]) == 2500


@pytest.mark.parametrize("count", [0, 1, 3])
def test_fewer_records_than_devices_and_no_record_at_all(tmp_path, count):
    records = gensort.records(3, 0, np.random.default_rng(2))[:count]
    paths = _write(str(tmp_path / "in"), [records.tobytes(), b""])
    rc, text, ps = _mesh(paths, str(tmp_path / "wd"), 4, n_reduce=3)
    assert rc == 0, text[-2000:]
    got = _committed(str(tmp_path / "wd"), 3)
    assert got == reference_sort.partitions(paths, 3)
    assert sum(len(g) for g in got) == 100 * count
    sort = ps["stages"]["sort"]
    assert sort["sort_records"] == count
    assert sort["device_rows"] == ([0] * 4 if not count else
                                   sort["device_rows"])
    assert sum(sort["device_rows"]) == count
    assert sort["steps"] == (1 if count else 0)


def test_a_sample_smaller_than_the_devices(three_files, tmp_path):
    """Two sampled keys for four devices: the split points repeat, two
    devices own nothing, and the job sorts right or fails by the store's
    rule, never wrongly.  Here (seeded) the key ranges fit."""
    rc, text, ps = _mesh(three_files, str(tmp_path), 4, "--sort-sample", "2")
    assert rc == 0, text[-2000:]
    assert ps["stages"]["sample"]["sort_sample_keys"] == 2
    got = _committed(str(tmp_path))
    assert got == reference_sort.partitions(three_files, 10, sample=2)
    rows = ps["stages"]["sort"]["device_rows"]
    assert sum(rows) == 6270 and rows.count(0) >= 1
    keys = _keys(b"".join(got))
    assert len(keys) == 6270 and keys == sorted(keys)


def test_the_devices_shares_and_the_records_that_crossed(three_files,
                                                         tmp_path):
    """The share test: the four devices' ``device_rows`` sum to the
    records, each device's committed range lies in its own key range and
    below the next's, and ``sort_exchange_rows`` is the count reckoned
    here from the device split points: a record crossed if its owner is
    not the device that read its chunk (chunk ``c`` of the input's
    sequence is read by device ``c % 4``)."""
    rc, _, ps = _mesh(three_files, str(tmp_path), 4)
    assert rc == 0
    sort = ps["stages"]["sort"]
    rows = sort["device_rows"]
    assert len(rows) == 4 and sum(rows) == 6270 and min(rows) > 0
    # a quarter each by the sample, which here is every key
    assert max(rows) - min(rows) <= 1
    points = sortstream.sample_splits(three_files, 10, n_dev=4)
    bounds = _key_bytes(points.devices)
    assert points.shares == tuple(rows)
    assert sort["sort_device_capacity"] == sortstream.device_capacity(
        6270, points.shares, PER_STEP)
    keys = _keys(b"".join(_committed(str(tmp_path))))
    first = 0
    for d, held in enumerate(rows):
        mine = keys[first:first + held]
        assert all(bisect.bisect_right(bounds, k) == d for k in mine), d
        if first:
            assert keys[first - 1] <= mine[0]
        first += held
    crossed = sum(bisect.bisect_right(bounds, rec[:10]) != (i // PER_STEP) % 4
                  for i, rec in enumerate(_records(three_files)))
    assert sort["sort_exchange_rows"] == crossed
    assert sort["sort_exchange_bytes"] == 100 * crossed
    assert 0.7 * 6270 < crossed < 0.8 * 6270   # three in four


def _skewed(tmp_path):
    """4,000 records: those a sample of 40 reads (every hundredth) carry
    spread keys, the others one key, ``MMMMMMMMMM``."""
    records = gensort.records(4000, 0, np.random.default_rng(31))
    hidden = np.ones(4000, bool)
    hidden[::100] = False
    records[hidden, :10] = np.frombuffer(b"MMMMMMMMMM", np.uint8)
    return _write(str(tmp_path / "in"), [records.tobytes()])


def test_an_overfull_device_fails_the_job_and_commits_nothing(tmp_path):
    """99 records in 100 share a key the sample never saw: its owner's
    range holds far more than the sample's share and a sixteenth.  Exit
    1, the device named and by how much, nothing committed: no record is
    dropped, cut or sent to the host."""
    paths = _skewed(tmp_path)
    rc, text, ps = _mesh(paths, str(tmp_path / "wd"), 4, "--sort-sample",
                         "40")
    assert rc == 1 and ps is None
    assert "outgrew its store" in text and "nothing is committed" in text
    m = re.search(r"planrun: device (\d)'s key range outgrew "
                  r"its store at step (\d+): (\d+) records, "
                  r"(\d+) more than the (\d+) it holds", text)
    assert m, text[-1000:]
    device, _, held, over, holds = (int(g) for g in m.groups())
    bounds = _key_bytes(sortstream.sample_splits(
        paths, 10, 40, n_dev=4).devices)
    assert device == bisect.bisect_right(bounds, b"MMMMMMMMMM")
    assert held - over == holds
    assert holds == sortstream.device_capacity(
        4000, (10, 10, 10, 10), PER_STEP) - PER_STEP
    assert not os.path.exists(str(tmp_path / "wd"))
    assert "Traceback" not in text
    # a sample that saw the key sizes its owner's store for it
    rc, text, ps = _mesh(paths, str(tmp_path / "wd2"), 4)
    assert rc == 0, text[-2000:]
    assert _committed(str(tmp_path / "wd2")) == reference_sort.partitions(
        paths, 10)
    assert max(ps["stages"]["sort"]["device_rows"]) > 3960


def test_there_is_no_host_path_on_a_mesh_either(three_files, tmp_path):
    rc, text, ps = _mesh(three_files, str(tmp_path / "wd"), 4, "--staged")
    assert rc == 1 and ps is None
    assert "needs the host path" in text
    assert not os.path.exists(str(tmp_path / "wd"))


def test_a_step_deeper_or_shallower_commits_the_same(three_files, tmp_path):
    """Pipeline depth 1 (no reader thread) and 3, a chunk that holds the
    whole input on one device and leaves three with nothing to read."""
    want = reference_sort.partitions(three_files, 10)
    for name, flags, chunk in (("d1", ("--pipeline-depth", "1"), CHUNK),
                               ("d3", ("--pipeline-depth", "3"), 8192),
                               ("big", (), 1 << 20)):
        rc, text, ps = _mesh(three_files, str(tmp_path / name), 4, *flags,
                             chunk=chunk)
        assert rc == 0, text[-2000:]
        assert _committed(str(tmp_path / name)) == want
    assert ps["stages"]["sort"]["steps"] == 1


def test_the_records_cross_the_mesh_and_the_stats_say_so(three_files,
                                                         tmp_path):
    rc, text, ps = _mesh(three_files, str(tmp_path), 4)
    assert rc == 0
    plan, sort = ps["plan"], ps["stages"]["sort"]
    assert plan["plan_handoff"] == "device"
    assert plan["plan_intermediate_bytes"] == 0
    assert plan.get("plan_spilled_bytes", 0) == 0
    assert "needs the host path" not in text
    # four stores of the records and their lanes
    assert sort["sort_resident_bytes"] == 4 * sort["sort_device_capacity"] \
        * (100 + 12) >= 627000
    assert ps["pull_bytes"] >= 627000
    new = ("sort_devices", "sort_device_capacity", "sort_exchange_rows",
           "sort_exchange_bytes")
    for key in new:
        assert key in registry.SCHEMA_KEYS and key in sort, key
    # one device's line does not grow
    rc, _, one = _run(three_files, str(tmp_path / "one"))
    assert rc == 0
    assert not set(new) & set(one["stages"]["sort"])
    assert set(sort) - set(new) == set(one["stages"]["sort"])
    assert one["stages"]["sort"]["device_rows"] == [6270]


def test_the_plans_signature_does_not_change_with_the_devices(three_files):
    """The partitions' split points are a function of the input alone;
    the devices' are the layout's and sign nothing."""
    sigs = []
    for n in (1, 4):
        plan = sort_plan(three_files, chunk_bytes=CHUNK, n_reduce=10)
        run_plan(plan, mesh=default_mesh(n))
        sigs.append(plan.signature())
    assert sigs[0] == sigs[1]
    one = sortstream.sample_splits(three_files, 10)
    four = sortstream.sample_splits(three_files, 10, n_dev=4)
    assert one.partitions.tobytes() == four.partitions.tobytes()
    assert one.devices.shape == (0, 3) and one.shares == (6270,)


def test_device_split_points_come_from_the_one_sample(tmp_path):
    """Positions ``d * m // n_dev`` of the sorted sample; a device's share
    counts a split point's equals with it, as the device will."""
    keys = [b"k%09d" % (i // 10) for i in range(100)]   # ten of each
    records = gensort.records(100, 0, np.random.default_rng(1))
    order = np.random.default_rng(4).permutation(100)
    for row, i in zip(records, order):
        row[:10] = np.frombuffer(keys[i], np.uint8)
    paths = _write(str(tmp_path), [records.tobytes()])
    points = sortstream.sample_splits(paths, 10, n_dev=4)
    # positions 25, 50, 75: keys 2, 5, 7 of the ten
    assert _key_bytes(points.devices) == [keys[25], keys[50], keys[75]]
    # device 0 owns keys 0-1, device 1 keys 2-4, device 2 keys 5-6
    assert points.shares == (20, 30, 20, 30)
    assert sum(points.shares) == 100
    owners = [bisect.bisect_right(_key_bytes(points.devices), k)
              for k in keys]
    assert [owners.count(d) for d in range(4)] == list(points.shares)
    assert sortstream.device_capacity(100, points.shares, 40) == 128
    assert sortstream.device_capacity(5368704, (25000,) * 4, 10485) == \
        -(-(1342176 + 83886 + 10485) // 128) * 128


def test_the_exchange_step_against_a_routing_by_hand():
    """One step of ``sort_exchange_step`` on four devices, the fills
    already uneven: every device's store then holds, behind what it held,
    the records it owns in (source device, row) order; the tally counts
    the partitions, what left its reader and the new fills."""
    mesh = default_mesh(4)
    per, cap, n_reduce = 8, 64, 3
    rng = np.random.default_rng(17)
    chunks = np.zeros((4, sortk.chunk_words(per * 100)), np.uint32)
    taken = [8, 8, 5, 0]
    rows = rng.integers(0, 256, (4, per, 100), dtype=np.uint8)
    rows[1, 3, :10] = 0xFF           # a key that looks like padding
    for s in range(4):
        chunks[s, :per * 25] = rows[s].reshape(-1).view("<u4")
        chunks[s, per * 25] = taken[s]
    real = [rows[s, i] for s in range(4) for i in range(taken[s])]
    keys = sorted(bytes(r[:10]) for r in real)
    bounds = [keys[5], keys[10], keys[16]]
    parts = [keys[7], keys[14]]

    def lanes_of(bs):
        return sortstream.host_lanes(np.frombuffer(
            b"".join(bs), np.uint8).reshape(len(bs), 10))

    fills = np.array([3, 0, 7, 1], np.int32)
    store, lanes, fill, tally = sortk.exchange_fn(per, mesh)(
        np.zeros((4 * cap, 25), np.uint32),
        np.full((3, 4 * cap), sortk.PAST_END, np.uint32), fills, chunks,
        lanes_of(parts), lanes_of(bounds))
    store = np.asarray(store).reshape(4, cap, 25)
    lanes = np.asarray(lanes).reshape(3, 4, cap)
    tally = np.asarray(tally)
    owners = [bisect.bisect_right(bounds, bytes(r[:10])) for r in real]
    for d in range(4):
        mine = [r for r, o in zip(real, owners) if o == d]
        n = len(mine)
        got = store[d, fills[d]:fills[d] + n]
        assert got.tobytes() == b"".join(bytes(r) for r in mine), d
        assert (lanes[:, d, fills[d]:fills[d] + n].T
                == sortstream.host_lanes(np.array([r[:10] for r in mine])
                                         .reshape(n, 10))).all()
        # behind the device's last record every lane still sorts last
        assert (lanes[:, d, fills[d] + n:] == sortk.PAST_END).all()
        assert tally[d, n_reduce + 1] == fills[d] + n
    assert np.asarray(fill).tolist() == tally[:, n_reduce + 1].tolist()
    readers = [s for s in range(4) for _ in range(taken[s])]
    assert tally[:, n_reduce].tolist() == [
        sum(1 for s, o in zip(readers, owners) if s == d and o != d)
        for d in range(4)]
    assert tally[:, :n_reduce].sum(axis=0).tolist() == [
        sum(1 for r in real if bisect.bisect_right(parts, bytes(r[:10])) == p)
        for p in range(n_reduce)]
    assert tally[:, :n_reduce].sum(axis=1).tolist() == taken


def test_the_programs_names_and_scopes_are_what_a_trace_reads():
    """The step's HLO module is ``jit_sort_exchange_step`` (a device trace
    tells it from ``jit_sort_ingest_step``), the mesh's ordering keeps the
    name ``jit_sort_order``, and the step's parts stand under the scopes
    ``route``, ``shuffle``, ``land`` and ``append``."""
    mesh = default_mesh(4)
    S = jax.ShapeDtypeStruct
    u32, i32 = np.uint32, np.int32
    step = sortk.exchange_fn(8, mesh).lower(
        S((256, 25), u32), S((3, 256), u32), S((4,), i32),
        S((4, sortk.chunk_words(800)), u32), S((2, 3), u32), S((3, 3), u32))
    text = step.as_text(debug_info=True)
    assert "module @jit_sort_exchange_step" in text
    for scope in ("pack", "partition", "route", "shuffle", "land", "append"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    assert text.count("all_to_all") >= 1
    order = sortk.mesh_order_fn(mesh).lower(S((256, 25), u32),
                                            S((3, 256), u32))
    assert "module @jit_sort_order" in order.as_text()
    assert "module @jit_sort_order" in sortk.sort_order.lower(
        S((64, 25), u32), S((3, 64), u32)).as_text()


def test_the_enqueue_span_names_the_exchange_program(three_files, tmp_path):
    rc, _, _ = _mesh(three_files, str(tmp_path / "wd"), 2, "--trace-dir",
                     str(tmp_path / "tr"))
    try:
        assert rc == 0
        programs = set()
        for path in glob.glob(str(tmp_path / "tr" / "**" / "*.json*"),
                              recursive=True):
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rt") as f:
                for line in f:
                    if '"enqueue"' in line and "program" in line:
                        programs |= {m for m in ("sort_exchange_step",
                                                 "sort_ingest_step")
                                     if m in line}
        assert programs == {"sort_exchange_step"}
    finally:
        from dsi_tpu.obs import configure_tracing
        configure_tracing(enabled=False)
