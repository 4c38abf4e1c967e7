"""``planrun --chain join``: ``Rankings`` rows built into a table that stays
on the device, ``UserVisits`` rows inside a date window matched with it by
a key of up to 100 bytes, and the matched rows' revenue, rank and count
summed by ``sourceIP``, committed as ``mr-out-<r>`` and ``plan-top.json``.

The committed partitions must equal, byte for byte and partition by
partition, what ``benchmarks/reference_join.py`` gives (plain Python over
the same files: ``split``, a ``dict``, integer arithmetic), whatever the
partitions, the chunks and the pipeline's depth; every byte of a key
decides a match, whatever the hash that orders the table does; sums pass
2^32 exactly and an average is truncated, not rounded; a row of either
table that cannot be read, and a key held twice, fail the job with file
and line and commit nothing.
"""

import ast
import contextlib
import io
import json
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

import rankvisits  # noqa: E402
import reference_join  # noqa: E402

import dsi_tpu.obs.trace as obs_trace  # noqa: E402
from dsi_tpu.cli import planrun as cli  # noqa: E402
from dsi_tpu.obs import Tracer, registry  # noqa: E402
from dsi_tpu.ops import joink  # noqa: E402
from dsi_tpu.parallel.shuffle import default_mesh  # noqa: E402
from dsi_tpu.plan import (PlanHostPath, STAGE_KINDS, join_plan,  # noqa: E402
                          run_plan)
from dsi_tpu.plan.graph import parse_dates  # noqa: E402

CHUNK = 4096  # ~30 visits a step: every file is cut many times
WEEK = "2000-01-15:2000-01-22"
HALF = "2000-01-01:2004-12-31"
REST = "ua|USA|en-US|word|5"


def _write(directory, name, blobs):
    os.makedirs(directory, exist_ok=True)
    paths = []
    for i, blob in enumerate(blobs):
        paths.append(os.path.join(directory, f"{name}{i:03d}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(bytes(blob))
    return paths


def _rank(url, rank=7, more="|3"):
    return f"{url}|{rank}{more}\n".encode("latin-1")


def _visit(ip, url, date="2000-01-16", revenue="1.5"):
    return f"{ip}|{url}|{date}|{revenue}|{REST}\n".encode("latin-1")


def _tables(tmp_path, seed=7, pages=300, files=(400, 350, 150), known=240,
            pool=60):
    """Two drawn tables: ``pages`` URLs of which the rankings hold the
    first ``known`` (in two files), visits over all of them, so that some
    visit no page; ``pool`` addresses, so that keys repeat within a step
    and across steps and files."""
    rng = np.random.default_rng([seed, 99])
    urls, lengths = rankvisits.pages(pages, rng)
    half = known // 2
    build = _write(tmp_path / "in", "r", [
        rankvisits.ranking_rows(urls[:half], lengths[:half], rng),
        rankvisits.ranking_rows(urls[half:known], lengths[half:known], rng)])
    probe = _write(tmp_path / "in", "v", [
        rankvisits.visit_rows(n, np.random.default_rng([seed, i]), urls,
                              lengths, pool=pool)
        for i, n in enumerate(files)])
    return build, probe


def _planrun(build, probe, workdir, *flags, dates=WEEK, chunk=CHUNK,
             nreduce=10, chain="join"):
    argv = ["--chain", chain, "--nreduce", str(nreduce), "--stats",
            "--chunk-bytes", str(chunk), "--workdir", str(workdir)]
    for path in build:
        argv += ["--join-build", path]
    if dates is not None:
        argv += ["--join-dates", dates]
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        try:
            rc = cli.main(argv + list(flags) + list(probe))
        except SystemExit as e:
            rc = e.code
    text = err.getvalue()
    m = re.search(r"^planrun: pipeline_stats=(\{.*\})$", text, re.M)
    return rc, (ast.literal_eval(m.group(1)) if m else None), text


def _committed(workdir, n_reduce=10):
    return [open(os.path.join(workdir, f"mr-out-{r}"), "rb").read()
            for r in range(n_reduce)]


def _top_line(workdir):
    with open(os.path.join(workdir, "plan-top.json")) as f:
        top = json.load(f)["top"]
    return None if top is None else (
        f"{top['sourceIP']} {top['totalRevenue']} {top['avgPageRank']}")


def _reference_top(build, probe, dates):
    total, _ = reference_join.sums(build, probe, dates)
    best = reference_join.top(total)
    return None if best is None else reference_join.line(best, total[best])


def _same_as_the_reference(build, probe, workdir, dates, n_reduce=10):
    assert _committed(workdir, n_reduce) == reference_join.partitions(
        build, probe, dates, n_reduce)
    assert _top_line(workdir) == _reference_top(build, probe, dates)


def _nothing_committed(workdir):
    assert not os.path.exists(workdir) or not [
        n for n in os.listdir(workdir) if n.startswith(("mr-out", "plan-"))]


@pytest.mark.parametrize("chunk", [1 << 17, 40960, CHUNK],
                         ids=["one-step", "three-steps", "many-steps"])
@pytest.mark.parametrize("nreduce", [1, 4, 10])
def test_byte_equal_to_the_reference(tmp_path, nreduce, chunk):
    build, probe = _tables(tmp_path)
    rc, ps, text = _planrun(build, probe, tmp_path / "out", dates=HALF,
                            chunk=chunk, nreduce=nreduce)
    assert rc == 0, text[-2000:]
    _same_as_the_reference(build, probe, tmp_path / "out", HALF, nreduce)
    total, counts = reference_join.sums(build, probe, HALF)
    join = ps["stages"]["join"]
    assert 0 < counts["matched_rows"] < counts["window_rows"] \
        < counts["probe_rows"]
    for key in ("build_rows", "probe_rows", "window_rows", "matched_rows"):
        assert join[f"join_{key}"] == counts[key], key
    assert join["join_groups"] == len(total) == ps["write_rows_packed"]
    assert ps["write_rows_dict"] == 0
    sizes = [sum(os.path.getsize(p) for p in ps_) for ps_ in (build, probe)]
    assert join["join_build_bytes"] == sizes[0]
    assert join["bytes_in"] == sum(sizes)
    assert join["join_build_steps"] >= -(-sizes[0] // chunk)
    assert join["steps"] >= -(-sizes[1] // chunk)
    assert join["join_table_bytes"] >= 100 * counts["build_rows"]
    assert join["join_value_lanes"] == 6
    assert join["step_pulls"] == join["pulls_early"] + join["pulls_late"]


@pytest.mark.parametrize("depth", [1, 3])
def test_the_pipelines_depth_changes_nothing(tmp_path, depth):
    build, probe = _tables(tmp_path, seed=11)
    rc, ps, text = _planrun(build, probe, tmp_path / "out", "--pipeline-depth",
                            str(depth), dates=HALF)
    assert rc == 0, text[-2000:]
    assert ps["stages"]["join"]["depth"] == depth
    _same_as_the_reference(build, probe, tmp_path / "out", HALF)


@pytest.mark.parametrize("length", [1, 16, 17, 99, 100])
def test_a_key_of_any_length_up_to_100_bytes(tmp_path, length):
    key = ("k" * length)[:length]
    near = key[:-1] + "j"  # differs in its last byte only
    build = _write(tmp_path / "in", "r", [
        _rank(key, 10) + _rank(near, 20) + _rank("other", 30)])
    probe = _write(tmp_path / "in", "v", [
        _visit("1.1.1.1", key) + _visit("1.1.1.1", near, revenue="2")
        + _visit("2.2.2.2", near) + _visit("3.3.3.3", key[:-1] or "x")])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)
    assert ps["stages"]["join"]["join_matched_rows"] == 3
    lines = b"".join(_committed(tmp_path / "out")).decode().splitlines()
    assert sorted(lines) == ["1.1.1.1 3.500000 15.000000",
                             "2.2.2.2 1.500000 20.000000"]


def test_a_visit_whose_url_is_no_page_contributes_nothing(tmp_path):
    build = _write(tmp_path / "in", "r", [_rank("http://a/", 4)])
    probe = _write(tmp_path / "in", "v", [
        _visit("1.1.1.1", "http://a/") + _visit("1.1.1.1", "http://b/")
        + _visit("9.9.9.9", "http://a") + _visit("9.9.9.9", "http://a//")])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    join = ps["stages"]["join"]
    assert (join["join_window_rows"], join["join_matched_rows"],
            join["join_groups"]) == (4, 1, 1)
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


@pytest.mark.parametrize("dates, some", [
    ("1999-01-01:1999-12-31", False), ("2003-03-03:2003-03-03", True),
    ("0000-00-00:9999-99-99", True)], ids=["nothing", "one-day", "all"])
def test_the_window_passes_nothing_a_day_or_everything(tmp_path, dates, some):
    build, probe = _tables(tmp_path, seed=5)
    with open(probe[1], "ab") as f:  # the drawn dates may miss the day
        f.write(_visit("7.7.7.7", open(build[0], "rb").readline().split(
            b"|")[0].decode(), date="2003-03-03"))
    rc, ps, text = _planrun(build, probe, tmp_path / "out", dates=dates)
    assert rc == 0, text[-2000:]
    _same_as_the_reference(build, probe, tmp_path / "out", dates)
    join = ps["stages"]["join"]
    if some:
        assert join["join_groups"] > 0 and _top_line(tmp_path / "out")
    else:
        assert _committed(tmp_path / "out") == [b""] * 10
        assert _top_line(tmp_path / "out") is None
        assert join["join_window_rows"] == join["join_groups"] == 0
    if dates.startswith("0000"):
        assert join["join_window_rows"] == join["join_probe_rows"]


def test_both_ends_of_the_window_are_inclusive(tmp_path):
    build = _write(tmp_path / "in", "r", [_rank("u", 5)])
    days = ["2000-01-14", "2000-01-15", "2000-01-22", "2000-01-23",
            "1999-12-31", "2000-02-15", "2001-01-16"]
    probe = _write(tmp_path / "in", "v", [
        b"".join(_visit(f"10.0.0.{i}", "u", date=day)
                 for i, day in enumerate(days))])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    lines = b"".join(_committed(tmp_path / "out")).decode().splitlines()
    assert sorted(line.split()[0] for line in lines) == ["10.0.0.1",
                                                         "10.0.0.2"]
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


def test_one_sourceip_in_several_steps_and_files(tmp_path):
    build = _write(tmp_path / "in", "r", [_rank("u", 5) + _rank("w", 8)])
    block = b"".join(_visit("1.2.3.4" if i % 3 == 0 else f"9.9.{i % 7}.9",
                            "u" if i % 2 else "w", revenue=f"{i % 10}.25")
                     for i in range(400))
    probe = _write(tmp_path / "in", "v", [block, block[:4000].rsplit(
        b"\n", 1)[0] + b"\n", block])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    assert ps["stages"]["join"]["steps"] > 8
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


def test_a_tie_for_the_top_row_goes_to_the_least_key(tmp_path):
    build = _write(tmp_path / "in", "r", [_rank("u", 5)])
    probe = _write(tmp_path / "in", "v", [
        _visit("9.9.9.9", "u", revenue="2.5") + _visit("10.0.0.1", "u")
        + _visit("10.0.0.1", "u", revenue="1") + _visit("8.8.8.8", "u")])
    rc, _, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    assert _top_line(tmp_path / "out") == "10.0.0.1 2.500000 5.000000"
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


def test_sums_past_2_32_and_a_truncated_average(tmp_path):
    build = _write(tmp_path / "in", "r", [
        _rank("big", 999_999_999) + _rank("one", 1) + _rank("two", 2)
        + _rank("zero", 0)])
    probe = _write(tmp_path / "in", "v", [
        # the rank's sum passes 2^32 inside one step, the revenue's over
        # the job
        b"".join(_visit("1.1.1.1", "big", revenue="999.999999")
                 for _ in range(9))
        + _visit("2.2.2.2", "one") + _visit("2.2.2.2", "one")
        + _visit("2.2.2.2", "two")            # 4 / 3 = 1.333333...
        + _visit("3.3.3.3", "two") + _visit("3.3.3.3", "zero")
        + _visit("3.3.3.3", "zero"),          # 2 / 3 = 0.666666...
        b"".join(_visit("1.1.1.1", "big", revenue="999.999999")
                 for _ in range(4500))])
    rc, _, text = _planrun(build, probe, tmp_path / "out", chunk=1 << 16)
    assert rc == 0, text[-2000:]
    lines = b"".join(_committed(tmp_path / "out")).decode().splitlines()
    assert sorted(lines) == [
        f"1.1.1.1 {4509 * 999_999_999 // 10 ** 6}."
        f"{4509 * 999_999_999 % 10 ** 6:06d} 999999999.000000",
        "2.2.2.2 4.500000 1.333333", "3.3.3.3 4.500000 0.666666"]
    assert 4509 * 999_999_999 > 1 << 32
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


def test_a_last_row_without_a_newline_in_either_table(tmp_path):
    build = _write(tmp_path / "in", "r", [_rank("u", 5), _rank("w", 9)[:-1]])
    probe = _write(tmp_path / "in", "v", [
        _visit("1.1.1.1", "u")[:-1], _visit("1.1.1.1", "w")[:-1]])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 0, text[-2000:]
    assert ps["stages"]["join"]["join_build_rows"] == 2
    _same_as_the_reference(build, probe, tmp_path / "out", WEEK)


GOOD_RANKS = _rank("a", 1) + _rank("b", 2)
GOOD_VISITS = _visit("1.1.1.1", "a") + _visit("2.2.2.2", "b", "1999-01-01")
BAD_BUILD = {
    "key-101": _rank("k" * 101),
    "key-empty": _rank(""),
    "key-high-byte": _rank("caf\xe9"),
    "key-del": _rank("a\x7fb"),
    "rank-letter": _rank("c", "1x"),
    "rank-10-digits": _rank("c", "1234567890"),
    "rank-empty": _rank("c", ""),
    "rank-signed": _rank("c", "-1"),
    "one-field": b"justakey\n",
}
BAD_PROBE = {
    "three-fields": b"1.1.1.1|a|2000-01-16\n",
    "ip-17": _visit("1" * 17, "a"),
    "ip-empty": _visit("", "a"),
    "url-101": _visit("1.1.1.1", "u" * 101),
    "url-empty": _visit("1.1.1.1", ""),
    "url-high-byte": _visit("1.1.1.1", "caf\xe9", "1999-01-01"),
    "date-short": _visit("1.1.1.1", "a", "2000-1-16"),
    "date-slashes": _visit("1.1.1.1", "a", "2000/01/16"),
    "date-letter": _visit("1.1.1.1", "a", "2000-01-1x"),
    "date-long": _visit("1.1.1.1", "a", "2000-01-160"),
    "value-letter": _visit("1.1.1.1", "a", revenue="1.x"),
    "value-outside-the-window": _visit("1.1.1.1", "a", "1999-01-01", "1e3"),
}


@pytest.mark.parametrize("kind", sorted(BAD_BUILD))
def test_a_bad_build_row_fails_the_job_and_commits_nothing(tmp_path, kind):
    build = _write(tmp_path / "in", "r", [
        GOOD_RANKS, _rank("c", 3) + BAD_BUILD[kind] + _rank("d", 4)])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 1 and ps is None
    assert f"planrun: {build[1]}:2: bad row" in text
    _nothing_committed(tmp_path / "out")
    with pytest.raises(ValueError, match=re.escape(f"{build[1]}:2")):
        reference_join.sums(build, probe, WEEK)


@pytest.mark.parametrize("kind", sorted(BAD_PROBE))
def test_a_bad_probe_row_fails_the_job_and_commits_nothing(tmp_path, kind):
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [
        GOOD_VISITS * 40, GOOD_VISITS + BAD_PROBE[kind] + GOOD_VISITS])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 1 and ps is None
    assert f"planrun: {probe[1]}:3: bad row" in text
    _nothing_committed(tmp_path / "out")
    with pytest.raises(ValueError, match=re.escape(f"{probe[1]}:3")):
        reference_join.sums(build, probe, WEEK)


@pytest.mark.parametrize("apart", [1, 400], ids=["in-a-step", "across-steps"])
def test_a_key_held_twice_fails_the_job(tmp_path, apart):
    rows = [_rank(f"http://page/{i}", i) for i in range(500)]
    rows.insert(20 + apart, _rank("http://page/20", 77))
    build = _write(tmp_path / "in", "r", [_rank("first", 1),
                                          b"".join(rows)])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 1 and ps is None
    assert f"{build[1]}:21 and {build[1]}:{21 + apart} hold one key" in text
    _nothing_committed(tmp_path / "out")
    with pytest.raises(ValueError, match="hold one key"):
        reference_join.rankings(build)


@pytest.fixture
def patched_hash(monkeypatch):
    """``ops/joink.key_hash`` replaced, for programs traced afresh."""
    def patch(fn):
        monkeypatch.setattr(joink, "key_hash", fn)
        joink.probe_fn.cache_clear()
        joink.join_build_order.clear_cache()
    yield patch
    monkeypatch.undo()
    joink.probe_fn.cache_clear()
    joink.join_build_order.clear_cache()


def test_keys_of_one_hash_under_every_salt_fail_loudly(tmp_path,
                                                       patched_hash):
    import jax.numpy as jnp

    patched_hash(lambda cols, salt: (jnp.zeros_like(cols[0]),) * 2)
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    rc, ps, text = _planrun(build, probe, tmp_path / "out")
    assert rc == 1 and ps is None
    assert "share their hash under each of 4 salts" in text
    _nothing_committed(tmp_path / "out")


def test_keys_of_one_hash_under_one_salt_never_change_the_answer(
        tmp_path, patched_hash):
    real = joink.key_hash

    def collide_at_salt_0(cols, salt):
        h1, h2 = real(cols, salt)
        return (h1 * (salt != 0).astype(h1.dtype),
                h2 * (salt != 0).astype(h2.dtype))

    patched_hash(collide_at_salt_0)
    build, probe = _tables(tmp_path, seed=3)
    rc, ps, text = _planrun(build, probe, tmp_path / "out", dates=HALF)
    assert rc == 0, text[-2000:]
    _same_as_the_reference(build, probe, tmp_path / "out", HALF)
    assert ps["stages"]["join"]["join_matched_rows"] > 0


@pytest.mark.parametrize("flags", [
    ["--staged"], ["--check"], ["--hosts"], ["--checkpoint-dir", "ck"],
    ["--pipeline"], ["--stage-shards", "2"], ["--device-accumulate"],
    ["--mesh-shards", "2"], ["--aot"], ["--devices", "2"],
    ["--devices", "4"]], ids=lambda f: f[0][2:] + "-".join(f[1:]))
def test_flags_that_are_not_the_chains_are_refused(tmp_path, flags):
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    rc, ps, _ = _planrun(build, probe, tmp_path / "out", *flags)
    assert rc == 2 and ps is None
    _nothing_committed(tmp_path / "out")


@pytest.mark.parametrize("dates", [None, "2000-01-15", "2000-1-15:2000-01-22",
                                   "2000-01-15:2000-01-22:", "a:b"])
def test_the_window_is_required_and_checked(tmp_path, dates):
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    rc, ps, _ = _planrun(build, probe, tmp_path / "out", dates=dates)
    assert rc == 2 and ps is None
    if dates:
        with pytest.raises(ValueError):
            parse_dates(dates)


def test_the_two_flags_are_the_joins_alone(tmp_path):
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    assert _planrun(build, probe, tmp_path / "out", chain="agg")[0] == 2
    assert _planrun([], probe, tmp_path / "out", chain="agg")[0] == 2
    assert _planrun([], probe, tmp_path / "out")[0] == 2  # no build side


def test_no_host_path_and_no_mesh_commit_a_join(tmp_path):
    build = _write(tmp_path / "in", "r", [GOOD_RANKS])
    probe = _write(tmp_path / "in", "v", [GOOD_VISITS])
    plan = join_plan(build, probe, dates=parse_dates(WEEK), chunk_bytes=CHUNK)
    with pytest.raises(PlanHostPath, match="needs the host path"):
        run_plan(plan, mesh=default_mesh(1), staged=True)
    with pytest.raises(PlanHostPath, match="exchange by key"):
        run_plan(plan, mesh=default_mesh(2))


def test_the_stage_the_plan_and_the_counters_are_registered():
    assert len(STAGE_KINDS) == 10 and STAGE_KINDS[-1] == "join"
    plan = join_plan(["r0", "r1"], ["v0"], dates=("2000-01-15",
                                                  "2000-01-22"))
    (stage,) = plan.ordered()
    assert (stage.name, stage.kind, stage.deps) == ("join", "join", ())
    identity = plan.signature()["stages"][0]
    assert identity["build_paths"] == ["r0", "r1"]
    assert identity["paths"] == ["v0"]
    assert identity["dates"] == ["2000-01-15", "2000-01-22"]
    other = join_plan(["r0"], ["v0"], dates=("2000-01-15", "2000-01-22"))
    assert other.signature() != plan.signature()
    for key in ("join_build_rows", "join_build_bytes", "join_build_steps",
                "join_table_bytes", "join_probe_rows", "join_window_rows",
                "join_matched_rows", "join_groups", "join_value_lanes"):
        assert key in registry.COUNTER_KEYS, key
    for key in ("join_build_s", "join_probe_s"):
        assert key in registry.PHASE_KEYS, key
    assert {"join_build", "join_probe"} <= obs_trace.SPAN_NAMES


def test_module_names_and_scopes_in_the_lowered_text():
    sds = jax.ShapeDtypeStruct
    table = sds((4096, joink.TABLE_COLS), np.uint32)
    state, chunk = sds((2,), np.int32), sds((1, CHUNK), np.uint8)
    texts = {
        "join_build_step": joink.build_fn(32).lower(table, state, chunk),
        "join_build_order": joink.join_build_order.lower(
            table, state, sds((), np.int32)),
        "join_probe_step": joink.probe_fn(64, 16).lower(
            table, sds((4096, 2), np.uint32), sds((3,), np.int32),
            sds((6,), np.uint32), chunk)}
    scopes = {"join_build_step": ("fields", "key_lanes", "integer", "append"),
              "join_build_order": ("hash", "sort", "gather", "unique"),
              "join_probe_step": ("fields", "window", "decimal", "key_lanes",
                                  "lookup", "hash", "sort", "group")}
    for name, lowered in texts.items():
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text, name
        for scope in scopes[name]:
            assert f"/{scope}/" in text or f"/{scope}\"" in text, (name,
                                                                     scope)
        # no scatter and no 64-bit value (the attributes' i64 are shapes)
        assert "scatter" not in lowered.as_text(), name
        assert not re.search(r"\) -> tensor<[^>]*[iuf]64>",
                             lowered.as_text()), name


def test_the_enqueue_spans_name_both_programs(tmp_path, monkeypatch):
    monkeypatch.delenv("DSI_TRACE_DIR", raising=False)
    tracer = Tracer(enabled=False)
    monkeypatch.setattr(obs_trace, "_global", tracer)
    try:
        build, probe = _tables(tmp_path, seed=13)
        rc, ps, text = _planrun(build, probe, tmp_path / "out", "--trace-dir",
                                str(tmp_path / "trace"), dates=HALF)
    finally:
        tracer.enabled = False
    assert rc == 0, text[-2000:]
    with open(tmp_path / "trace" / "trace.jsonl", encoding="utf-8") as f:
        events = [json.loads(line) for line in f][1:]
    spans = [e for e in events if e["ph"] == "X"]
    join = ps["stages"]["join"]
    programs = [e["program"] for e in spans if e["name"] == "enqueue"]
    assert programs.count("join_build_step") >= join["join_build_steps"]
    assert programs.count("join_probe_step") >= join["steps"]
    assert set(programs) == {"join_build_step", "join_probe_step"}
    names = {e["name"] for e in spans}
    assert {"join_build", "join_probe", "order", "read", "upload", "kernel",
            "pull", "merge", "finalize"} <= names
    (built,) = [e for e in spans if e["name"] == "join_build"]
    (probed,) = [e for e in spans if e["name"] == "join_probe"]
    assert join["join_build_s"] == pytest.approx(built["dur"], abs=5e-4)
    assert join["join_probe_s"] == pytest.approx(probed["dur"], abs=5e-4)
    # the starvation account covers the stage: its spans are boundaries
    assert {"join_build", "join_probe", "enqueue"} <= set(ps["starved_by"])
    assert sum(ps["starved_groups"].values()) == pytest.approx(
        ps["starved_s"], abs=1e-6)
