"""The plain reference of a served wave against inputs small enough to
count by hand: the deal of files to jobs, the prefixes, and each job's
statistics over its own files only."""

import json
import os

import pytest

import reference_grepstats
import reference_servegrep

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, data):
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def test_the_mix_deals_32_files_to_12_jobs_of_8_tenants_in_order():
    with open(os.path.join(BENCH, "traffic", "fb12-grep.json")) as f:
        tenants = json.load(f)["reference_params"]["tenants"]
    jobs = reference_servegrep.deal(tenants, 32)
    assert [(j["tenant"], j["k"], j["files"][0], j["files"][-1])
            for j in jobs] == [
        ("t0", 0, 0, 11), ("t1", 0, 12, 18), ("t2", 0, 19, 20),
        ("t2", 1, 21, 22), ("t2", 2, 23, 24), ("t3", 0, 25, 25),
        ("t3", 1, 26, 26), ("t4", 0, 27, 27), ("t4", 1, 28, 28),
        ("t5", 0, 29, 29), ("t6", 0, 30, 30), ("t7", 0, 31, 31)]
    # every file in exactly one job; sizes 7 x 1, 3 x 2, one 7, one 12
    assert sorted(i for j in jobs for i in j["files"]) == list(range(32))
    assert sorted(len(j["files"]) for j in jobs) == \
        [1] * 7 + [2] * 3 + [7, 12]
    assert [j["pattern"] for j in jobs if j["k"] == 0] == \
        ["the", "and", "ing", "ion", "ent", "ter", "ate", "ver"]
    # the two largest jobs carry 59 % of the files
    assert (12 + 7) / 32 == pytest.approx(0.59, abs=0.005)


def test_a_mix_that_does_not_fit_the_corpus_is_refused():
    tenants = [{"tenant": "a", "pattern": "x", "jobs": [2, 1]}]
    with pytest.raises(ValueError):
        reference_servegrep.deal(tenants, 4)
    with pytest.raises(ValueError):
        reference_servegrep.deal(tenants, 2)


def test_two_tenants_three_jobs_counted_by_hand(tmp_path):
    """Tenant ``a`` greps ``ab`` over files 0-1 as one stream (the first
    ends mid-line, so the joining newline ends its last record), tenant
    ``b`` greps ``b`` over file 2 and then over file 3, each alone.

    a/0: records ``ab abab`` (3), ``x`` (0), ``tail ab`` (1), ``cab`` (1),
    ``ab`` (1): 5 lines, 4 matched, 6 occurrences.
    b/0: ``b`` (1), ``bbb`` (3), ``a`` (0).   b/1: ``none`` (0)."""
    files = [_write(tmp_path, "f0", b"ab abab\nx\ntail ab"),
             _write(tmp_path, "f1", b"cab\nab\n"),
             _write(tmp_path, "f2", b"b\nbbb\na\n"),
             _write(tmp_path, "f3", b"none")]
    params = {"bins": 4, "topk": 2, "tenants": [
        {"tenant": "a", "pattern": "ab", "jobs": [2]},
        {"tenant": "b", "pattern": "b", "jobs": [1, 1]}]}
    assert reference_servegrep.lines(files, params) == sorted([
        "a/0 lines 5", "a/0 matched 4", "a/0 occurrences 6",
        "a/0 hist 0 1", "a/0 hist 1 3", "a/0 hist 2 0", "a/0 hist 3 1",
        "a/0 top 0 0 3", "a/0 top 1 2 1",
        "b/0 lines 3", "b/0 matched 2", "b/0 occurrences 4",
        "b/0 hist 0 1", "b/0 hist 1 1", "b/0 hist 2 0", "b/0 hist 3 1",
        "b/0 top 0 1 3", "b/0 top 1 0 1",
        "b/1 lines 1", "b/1 matched 0", "b/1 occurrences 0",
        "b/1 hist 0 1", "b/1 hist 1 0", "b/1 hist 2 0", "b/1 hist 3 0"])


def test_a_job_is_its_tenant_alone_over_its_own_files(tmp_path):
    """Each job's lines are ``reference_grepstats`` over exactly its files,
    prefixed: nothing of a neighbour's files or pattern leaks in."""
    files = [_write(tmp_path, f"f{i}", (b"the and %d\n" % i) * (i + 1))
             for i in range(3)]
    params = {"bins": 8, "topk": 16, "tenants": [
        {"tenant": "t0", "pattern": "the", "jobs": [2]},
        {"tenant": "t1", "pattern": "and", "jobs": [1]}]}
    alone = {"t0/0": reference_grepstats.lines(
                 files[:2], {"pattern": "the", "bins": 8, "topk": 16}),
             "t1/0": reference_grepstats.lines(
                 files[2:], {"pattern": "and", "bins": 8, "topk": 16})}
    assert reference_servegrep.lines(files, params) == sorted(
        f"{job} {line}" for job, got in alone.items() for line in got)
    # f0 holds 1 record and f1 2; both end in a newline, so the newline
    # that joins them makes one empty record more
    assert "t0/0 lines 4" in reference_servegrep.lines(files, params)
    assert "t1/0 lines 3" in reference_servegrep.lines(files, params)
