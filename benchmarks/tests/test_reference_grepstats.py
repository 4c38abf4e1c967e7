"""The plain reference of a ``grepstream`` job against a text small enough
to count by hand."""

import reference_grepstats

PARAMS = {"pattern": "aa", "bins": 4, "topk": 3}


def _write(tmp_path, name, data):
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def test_ten_lines_counted_by_hand(tmp_path):
    """Two files; the first ends in a newline, so the joining newline opens
    an empty record (number 5); the second ends in an unterminated tail
    (number 9).  Record by record, with the count of ``aa``:

    0 ``aa`` 1 · 1 ``aaa`` 2 (overlapping) · 2 ``xyz`` 0 · 3 ``aaaaa`` 4 ·
    4 ``baab`` 1 · 5 (empty) 0 · 6 ``aaa`` 2 · 7 ``a a`` 0 · 8 ``aaaaaa`` 5 ·
    9 ``caa`` 1 (the tail)."""
    a = _write(tmp_path, "a.txt", b"aa\naaa\nxyz\naaaaa\nbaab\n")
    b = _write(tmp_path, "b.txt", b"aaa\na a\naaaaaa\ncaa")
    got = reference_grepstats.lines([a, b], PARAMS)
    assert got == sorted([
        "lines 10", "matched 7", "occurrences 16",
        # bucket min(count, 3): 0 x3, 1 x3, 2 x2, 3+ x2
        "hist 0 3", "hist 1 3", "hist 2 2", "hist 3 2",
        # count descending, ties to the lower record number
        "top 0 8 5", "top 1 3 4", "top 2 1 2"])


def test_a_trailing_newline_opens_no_record_and_ties_go_to_the_earlier(
        tmp_path):
    a = _write(tmp_path, "a.txt", b"aa\naa\n\naa\n")
    got = reference_grepstats.lines([a], dict(PARAMS, topk=2))
    assert got == sorted(["lines 4", "matched 3", "occurrences 3",
                          "hist 0 1", "hist 1 3", "hist 2 0", "hist 3 0",
                          "top 0 0 1", "top 1 1 1"])


def test_no_input_and_no_match(tmp_path):
    empty = _write(tmp_path, "e.txt", b"")
    assert reference_grepstats.lines([empty], PARAMS) == sorted(
        ["lines 0", "matched 0", "occurrences 0",
         "hist 0 0", "hist 1 0", "hist 2 0", "hist 3 0"])
    # two empty files: the joining newline alone is one empty record
    assert "lines 1" in reference_grepstats.lines([empty, empty], PARAMS)
    a = _write(tmp_path, "a.txt", b"xyz\nxyz")
    got = reference_grepstats.lines([a], PARAMS)
    assert "matched 0" in got and "hist 0 2" in got
    assert not any(line.startswith("top ") for line in got)
