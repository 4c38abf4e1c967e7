"""The plain reference against inputs small enough to count by hand."""

import reference


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_bytes(text.encode("ascii"))
    return str(p)


def test_wc_splits_on_everything_but_ascii_letters(tmp_path):
    a = _write(tmp_path, "a.txt",
               "The cat, the CAT; the-cat!\n\n  it's 2 cats_and 1dog\n")
    b = _write(tmp_path, "b.txt", "cat\nThe")  # no newline at the end
    assert reference.wc_lines([a, b], {}) == sorted([
        "The 2", "the 2", "cat 3", "CAT 1", "it 1", "s 1", "cats 1",
        "and 1", "dog 1"])


def test_wc_passes_multiply_counts(tmp_path):
    a = _write(tmp_path, "a.txt", "x y x\n")
    assert reference.wc_lines([a], {"passes": 3}) == ["x 6", "y 3"]


def test_grep_counts_whole_lines_and_keeps_the_tail(tmp_path):
    a = _write(tmp_path, "a.txt",
               "the end\nThe end\nnothing here\n\nthe end\nbathe")
    got = reference.grep_lines([a], {"pattern": "[Tt]he"})
    assert got == sorted(["the end 2", "The end 1", "bathe 1"])


def test_grep_empty_lines_and_no_match(tmp_path):
    a = _write(tmp_path, "a.txt", "\n\n\n")
    assert reference.grep_lines([a], {"pattern": "x"}) == []


def test_read_output_merges_sorts_and_drops_blank_lines(tmp_path):
    (tmp_path / "mr-out-0").write_text("b 1\n\na 2\n")
    (tmp_path / "mr-out-1").write_text("c 3\n")
    (tmp_path / "other").write_text("zzz 9\n")
    assert reference.read_output(str(tmp_path)) == ["a 2", "b 1", "c 3"]
