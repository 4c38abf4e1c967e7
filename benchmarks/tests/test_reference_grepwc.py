"""The plain reference of a ``planrun --chain grep-wc`` job against a text
small enough to count by hand."""

import reference
import reference_grepwc


def _write(tmp_path, name, data):
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def test_words_of_the_records_that_pass_counted_by_hand(tmp_path):
    """Two files.  The first ends mid-line, so the joining newline ends its
    last record; the second ends in a newline.  Records, with whether
    ``"; "`` occurs in them:

    0 ``the cat; sat`` yes · 1 ``the dog sat`` no · 2 ``a;b`` no (no space
    after it) · 3 ``Cat; the cat; x9y`` yes · 4 ``tail; of one`` yes (the
    first file's unterminated tail) · 5 ``two;`` no · 6 ``the end; `` yes ·
    7 (empty, after the last newline) no."""
    a = _write(tmp_path, "a.txt",
               b"the cat; sat\nthe dog sat\na;b\nCat; the cat; x9y\n"
               b"tail; of one")
    b = _write(tmp_path, "b.txt", b"two;\nthe end; \n")
    got = reference_grepwc.lines([a, b], {"pattern": "; "})
    assert got == sorted([
        "the 3", "cat 2", "sat 1", "Cat 1", "x 1", "y 1",  # a digit splits
        "tail 1", "of 1", "one 1", "end 1"])
    # file order is argument order: the other way round, ``of one`` joins
    # no ``; `` any more, and ``tail; of one`` still passes
    assert reference_grepwc.lines([b, a], {"pattern": "; "}) == got


def test_a_record_does_not_reach_across_the_joining_newline(tmp_path):
    """``x;`` ends the first file and `` y`` starts the second: the stream
    holds ``x;\\n y``, in which ``; `` does not occur."""
    a = _write(tmp_path, "a.txt", b"x;")
    b = _write(tmp_path, "b.txt", b" y\n")
    assert reference_grepwc.lines([a, b], {"pattern": "; "}) == []
    joined = _write(tmp_path, "c.txt", b"x; y\n")
    assert reference_grepwc.lines([joined], {"pattern": "; "}) == \
        ["x 1", "y 1"]


def test_every_record_passing_is_a_word_count_and_none_is_nothing(tmp_path):
    a = _write(tmp_path, "a.txt", b"one two\nTwo three two\n\nfour")
    assert reference_grepwc.lines([a], {"pattern": "o"}) == \
        reference.wc_lines([a], {})
    assert reference_grepwc.lines([a], {"pattern": "QZQ"}) == []
    empty = _write(tmp_path, "e.txt", b"")
    assert reference_grepwc.lines([empty, empty], {"pattern": "o"}) == []


def test_passes_multiply_the_counts(tmp_path):
    a = _write(tmp_path, "a.txt", b"b; a a\nc\n")
    assert reference_grepwc.lines([a], {"pattern": "; ", "passes": 3}) == \
        ["a 6", "b 3"]
