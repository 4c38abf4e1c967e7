"""Percent of the posting rows the traced job grouped that arrived in runs
(a wave's rows as the device leaves them, in word order) and were merged
without a sort: 100 · (1 − ``group_rows_sorted`` ÷ ``postings_rows``) of
the walk's scope.  A count, not a time; every job of a run walks the same
collection and counts the same, so the traced job stands for them.  100
where the group never sorts what the device already sorted.  A run without
a traced job (an untraced run, a rehearsal), and a program whose group
counts no sorted rows, have nothing here to read."""

from layer_metrics._index import traced_walk


def read(obs):
    walk = traced_walk(obs) or {}
    rows, sorted_rows = walk.get("postings_rows"), walk.get(
        "group_rows_sorted")
    if not rows or sorted_rows is None:
        return None
    return 100.0 * (1.0 - sorted_rows / rows)
