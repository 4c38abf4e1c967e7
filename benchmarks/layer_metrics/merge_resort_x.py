"""Rows the host accumulator sorted over rows it was given
(``merge_rows_sorted`` over ``merge_rows_in`` of the traced job's
``pipeline_stats``): how many times over the merge re-sorts its input.  A
count, not a time; every job of a run reads the same corpus and counts
the same, so the traced job stands for them.  A run without a traced job
(an untraced run, a rehearsal) has nothing here to read."""


def read(obs):
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    given, sorted_ = ps.get("merge_rows_in"), ps.get("merge_rows_sorted")
    return sorted_ / given if given and sorted_ is not None else None
