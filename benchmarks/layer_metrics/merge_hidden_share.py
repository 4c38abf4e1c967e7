"""Percent of the host accumulator's compactions that the job's steps hid:
100 · (1 − ``compact_caller_s`` ÷ ``compact_s``) of ``pipeline_stats``,
the median over a run's whole jobs.  ``compact_s`` sums the ``compact``
spans of ``parallel/merge.py`` on whichever thread they ran (a window
that filled is compacted on a merger thread, beside the steps that
follow); ``compact_caller_s`` is what of them the accumulator's caller
was held for: the compactions on its own thread (the last, partial
window, inside ``finalize``) and its ``merge_wait`` spans (a compaction
still in flight when the next window was full or the table was asked
for).  0 where every compaction held the caller.  A program that prints
no ``compact_caller_s``, as those before PR 52, and a job that compacted
nothing, have nothing here to read."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([
        100.0 * (1.0 - p["compact_caller_s"] / p["compact_s"])
        for p in pipeline_stats(obs)
        if p.get("compact_s") and "compact_caller_s" in p])
