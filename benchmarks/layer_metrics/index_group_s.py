"""Seconds per job grouping the postings table into the index (``group``
span of ``merge.PostingsTable.finalize_packed``, ``group_s`` of the
walk's scope: one lexsort over the key lanes and the run detection)."""

from layer_metrics._index import STAGE, job_median


def read(obs):
    return job_median(obs, lambda p: p["stages"][STAGE]["group_s"])
