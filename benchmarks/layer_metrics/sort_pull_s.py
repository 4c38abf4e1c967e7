"""Seconds per job in the ``pull`` spans of the commit: the host asking
for the ordered blocks, whose copies were started ahead (``pull_s`` at the
top of ``pipeline_stats``; its ``d2h_s`` is the copies themselves)."""

from layer_metrics._sort import job_median


def read(obs):
    return job_median(obs, lambda p: p["pull_s"])
