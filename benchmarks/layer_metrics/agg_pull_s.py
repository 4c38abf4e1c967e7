"""Seconds per job in the ``pull`` spans of the ``agg`` stage (``pull_s``):
the host waiting for a step's packed table of sums and copying it down,
two ``uint32`` lanes a sum."""

from layer_metrics._agg import stage_median


def read(obs):
    return stage_median(obs, lambda s: s["pull_s"])
