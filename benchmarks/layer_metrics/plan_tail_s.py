"""Seconds per job outside the stages: the job's wall minus ``plan_s``
(argument parsing, the mesh, the commit of ``mr-out-*``, the stats)."""

from layer_metrics._plan import plan_median


def read(obs):
    return plan_median(obs, lambda s: s["wall_s"] - s["plan_s"])
