"""Seconds per job in the ``agg`` stage (its wall in the plan scope's
``plan_stage_walls``): every step's dispatch, pull and merge, and the last
compaction."""

from layer_metrics._plan import stage_wall_s


def read(obs):
    return stage_wall_s(obs, "agg")
