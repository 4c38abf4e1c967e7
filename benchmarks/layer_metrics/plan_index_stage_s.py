"""Seconds per job in the index plan's first stage, the wave walk over the
documents (``plan_stage_walls['indexer']``: the stage's ``plan`` span from
its engine's construction to its close)."""

from layer_metrics._plan import stage_wall_s


def read(obs):
    return stage_wall_s(obs, "indexer")
