"""Seconds per job in the plan's first stage, the grep over the input
stream (``plan_stage_walls['grep']``: the stage's ``plan`` span from its
engine's construction to its close)."""

from layer_metrics._plan import stage_wall_s


def read(obs):
    return stage_wall_s(obs, "grep")
