"""Seconds per job in the plan's second stage, the word count over the
relay's buffers (``plan_stage_walls['wc']``)."""

from layer_metrics._plan import stage_wall_s


def read(obs):
    return stage_wall_s(obs, "wc")
