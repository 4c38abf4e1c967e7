"""Seconds per job in the sampling pre-pass (``plan_stage_walls['sample']``:
the stage's ``plan`` span around the ``sample`` span: 100,000 keys read at
evenly spaced record offsets, sorted, the split points taken)."""

from layer_metrics._plan import stage_wall_s


def read(obs):
    return stage_wall_s(obs, "sample")
