"""Seconds per job in the ``join_build`` span: the build side's rows
counted, every chunk of them through ``join_build_step`` into the table on
the device, the table ordered and its neighbours checked.  Median over the
whole jobs."""

from layer_metrics._join import stage_median


def read(obs):
    return stage_median(obs, lambda s: s["join_build_s"])
