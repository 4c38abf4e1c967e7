"""Seconds per job in the ``join_probe`` span: every chunk of the probe
side through ``join_probe_step``, every step's table of groups pulled and
merged, to the last.  Median over the whole jobs."""

from layer_metrics._join import stage_median


def read(obs):
    return stage_median(obs, lambda s: s["join_probe_s"])
