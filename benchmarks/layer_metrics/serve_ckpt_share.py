"""Percent of a served wave's wall in ``ckpt_s``: the lanes' ``save_ckpt``
(the ``ckpt`` spans), every 8 confirmed steps and once more at each
eviction, each a durable write."""

from layer_metrics._serve import share_of_wall


def read(obs):
    return share_of_wall(obs, "daemon", "ckpt_s")
