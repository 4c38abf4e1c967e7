"""Median seconds of a map spent reading its split (``read`` spans under
the device worker's ``worker.map``)."""

from layer_metrics._tasks import map_part_s


def read(obs):
    return map_part_s(obs, ("read",))
