"""Median seconds of a map spent encoding and committing its intermediate
files (the ``write`` span of ``mr/worker.write_intermediates``)."""

from layer_metrics._tasks import map_part_s


def read(obs):
    return map_part_s(obs, ("write",))
