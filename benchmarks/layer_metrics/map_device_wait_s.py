"""Median seconds of a map spent on the device's side of it: ``upload`` of
the padded split, ``kernel`` (dispatch to the first blocking scalar read,
every attempt) and ``pull`` of the result arrays."""

from layer_metrics._tasks import map_part_s


def read(obs):
    return map_part_s(obs, ("upload", "kernel", "pull"))
