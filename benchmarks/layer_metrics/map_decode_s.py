"""Median seconds of a map spent on the host's byte work between the read
and the write: padding the split for the kernel (``materialize``) and turning
bytes into records (``decode``: the ASCII check and decode, detokenizing or
line extraction, the app's ``KeyValue`` list)."""

from layer_metrics._tasks import map_part_s


def read(obs):
    return map_part_s(obs, ("materialize", "decode"))
