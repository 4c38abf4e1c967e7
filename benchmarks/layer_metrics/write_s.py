"""Seconds per job writing the partitioned ``mr-out-*`` (``write`` span of
``cli/wcstream.py``, ``write_s``)."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "write_s")
