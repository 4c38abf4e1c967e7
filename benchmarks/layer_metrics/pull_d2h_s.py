"""Seconds per job in the device-to-host copy of the steps' results
(``d2h_s``), the part of ``pull_s`` that is not waiting for the device."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "d2h_s")
