"""Seconds per job before the first step (``start`` span of the stream
commands, ``start_s``): the device gate, the mesh, the engine's
construction up to the pipeline armed."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "start_s")
