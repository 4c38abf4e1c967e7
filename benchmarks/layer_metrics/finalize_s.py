"""Seconds per job in the accumulator's final merge into the result
(``finalize`` span, ``finalize_s``): serial host work after the last step."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "finalize_s")
