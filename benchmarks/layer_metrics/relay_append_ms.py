"""Milliseconds of host time per handoff: the ``relay_append`` spans of a
job (``relay_append_s``: a put of the offset and the dispatch of the pack
program, or a seal) over its appends (``relay_appends``)."""

from layer_metrics._plan import plan_median


def read(obs):
    return plan_median(
        obs, lambda s: 1e3 * s["relay_append_s"] / s["relay_appends"])
