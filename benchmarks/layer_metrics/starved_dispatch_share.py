"""Percent of a job's root span starved (``starved_share``) under the spans
of the group ``dispatch`` (``starved_groups`` of ``pipeline_stats``): a step
on its way to the device: its upload, the call of its program, a relay
append."""

from layer_metrics._starved import group_share


def read(obs):
    return group_share(obs, "dispatch")
