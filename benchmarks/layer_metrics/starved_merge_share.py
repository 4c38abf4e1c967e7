"""Percent of a job's root span starved (``starved_share``) under the spans
of the group ``merge`` (``starved_groups`` of ``pipeline_stats``): a step's
retirement and the host's part of the device services: merge, compact,
replay, fold, sync, group, checkpoint."""

from layer_metrics._starved import group_share


def read(obs):
    return group_share(obs, "merge")
