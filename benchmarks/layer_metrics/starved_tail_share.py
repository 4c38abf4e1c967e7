"""Percent of a job's root span starved (``starved_share``) under the spans
of the group ``tail`` (``starved_groups`` of ``pipeline_stats``): after the
last step: drain, finalize, write, report, and the root's own time."""

from layer_metrics._starved import group_share


def read(obs):
    return group_share(obs, "tail")
