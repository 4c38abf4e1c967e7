"""Percent of a job's root span starved (``starved_share``) under the spans
of the group ``input`` (``starved_groups`` of ``pipeline_stats``): before a
step can be cut: the start, the reads, the step loop's wait for its
producer, a stage's construction."""

from layer_metrics._starved import group_share


def read(obs):
    return group_share(obs, "input")
