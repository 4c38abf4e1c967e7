"""Percent of the job wall the engine spends blocked on result pulls and
widen replays (``pull_s + replay_s`` of ``pipeline_stats``: host-blocked
time, not device time)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * (p["pull_s"] + p["replay_s"]) / p["wall_s"]
                      for p in pipeline_stats(obs)])
