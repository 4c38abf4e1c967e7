"""Percent of the job wall the engine spends putting step inputs on the
device (``upload_s`` of ``pipeline_stats``: host-blocked in the one
host-to-device put of a step's inputs, the chunk and one small array of
its lengths and line bases)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * p["upload_s"] / p["wall_s"]
                      for p in pipeline_stats(obs) if "upload_s" in p])
