"""Percent of the job wall inside the pipeline's ``dispatch`` spans
(``dispatch_s`` of ``pipeline_stats``: a step's upload, the call of its
program, the copy starts, and the Python between them)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * p["dispatch_s"] / p["wall_s"]
                      for p in pipeline_stats(obs) if "dispatch_s" in p])
