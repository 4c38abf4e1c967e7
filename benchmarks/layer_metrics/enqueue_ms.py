"""Milliseconds a step to call the compiled step program and start its
async copies to the host (``enqueue_s`` of ``pipeline_stats`` over
``steps``): what a dispatch costs beside its upload."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([1e3 * p["enqueue_s"] / p["steps"]
                      for p in pipeline_stats(obs)
                      if "enqueue_s" in p and p.get("steps")])
