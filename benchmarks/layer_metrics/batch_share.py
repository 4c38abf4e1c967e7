"""Percent of the job wall the batcher thread is busy cutting the next
batch (``batch_s`` of ``pipeline_stats``, the ``materialize`` spans of the
producer thread).  Read only where the program keeps the job's account
(``job_s``): beside the main thread's shares it says how far the two
threads want the interpreter at once."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * p["batch_s"] / p["wall_s"]
                      for p in pipeline_stats(obs)
                      if "batch_s" in p and "job_s" in p])
