"""Percent of the job wall the engine waits for the next input batch
(``batch_wait_s`` of ``pipeline_stats``: the consumer starved of input by
the reader and the batcher thread)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * p["batch_wait_s"] / p["wall_s"]
                      for p in pipeline_stats(obs) if "batch_wait_s" in p])
