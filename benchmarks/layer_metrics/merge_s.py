"""Host merge seconds per job (``merge_s`` of ``pipeline_stats``)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([p["merge_s"] for p in pipeline_stats(obs)])
