"""Job wall over steps: what one stream step costs end to end (median over
the window's jobs)."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([1e3 * p["wall_s"] / p["steps"]
                      for p in pipeline_stats(obs) if p.get("steps")])
