"""Percent of a job's root span that none of its direct children covers
(``job_s`` less ``job_children_s``, over ``job_s``, of ``pipeline_stats``:
the self time of the ``job`` span on the stream command's main thread,
whose children are its start, the step loop's wait, dispatch and finish,
the end-of-stream drain, finalize and write).  What is here has no span
yet."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([100.0 * (p["job_s"] - p["job_children_s"])
                      / p["job_s"] for p in pipeline_stats(obs)
                      if p.get("job_s") and "job_children_s" in p])
