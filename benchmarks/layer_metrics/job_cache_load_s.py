"""Seconds a job's device worker spends loading programs from the compile
cache (JAX's own ``cache_retrieval_time_sec``), median over the jobs."""

from layer_metrics._common import median_of


def read(obs):
    return median_of([j["cache_load_s"] for j in obs["jobs"]
                      if "cache_load_s" in j and j.get("hook")])
