"""Seconds the warm-up spends loading programs from the compile cache
(JAX's own ``cache_retrieval_time_sec``), in the harness process."""


def read(obs):
    warm = obs.get("warm_up")
    return warm["jax"]["cache_load_s"] if warm else None
