"""Seconds per job the host is blocked on the device's ordering of the
resident store (``order`` span, ``order_s`` of the sort stage's scope)."""

from layer_metrics._sort import STAGE, job_median


def read(obs):
    return job_median(obs, lambda p: p["stages"][STAGE]["order_s"])
