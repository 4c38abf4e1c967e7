"""Percent of a job's root span through which its main thread had given the
chip nothing to do, or the chip ran out (``starved_s`` over ``job_s`` of
``pipeline_stats``: the starvation account, an upper bound of the chip's
idle time as the host can see it; what is idle between the ops of one
program it cannot).  Median over the run's whole jobs, traced or not."""

from layer_metrics._starved import share_of_job


def read(obs):
    return share_of_job(obs, lambda p: p["starved_s"])
