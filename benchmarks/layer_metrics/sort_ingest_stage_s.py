"""Seconds per job taking the records up: the sort stage's wall
(``plan_stage_walls['sort']``) less its ``order_s``: the shared
pipeline's ``materialize`` / ``dispatch`` / ``finish`` over the job's
steps, from the first read to the last step retired."""

from layer_metrics._sort import STAGE, job_median


def read(obs):
    return job_median(obs, lambda p: p["plan"]["plan_stage_walls"][STAGE]
                      - p["stages"][STAGE]["order_s"])
