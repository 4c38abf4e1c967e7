"""Median over a wave's jobs of ``queue_wait_s``: seconds from a job's
submission to the first row a packer took from it."""

import statistics

from layer_metrics._serve import wave_median


def read(obs):
    return wave_median(obs, lambda w: statistics.median(
        j["stats"]["queue_wait_s"] for j in w["jobs"]))
