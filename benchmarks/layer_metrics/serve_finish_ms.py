"""Median milliseconds of a ``finish`` span: a job's finalize and the
durable write of its output and of its journal record."""

import statistics

from layer_metrics._serve import wave_median


def read(obs):
    return wave_median(
        obs, lambda w: statistics.median(w["spans"]["finish_ms"]))
