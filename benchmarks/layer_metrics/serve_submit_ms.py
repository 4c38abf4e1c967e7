"""Median milliseconds of a ``submit`` span: the daemon's ``Submit``
handler, validation, the durable journal write (an fsync) and the reply."""

import statistics

from layer_metrics._serve import wave_median


def read(obs):
    return wave_median(
        obs, lambda w: statistics.median(w["spans"]["submit_ms"]))
