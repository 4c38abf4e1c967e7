"""Evictions a wave: resident jobs parked to their checkpoint chains so
that a queued one gets a turn.  A count, read in the traced run (a
rehearsal's waves print theirs in their ``job`` lines)."""

from layer_metrics._serve import wave_median


def read(obs):
    if not obs.get("traced_job"):
        return None
    return wave_median(obs, lambda w: w["stats"]["daemon"]["evictions"])
