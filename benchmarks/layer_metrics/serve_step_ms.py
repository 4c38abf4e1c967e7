"""Milliseconds of a served wave's wall per packed grep step: what a step
costs end to end on the daemon's one scheduler thread, row cut, put, run,
read, fold, checkpoints, evictions and resumes included."""

from layer_metrics._serve import wave_median


def read(obs):
    return wave_median(obs, lambda w: 1e3 * w["wall_s"]
                       / w["stats"]["serve_grep"]["packed_steps"])
