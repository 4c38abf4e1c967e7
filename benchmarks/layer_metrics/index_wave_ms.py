"""Milliseconds of the wave walk's wall per wave: the ``indexer`` stage's
wall over the waves it walked (``plan_stage_walls['indexer']`` /
``stages.indexer.waves``): upload, the wave program, pull and host merge
of one document, as far as the window of two does not hide them."""

from layer_metrics._index import STAGE, job_median


def read(obs):
    return job_median(obs, lambda p: 1e3 * p["plan"]["plan_stage_walls"][STAGE]
                      / p["stages"][STAGE]["waves"])
