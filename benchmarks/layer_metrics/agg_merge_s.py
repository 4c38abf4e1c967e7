"""Seconds per job merging the steps' tables into the table of groups:
the ``merge`` spans of the ``agg`` stage and the compactions that fall
outside them, at the job's end (``merge_s`` + ``finalize_s``)."""

from layer_metrics._agg import stage_median


def read(obs):
    return stage_median(obs, lambda s: s["merge_s"] + s["finalize_s"])
