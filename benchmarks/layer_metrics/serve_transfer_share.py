"""Percent of a served wave's wall in ``upload_s`` + ``pull_s`` of the
packed grep scheduler: the step's puts and its blocking reads, which hold
the device's own time too (nothing overlaps them)."""

from layer_metrics._serve import share_of_wall


def read(obs):
    return share_of_wall(obs, "serve_grep", "upload_s", "pull_s")
