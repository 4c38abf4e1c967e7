"""Percent of a served wave's wall in ``take_s``: the ``take_row`` spans,
a lane's next row cut from its input at a newline on the scheduler
thread."""

from layer_metrics._serve import share_of_wall


def read(obs):
    return share_of_wall(obs, "serve_grep", "take_s")
