"""Seconds per job in the index plan's second and third stages
(``plan_stage_walls['dftopk']`` + ``['join']``): the document-frequency
top-k and the postings join, whichever of them first needs the whole
table grouped (the ``group`` span) included."""

from layer_metrics._plan import plan_median


def read(obs):
    return plan_median(obs, lambda s: s["plan_stage_walls"]["dftopk"]
                       + s["plan_stage_walls"]["join"])
