"""Millions of groups the traced job committed (``agg_groups``: the keys
of the merged table, one line each)."""

from layer_metrics._agg import traced_stage


def read(obs):
    scope = traced_stage(obs)
    return scope["agg_groups"] / 1e6 if scope and "agg_groups" in scope \
        else None
