"""Percent of device busy time in ``all-to-all`` ops."""

from layer_metrics._common import category_share


def read(obs):
    return category_share(obs, "all-to-all")
