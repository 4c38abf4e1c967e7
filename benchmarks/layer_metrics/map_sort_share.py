"""Percent of the device worker's busy time in sort ops."""

from layer_metrics._common import category_share


def read(obs):
    return category_share(obs, "sort")
