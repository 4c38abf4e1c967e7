"""The fullest device's records over the mean, in the traced job
(``device_rows`` of a sort across a mesh): what the sample's device split
points cost the heaviest chip; 1.0 is an even split.  A count, read from
the traced job alone."""

from layer_metrics._sort import traced_sort


def read(obs):
    scope = traced_sort(obs) or {}
    rows = scope.get("device_rows")
    if "sort_exchange_rows" not in scope or not rows or not sum(rows):
        return None
    return max(rows) * len(rows) / sum(rows)
