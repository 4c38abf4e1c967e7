"""The largest partition's records over the mean, in the traced job
(``sort_partition_rows``): what the 100,000-key sample's split points
cost the heaviest reducer; 1.0 is an even split.  A count, read from the
traced job alone, so a run without one (an untraced run, a rehearsal) has
nothing here to read."""

from layer_metrics._sort import traced_sort


def read(obs):
    rows = (traced_sort(obs) or {}).get("sort_partition_rows")
    if not rows or not sum(rows):
        return None
    return max(rows) * len(rows) / sum(rows)
