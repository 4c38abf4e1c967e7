"""Megabytes of the store that stays on the device from the first step
to the ordering, in the traced job (``sort_resident_bytes``: the records
and their key lanes, unpadded).  A count, read from the traced job alone."""

from layer_metrics._sort import traced_sort


def read(obs):
    held = (traced_sort(obs) or {}).get("sort_resident_bytes")
    return None if held is None else held / 1e6
