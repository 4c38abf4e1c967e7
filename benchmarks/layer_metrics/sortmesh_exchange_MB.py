"""Megabytes of records that crossed the mesh in the traced job
(``sort_exchange_bytes``: 100 bytes a record whose owner is not the device
that read it, counted on the device).  A count, read from the traced job
alone, so a run without one (an untraced run, a rehearsal) has nothing
here to read."""

from layer_metrics._sort import traced_sort


def read(obs):
    crossed = (traced_sort(obs) or {}).get("sort_exchange_bytes")
    return None if crossed is None else crossed / 1e6
