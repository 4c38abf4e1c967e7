"""Megabytes of the join's table that stay on the device for the probe,
in the traced job (``join_table_bytes``: the ordered rows and their
hashes, unpadded).  A count, read from the traced job alone."""

from layer_metrics._join import traced_stage


def read(obs):
    held = (traced_stage(obs) or {}).get("join_table_bytes")
    return None if held is None else held / 1e6
