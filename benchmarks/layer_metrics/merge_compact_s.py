"""Seconds per job in the host accumulator's compactions (``compact``
spans of ``parallel/merge.py``, ``compact_s``): every buffered row sorted
and merged again, inside the merge, the finalize or a table sync."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "compact_s")
