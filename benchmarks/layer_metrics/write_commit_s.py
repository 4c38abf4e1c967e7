"""Seconds per job in the durable commits of ``mr-out-*`` (``commit``
spans inside ``write``, ``write_commit_s``: each partition's write,
flush, fsync and rename, apart from the sort and the formatting)."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "write_commit_s")
