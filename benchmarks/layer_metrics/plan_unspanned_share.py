"""Percent of a ``planrun`` job's root span that none of its direct children
covers (``job_s`` less ``job_children_s``, over ``job_s``: the children are
``start``, ``read``, one ``plan`` a stage, ``write`` and ``report``).  What
is here has no span yet.  A stream command's line has no ``plan`` group and
is ``host_unspanned_share``'s to read."""

from layer_metrics._starved import share_of_job


def _unspanned_s(p: dict) -> float:
    p["plan"]  # a line without the group is not a plan job's
    return p["job_s"] - p["job_children_s"]


def read(obs):
    return share_of_job(obs, _unspanned_s)
