"""Megabytes that stage 1 handed to stage 2 through the relay in the
traced job (``plan_handoff_bytes``: the content of the records that
passed).  A count, not a time; every job of a run reads the same corpus,
so they all hand over the same bytes, and the traced one is the job whose
pack runs the device trace shows.  A run without a traced job (an
untraced run, a rehearsal) has nothing here to read."""


def read(obs):
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    handed = (ps.get("plan") or {}).get("plan_handoff_bytes")
    return None if handed is None else handed / 1e6
