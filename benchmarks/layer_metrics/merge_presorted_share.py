"""Percent of the batches handed to the host accumulator that arrived as
runs (rows that strictly increase: a device's step table as the step
program leaves it) and were merged without a sort: 100 · (1 −
``merge_runs_unsorted`` ÷ ``merge_runs_in``) of the traced job's
``pipeline_stats``.  A count, not a time; every job of a run reads the
same corpus and counts the same, so the traced job stands for them.  100
where the accumulator never sorts what the device already sorted.  A run
without a traced job (an untraced run, a rehearsal), and a program that
counts no runs, have nothing here to read."""


def read(obs):
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    runs, unsorted = ps.get("merge_runs_in"), ps.get("merge_runs_unsorted")
    if not runs or unsorted is None:
        return None
    return 100.0 * (1.0 - unsorted / runs)
