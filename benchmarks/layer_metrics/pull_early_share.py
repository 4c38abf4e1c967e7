"""Percent of a word-count stream job's step pulls that were served by the
tensor packed when the step was dispatched, and so waited for the step's
own device time and not behind the next step's kernel: 100 ·
``pulls_early`` ÷ ``step_pulls`` of the traced job's ``pipeline_stats``.
The rest (``pulls_late``) were packed at retirement: a step whose table
outgrew the predicted prefix, or a replay's payload.  A count, not a
time; every job of a run reads the same corpus and counts the same, so
the traced job stands for them.  A run without a traced job (an untraced
run, a rehearsal), a job that pulled no step table, and a program that
does not count its pulls by kind, have nothing here to read."""


def read(obs):
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    pulls, early = ps.get("step_pulls"), ps.get("pulls_early")
    if not pulls or early is None:
        return None
    return 100.0 * early / pulls
