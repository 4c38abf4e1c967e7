"""What the readers of an aggregation job's metrics share.

An aggregation job is a plan job (``_plan.py``) of one stage:
``pipeline_stats`` nests ``stages``, whose ``agg`` entry is the stream
engine's own scope (``steps``, ``pull_s``, ``merge_s``, ``compact_s``,
``merge_rows_in`` ... as a word count's) with ``agg_rows``, ``agg_groups``
and ``agg_value_lanes``.  A program that prints no such entry or key has
nothing here to read, and every reader returns None.

The step program is read from the traced job under the configuration's
kernel block ``agg_step``.  A trace that holds fewer runs of it than the
job dispatched steps was cut before the job's end: its seconds are a part
and the counters the whole, so nothing is read from it.
"""

from __future__ import annotations

from typing import Callable, Optional

from layer_metrics import _common
from layer_metrics._index import job_median
from layer_metrics._plan import for_kernel

STAGE = "agg"
KERNEL = "agg_step"


def stage_median(obs: dict, value: Callable[[dict], Optional[float]]
                 ) -> Optional[float]:
    """Median over the whole jobs of ``value(the agg stage's scope)``."""
    return job_median(obs, lambda p: value(p["stages"][STAGE]))


def traced_stage(obs: dict) -> Optional[dict]:
    """The stage's scope in the traced job; None without one (an untraced
    run, a rehearsal)."""
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    scope = (ps.get("stages") or {}).get(STAGE)
    return scope if isinstance(scope, dict) and "agg_rows" in scope else None


def step_seconds(obs: dict) -> Optional[float]:
    """Device seconds of the step program in the traced job, if the trace
    holds every step."""
    scope = traced_stage(obs)
    runs = _common.kernel_runs(for_kernel(obs, KERNEL))
    if not scope or not runs or not scope.get("steps") \
            or runs["runs"] < scope["steps"]:
        return None
    return runs["seconds"]


def shapes(obs: dict) -> dict:
    return _common.kernel(for_kernel(obs, KERNEL))["shapes"]
