"""What the readers of a join job's metrics share.

A join job is a plan job (``_plan.py``) of one stage: ``pipeline_stats``
nests ``stages``, whose ``join`` entry is the join engine's scope: the
stream engines' ``steps`` (the probe's), ``kernel_s``, ``pull_s``,
``merge_s`` ... beside ``join_build_s``, ``join_probe_s`` and the
``join_*`` counters.  A program that prints no such entry or key has
nothing here to read, and every reader returns None.

The device programs are read from the traced job, each under its own
kernel block of the configuration (``join_probe``: the modules that match
``join_probe_step``; ``join_build``: ``join_build_step`` and
``join_build_order``).  A trace that holds fewer runs of a program than
the job made was cut before the job's end: its seconds are a part and the
counters the whole, so nothing is read from it.
"""

from __future__ import annotations

from typing import Callable, Optional

from layer_metrics import _common
from layer_metrics._index import job_median
from layer_metrics._plan import for_kernel

STAGE = "join"


def stage_median(obs: dict, value: Callable[[dict], Optional[float]]
                 ) -> Optional[float]:
    """Median over the whole jobs of ``value(the join stage's scope)``."""
    return job_median(obs, lambda p: value(p["stages"][STAGE]))


def traced_stage(obs: dict) -> Optional[dict]:
    """The stage's scope in the traced job; None without one (an untraced
    run, a rehearsal)."""
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    scope = (ps.get("stages") or {}).get(STAGE)
    return scope if isinstance(scope, dict) and "join_probe_rows" in scope \
        else None


def program_seconds(obs: dict, kernel: str, runs: Callable[[dict], int]
                    ) -> Optional[float]:
    """Device seconds of the modules of kernel block ``kernel`` in the
    traced job, if the trace holds the ``runs(scope)`` runs the job made
    of them."""
    scope = traced_stage(obs)
    found = _common.kernel_runs(for_kernel(obs, kernel))
    if not scope or not found or not runs(scope) \
            or found["runs"] < runs(scope):
        return None
    return found["seconds"]


def probe_seconds(obs: dict) -> Optional[float]:
    return program_seconds(obs, "join_probe", lambda s: s.get("steps", 0))


def build_seconds(obs: dict) -> Optional[float]:
    """Every build step and the one ordering (a job whose table was
    ordered again under another salt ran it more often)."""
    return program_seconds(obs, "join_build",
                           lambda s: s.get("join_build_steps", 0) + 1)


def shapes(obs: dict, kernel: str) -> dict:
    return _common.kernel(for_kernel(obs, kernel))["shapes"]
