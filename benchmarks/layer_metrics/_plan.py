"""What the readers of a plan job's metrics share.

A plan job's ``pipeline_stats`` nests: ``stages`` (stage name → that
engine's own ``pipeline_stats``), ``plan`` (the plan scope: ``plan_*`` and
``relay_*`` keys) and ``write_s``.  A program that prints no such line, or
a line without these groups or keys, has nothing here to read, and every
reader returns None.  The kernels of a plan job are several (one per
stage, and the relay's pack program); the configuration's ``kernels`` block
names them, and a reader says which one it reads."""

from __future__ import annotations

from typing import Callable, List, Optional

from layer_metrics import _common


def plan_scopes(obs: dict) -> List[dict]:
    """The plan scope of every whole job, with the job's wall."""
    return [dict(p["plan"], wall_s=p["wall_s"])
            for p in _common.pipeline_stats(obs)
            if isinstance(p.get("plan"), dict)]


def plan_median(obs: dict, value: Callable[[dict], Optional[float]]
                ) -> Optional[float]:
    """Median over the jobs of ``value(plan scope)``, leaving out the jobs
    where it is None or a key it needs is missing."""
    got = []
    for scope in plan_scopes(obs):
        try:
            v = value(scope)
        except (KeyError, TypeError, ZeroDivisionError):
            continue
        if v is not None:
            got.append(v)
    return _common.median_of(got)


def stage_wall_s(obs: dict, stage: str) -> Optional[float]:
    return plan_median(obs, lambda s: s["plan_stage_walls"][stage])


def for_kernel(obs: dict, name: str) -> dict:
    """``obs`` as the readers of ``_common`` want it, for the kernel
    ``name`` of the configuration's ``kernels`` block."""
    return {**obs, "traffic": {**obs.get("traffic", {}), "kernel": name}}
