"""What the readers of a sort job's metrics share.

A sort job is a plan job (``_plan.py``): its ``pipeline_stats`` nests
``stages``, whose ``sample`` and ``sort`` entries are the two stages' own
scopes (``sample_s``, ``sort_sample_keys``; ``steps``, ``order_s``,
``sort_records``, ``sort_resident_bytes``, ``sort_partition_rows``, beside
the phase keys the shared pipeline gives every engine), ``plan`` and, at
the top, the commit's ``pull_s``, ``d2h_s``, ``write_commit_s`` and
``write_s``.  A program that prints no such entry or key has nothing here
to read, and every reader returns None.

The two device programs are read from the traced job, each under its own
kernel block of the configuration (``sort_ingest``, ``sort_order``).  A
trace that holds fewer runs of a program than the job made was cut before
the job's end: its seconds are a part and the counters the whole, so
nothing is read from it.
"""

from __future__ import annotations

from typing import Optional

from layer_metrics import _common
from layer_metrics._index import job_median  # noqa: F401  (the readers')
from layer_metrics._plan import for_kernel

STAGE = "sort"


def traced_sort(obs: dict) -> Optional[dict]:
    """The sort stage's scope in the traced job; None without one (an
    untraced run, a rehearsal)."""
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    scope = (ps.get("stages") or {}).get(STAGE)
    return scope if isinstance(scope, dict) else None


def program_seconds(obs: dict, kernel: str, runs_key: Optional[str]
                    ) -> Optional[float]:
    """Device seconds of one of the job's programs in the traced job, if
    the trace holds all of its runs: ``runs_key`` names the scope's count
    of them (``steps``), None stands for one run a job."""
    scope = traced_sort(obs)
    runs = _common.kernel_runs(for_kernel(obs, kernel))
    if not scope or not runs:
        return None
    want = scope.get(runs_key) if runs_key else 1
    if not want or runs["runs"] < want:
        return None
    return runs["seconds"]


def shapes(obs: dict, kernel: str) -> dict:
    return _common.kernel(for_kernel(obs, kernel))["shapes"]
