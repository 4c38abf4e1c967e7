"""What the readers of an index job's metrics share.

An index job is a plan job (``_plan.py``): its ``pipeline_stats`` nests
``stages``, whose ``indexer`` entry is the wave walk's own scope (``waves``,
``waves_by_size``, ``wave_doc_bytes``, ``wave_chunk_bytes``, ``group_s``,
``postings_rows``, ``index_terms``, beside the phase keys every engine
has).  A program that prints no such entry or key has nothing here to
read, and every reader returns None.

The wave program's device time is read from the traced job, whose waves
come in several chunk sizes under one module name: so these readers take
the module's seconds whole and divide by what the traced job's own
counters say it uploaded, where ``_common.kernel_ms_per_mib`` and
``roofline_share`` take one ``input_bytes`` a run.  A trace that holds
fewer runs of the module than the job dispatched waves was cut before the
job's end: its seconds are a part of the walk's and the counters the
whole, so nothing is read from it.
"""

from __future__ import annotations

from typing import Callable, Optional

from layer_metrics import _common
from layer_metrics._plan import for_kernel

STAGE = "indexer"


def job_median(obs: dict, value: Callable[[dict], Optional[float]]
               ) -> Optional[float]:
    """Median over the whole jobs of ``value(the job's pipeline_stats)``,
    leaving out the jobs where it is None or a key it needs is missing."""
    got = []
    for p in _common.pipeline_stats(obs):
        try:
            v = value(p)
        except (KeyError, TypeError, ZeroDivisionError):
            continue
        if v is not None:
            got.append(v)
    return _common.median_of(got)


def traced_walk(obs: dict) -> Optional[dict]:
    """The walk's scope in the traced job; None without one (an untraced
    run, a rehearsal)."""
    ps = (obs.get("traced_job") or {}).get("pipeline_stats") or {}
    walk = (ps.get("stages") or {}).get(STAGE)
    return walk if isinstance(walk, dict) else None


def wave_seconds(obs: dict) -> Optional[float]:
    """Device seconds of the wave program in the traced job, if the trace
    holds the walk whole."""
    walk = traced_walk(obs)
    runs = _common.kernel_runs(for_kernel(obs, "idx_wave"))
    if not walk or not runs:
        return None
    waves = sum((walk.get("waves_by_size") or {}).values())
    if not waves or runs["runs"] < waves:
        return None
    return runs["seconds"]
