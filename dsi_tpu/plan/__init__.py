"""dsi_tpu.plan — multi-stage dataflow plans without the host round-trip.

Dean & Ghemawat's production MapReduce was a *sequence* of jobs (the
indexing pipeline, OSDI'04 §6.4); this package chains this repo's
engines so stage N+1's upload IS stage N's device-resident output:

* :mod:`~dsi_tpu.plan.graph`  — the :class:`Plan`/:class:`Stage` DAG
  model (+ the canonical chains: grep → wordcount-over-matches,
  indexer → df-top-k → postings join, sample → range sort, and the
  one-stage aggregation);
* :mod:`~dsi_tpu.plan.driver` — :func:`run_plan`, driving each stage as
  a resumable step object with relay handoffs
  (``device/relay.py``), stage-manifest commits through ``ckpt/``, and
  resume-at-the-last-completed-stage semantics.

CLI entry point: ``python -m dsi_tpu.cli.planrun``.  DESIGN.md "Plan
layer" documents the graph model, handoff rules, commit protocol, and
blind spots.
"""

from dsi_tpu.plan.graph import (
    STAGE_KINDS,
    Plan,
    PlanError,
    Stage,
    agg_plan,
    grep_cascade_plan,
    grep_wordcount_plan,
    indexer_join_plan,
    join_plan,
    sort_plan,
    wordcount_topk_plan,
)
from dsi_tpu.plan.driver import (
    PlanHostPath,
    PlanResult,
    run_plan,
)

__all__ = [
    "STAGE_KINDS",
    "Plan",
    "PlanError",
    "PlanHostPath",
    "PlanResult",
    "Stage",
    "agg_plan",
    "grep_cascade_plan",
    "grep_wordcount_plan",
    "indexer_join_plan",
    "join_plan",
    "run_plan",
    "sort_plan",
    "wordcount_topk_plan",
]
