"""What the readers of the starvation account share.

``wcstream``, ``grepstream`` and ``planrun`` print the account of their main
thread at the top level of ``pipeline_stats`` (``dsi_tpu/obs/trace.py``, "The
starvation account"): ``starved_s`` (seconds of ``job_s`` in pieces through
which the chip had nothing queued, ran out, or began with nothing),
``starved_dry_s``, ``starved_by`` and ``starved_groups`` (``input``,
``dispatch``, ``merge``, ``tail``: the four sum to ``starved_s``).  Every
whole job of the run prints it, traced or not, and a reader gives the median
over them.  A program that prints no such key, as those before PR 51, has
nothing here to read."""

from __future__ import annotations

from typing import Callable, Optional

from layer_metrics._common import median_of, pipeline_stats


def share_of_job(obs: dict, seconds: Callable[[dict], float]
                 ) -> Optional[float]:
    """Median over the whole jobs of ``100 * seconds(stats) / job_s``,
    leaving out the jobs whose line lacks a key it needs."""
    got = []
    for p in pipeline_stats(obs):
        try:
            got.append(100.0 * seconds(p) / p["job_s"])
        except (KeyError, TypeError, ZeroDivisionError):
            continue
    return median_of(got)


def group_share(obs: dict, group: str) -> Optional[float]:
    return share_of_job(obs, lambda p: p["starved_groups"][group])
