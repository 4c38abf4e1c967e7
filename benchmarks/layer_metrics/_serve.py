"""What the readers of a served wave's metrics share.

A job of a ``serve_child`` cell is one wave; its record holds ``serve``:
``wall_s`` (first submit to last ``done`` seen), ``stats`` (the daemon's
``Status`` ``stats`` section after the wave minus before it: the scopes
``serve_grep``, ``serve`` and ``daemon``), ``jobs`` (the wave's job
records, each with the daemon's own ``stats`` of it) and, once the daemon
has stopped, ``spans`` (the milliseconds of the ``submit`` and ``finish``
spans that fell inside the wave).  A program whose daemon reports none of
this has nothing here to read, and every reader returns None.  Each
metric is the median over the run's whole waves of one value a wave."""

from __future__ import annotations

import statistics
from typing import Callable, List, Optional

from layer_metrics._common import median_of


def waves(obs: dict) -> List[dict]:
    """``serve`` of every whole wave of the run."""
    return [j["serve"] for j in obs.get("jobs", [])
            if isinstance(j.get("serve"), dict) and not j.get("problems")]


def wave_median(obs: dict, value: Callable[[dict], Optional[float]]
                ) -> Optional[float]:
    """Median over the waves of ``value(serve)``, leaving out the waves
    where it is None or something it needs is missing."""
    got = []
    for wave in waves(obs):
        try:
            v = value(wave)
        except (KeyError, TypeError, ZeroDivisionError,
                statistics.StatisticsError):
            continue
        if v is not None:
            got.append(v)
    return median_of(got)


def share_of_wall(obs: dict, scope: str, *keys: str) -> Optional[float]:
    """Percent of a wave's wall in the summed seconds of ``keys``."""
    return wave_median(obs, lambda w: 100.0 * sum(
        w["stats"][scope][k] for k in keys) / w["wall_s"])
