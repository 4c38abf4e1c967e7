"""Median gap on a device worker between the end of one task span
(``worker.map``, ``worker.reduce``) and the start of its next: the
completion call, the next request and whatever else the loop does between
two tasks."""

from layer_metrics._common import median_of, span_events
from layer_metrics._tasks import device_maps


def read(obs):
    gaps = []
    for pid in {m["pid"] for m in device_maps(obs)}:
        tasks = sorted((e for name in ("worker.map", "worker.reduce")
                        for e in span_events(obs, name) if e["pid"] == pid),
                       key=lambda e: e["wall"])
        gaps += [1e3 * (b["wall"] - a["wall"] - a["dur"])
                 for a, b in zip(tasks, tasks[1:])]
    return median_of(gaps)
