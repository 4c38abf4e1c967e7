"""Median duration of the traced job's ``worker.map`` spans."""

from layer_metrics._common import median_of, span_events


def read(obs):
    return median_of([e["dur"] for e in span_events(obs, "worker.map")])
