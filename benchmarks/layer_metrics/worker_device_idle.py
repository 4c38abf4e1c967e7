"""Idle share of the chip while a batch job's device worker holds it: the
traced window runs from the worker's backend coming up to its exit (or the
configuration's ``trace_seconds``)."""

from layer_metrics._common import device_idle as read  # noqa: F401
