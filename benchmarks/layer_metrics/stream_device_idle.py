"""Idle share of the chip(s) in a few seconds of a stream job."""

from layer_metrics._common import device_idle as read  # noqa: F401
