"""The line-flag kernel against the chip's memory roofline."""

from layer_metrics._common import roofline_share as read  # noqa: F401
