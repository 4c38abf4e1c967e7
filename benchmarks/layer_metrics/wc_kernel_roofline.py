"""The batch map kernel against the chip's memory roofline
(``benchmarks/roofline.py``: least bytes over peak bandwidth over measured
device time per run)."""

from layer_metrics._common import roofline_share as read  # noqa: F401
