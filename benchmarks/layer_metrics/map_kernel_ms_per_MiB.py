"""Device time of one map program run per MiB of its split."""

from layer_metrics._common import kernel_ms_per_mib as read  # noqa: F401
