"""Device time of one step program run per MiB of its per-device chunk."""

from layer_metrics._common import kernel_ms_per_mib as read  # noqa: F401
