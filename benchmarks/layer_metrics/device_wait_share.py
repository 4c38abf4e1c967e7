"""Percent of the job wall the engine spends blocked on the device:
``kernel_s`` (on a step's flags) plus ``device_wait_s`` (in
``block_until_ready`` on the step's packed result, before the copy).  Set
beside ``stream_device_idle``: what the host waits for against what the
device is busy with."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([
        100.0 * (p["kernel_s"] + p["device_wait_s"]) / p["wall_s"]
        for p in pipeline_stats(obs) if "device_wait_s" in p])
