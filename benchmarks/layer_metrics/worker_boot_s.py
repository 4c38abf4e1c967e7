"""Seconds from the device worker's ``main`` (``worker.start``) to its
backend being up (``backend_up``): its imports, the app's, and the TPU
runtime's start."""

from layer_metrics._tasks import launch_events


def read(obs):
    ups = launch_events(obs, "backend_up")
    if not ups:
        return None
    up = min(ups, key=lambda e: e["wall"])
    starts = [e["wall"] for e in launch_events(obs, "worker.start")
              if e["pid"] == up["pid"]]
    return up["wall"] - min(starts) if starts else None
