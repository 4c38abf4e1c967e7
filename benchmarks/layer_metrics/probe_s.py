"""Seconds ``mrrun`` spends deciding which workers get a chip: the
``probe`` span around ``plan_device_workers``, a whole JAX start in a child
that exits."""

from layer_metrics._tasks import launch_events


def read(obs):
    probes = [e for e in launch_events(obs, "probe") if e["ph"] == "X"]
    return sum(e["dur"] for e in probes) if probes else None
