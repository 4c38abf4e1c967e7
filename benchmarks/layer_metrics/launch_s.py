"""``mrrun`` spawn (harness clock) to the coordinator handing out the first
map task, which is the device worker up and asking.  The worker's own first
``worker.map`` span cannot date this: its start is clamped to the lazily
built tracer's epoch.  The coordinator's ``assign`` instant is the same
moment seen from the other end of the RPC."""

from layer_metrics._common import span_events


def read(obs):
    assigns = span_events(obs, "assign", kind="map")
    if not assigns:
        return None
    return min(e["wall"] for e in assigns) - obs["traced_job"]["spawn_wall"]
