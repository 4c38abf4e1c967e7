"""First reduce task handed out to last reduce task completed, on the
coordinator's clock."""

from layer_metrics._common import span_events


def read(obs):
    assigns = span_events(obs, "assign", kind="reduce")
    completes = span_events(obs, "complete", kind="reduce")
    if not assigns or not completes:
        return None
    return max(e["wall"] for e in completes) - min(e["wall"] for e in assigns)
