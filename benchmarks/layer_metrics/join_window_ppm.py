"""Probe rows inside the date window per million read, in the traced job
(``join_window_rows`` over ``join_probe_rows``): the share of the scan the
match sees, so that a reader of the cell's other numbers knows which mix
produced them.  A count, read from the traced job alone."""

from layer_metrics._join import traced_stage


def read(obs):
    scope = traced_stage(obs) or {}
    rows = scope.get("join_probe_rows")
    return 1e6 * scope["join_window_rows"] / rows if rows else None
