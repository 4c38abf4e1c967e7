"""The aggregation's step program against the chip's memory roofline: the
least time for the traced job's steps (``roofline_agg.step_bytes``: every
chunk read once, every row of every step's table written once, over the
HBM peak) as a share of the device seconds the modules that match
``mapreduce_step`` took."""

from layer_metrics._agg import shapes, step_seconds, traced_stage


def read(obs):
    import roofline_agg

    seconds = step_seconds(obs)
    if not seconds or "peaks" not in obs:
        return None
    scope = traced_stage(obs)
    least = roofline_agg.step_bytes(dict(
        shapes(obs), steps=scope["steps"], table_rows=scope["merge_rows_in"]))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
