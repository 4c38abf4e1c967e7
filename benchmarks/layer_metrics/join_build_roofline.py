"""The join's build against the chip's memory roofline: the least time to
read the traced job's build side once and write every row of its table
once (``roofline_join.build_bytes``, over the HBM peak) as a share of the
device seconds the modules that match ``join_build_step`` and
``join_build_order`` took."""

from layer_metrics._join import build_seconds, shapes, traced_stage


def read(obs):
    import roofline_join

    seconds = build_seconds(obs)
    if not seconds or "peaks" not in obs:
        return None
    scope = traced_stage(obs)
    least = roofline_join.build_bytes(dict(
        shapes(obs, "join_build"), build_rows=scope["join_build_rows"],
        build_bytes=scope["join_build_bytes"]))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
