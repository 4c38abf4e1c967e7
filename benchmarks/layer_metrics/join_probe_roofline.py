"""The join's probe step against the chip's memory roofline: the least
time for the traced job's probe steps (``roofline_join.probe_bytes``:
every chunk read once, the keys and values of every row inside the window
written once, over the HBM peak) as a share of the device seconds the
modules that match ``join_probe_step`` took."""

from layer_metrics._join import probe_seconds, shapes, traced_stage


def read(obs):
    import roofline_join

    seconds = probe_seconds(obs)
    if not seconds or "peaks" not in obs:
        return None
    scope = traced_stage(obs)
    least = roofline_join.probe_bytes(dict(
        shapes(obs, "join_probe"), steps=scope["steps"],
        window_rows=scope["join_window_rows"]))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
