"""The ingest step against the chip's memory roofline: the least time
for the traced job's steps (``roofline_sort.ingest_bytes`` a step, over
the HBM peak) as a share of the device seconds the modules that match
``sort_ingest_step`` took."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    import roofline_sort

    seconds = program_seconds(obs, "sort_ingest", "steps")
    if not seconds or "peaks" not in obs:
        return None
    least = traced_sort(obs)["steps"] * roofline_sort.ingest_bytes(
        shapes(obs, "sort_ingest"))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
