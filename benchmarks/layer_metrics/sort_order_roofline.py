"""The ordering program against the chip's memory roofline: the least
time to order the traced job's ``sort_records``
(``roofline_sort.order_bytes``, over the HBM peak) as a share of the
device seconds the module that matches ``sort_order`` took."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    import roofline_sort

    seconds = program_seconds(obs, "sort_order", None)
    if not seconds or "peaks" not in obs:
        return None
    least = roofline_sort.order_bytes(dict(
        shapes(obs, "sort_order"),
        records=traced_sort(obs)["sort_records"]))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
