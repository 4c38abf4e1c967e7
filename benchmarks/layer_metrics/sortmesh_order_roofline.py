"""The ordering program on a mesh against the chip's memory roofline: the
least time to order a device's records (``roofline_sort.order_bytes`` over
the mean of the traced job's ``device_rows``, over the HBM peak) as a
share of the device seconds the module that matches ``sort_order`` took on
a device (the trace reduction averages a module over the devices)."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    import roofline_sort

    seconds = program_seconds(obs, "sort_order", None)
    rows = (traced_sort(obs) or {}).get("device_rows")
    if not seconds or not rows or "peaks" not in obs:
        return None
    least = roofline_sort.order_bytes(dict(
        shapes(obs, "sort_order"), records=sum(rows) / len(rows)))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
