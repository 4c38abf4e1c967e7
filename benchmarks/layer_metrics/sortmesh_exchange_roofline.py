"""The exchange step against the chip's rooflines: the least time for the
traced job's steps (``roofline_sortmesh.exchange_least_s`` a step: the
larger of a device's memory time and its interconnect time) as a share of
the device seconds the modules that match ``sort_exchange_step`` took on a
device."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    import roofline_sortmesh

    seconds = program_seconds(obs, "sort_exchange", "steps")
    if not seconds or "peaks" not in obs:
        return None
    least = traced_sort(obs)["steps"] * roofline_sortmesh.exchange_least_s(
        shapes(obs, "sort_exchange"), obs["peaks"])
    return 100.0 * least / seconds
