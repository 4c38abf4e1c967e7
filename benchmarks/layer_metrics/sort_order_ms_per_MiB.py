"""Device time of the ordering program per MiB of records: the traced
job's device seconds in the module that matches ``sort_order`` over its
``sort_records`` in MiB."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    seconds = program_seconds(obs, "sort_order", None)
    if seconds is None:
        return None
    mib = (traced_sort(obs)["sort_records"]
           * shapes(obs, "sort_order")["record_bytes"] / float(1 << 20))
    return 1e3 * seconds / mib if mib else None
