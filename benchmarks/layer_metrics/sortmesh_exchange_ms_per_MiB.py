"""Device time of the exchange step per MiB a device took up: the traced
job's device seconds in the modules that match ``sort_exchange_step``
(averaged over the devices, as the trace reduction averages a module) over
its steps' chunks, one a device, in MiB."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    seconds = program_seconds(obs, "sort_exchange", "steps")
    if seconds is None:
        return None
    mib = (traced_sort(obs)["steps"]
           * shapes(obs, "sort_exchange")["input_bytes"] / float(1 << 20))
    return 1e3 * seconds / mib if mib else None
