"""Device time of the ingest step per MiB uploaded: the traced job's
device seconds in the modules that match ``sort_ingest_step`` over its
steps' chunks in MiB."""

from layer_metrics._sort import program_seconds, shapes, traced_sort


def read(obs):
    seconds = program_seconds(obs, "sort_ingest", "steps")
    if seconds is None:
        return None
    mib = (traced_sort(obs)["steps"]
           * shapes(obs, "sort_ingest")["input_bytes"] / float(1 << 20))
    return 1e3 * seconds / mib if mib else None
