"""Device time of the aggregation's step program per MiB of rows: the
traced job's device seconds in the modules that match ``mapreduce_step``
over its steps' chunks in MiB."""

from layer_metrics._agg import shapes, step_seconds, traced_stage


def read(obs):
    seconds = step_seconds(obs)
    if seconds is None:
        return None
    mib = traced_stage(obs)["steps"] * shapes(obs)["input_bytes"] / float(
        1 << 20)
    return 1e3 * seconds / mib if mib else None
