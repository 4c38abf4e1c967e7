"""Device time of the join's probe step per MiB of the probe side: the
traced job's device seconds in the modules that match ``join_probe_step``
over its steps' chunks in MiB."""

from layer_metrics._join import probe_seconds, shapes, traced_stage


def read(obs):
    seconds = probe_seconds(obs)
    if seconds is None:
        return None
    mib = traced_stage(obs)["steps"] * shapes(obs, "join_probe")[
        "input_bytes"] / float(1 << 20)
    return 1e3 * seconds / mib if mib else None
