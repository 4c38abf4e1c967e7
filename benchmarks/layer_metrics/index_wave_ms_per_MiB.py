"""Device time of the wave program per MiB uploaded: the traced job's
device seconds in the modules that match ``idx_wave_step`` over its
``wave_chunk_bytes`` in MiB (padded bytes: what the program is given)."""

from layer_metrics._index import traced_walk, wave_seconds


def read(obs):
    seconds = wave_seconds(obs)
    if seconds is None:
        return None
    mib = traced_walk(obs)["wave_chunk_bytes"] / float(1 << 20)
    return 1e3 * seconds / mib if mib else None
