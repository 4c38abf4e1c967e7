"""The wave program against the chip's memory roofline: the least time
for the traced job's waves (``roofline_index.wave_bytes`` over the sizes
its ``waves_by_size`` reports, over the HBM peak) as a share of the device
seconds the modules that match ``idx_wave_step`` took."""

from layer_metrics._common import kernel
from layer_metrics._index import traced_walk, wave_seconds
from layer_metrics._plan import for_kernel


def read(obs):
    import roofline_index

    seconds = wave_seconds(obs)
    if not seconds or "peaks" not in obs:
        return None
    least = roofline_index.wave_bytes(
        kernel(for_kernel(obs, "idx_wave"))["shapes"],
        traced_walk(obs)["waves_by_size"])
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / seconds
