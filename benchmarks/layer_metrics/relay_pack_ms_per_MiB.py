"""Device time of one run of the relay's pack program per MiB of its row
(module ``relay_pack`` in the device trace)."""

from layer_metrics._common import kernel_ms_per_mib
from layer_metrics._plan import for_kernel


def read(obs):
    return kernel_ms_per_mib(for_kernel(obs, "relay_pack"))
