"""Device time of one run of stage 2's step program per MiB of relay
buffer (module ``mapreduce_step`` in the device trace of a plan job)."""

from layer_metrics._common import kernel_ms_per_mib
from layer_metrics._plan import for_kernel


def read(obs):
    return kernel_ms_per_mib(for_kernel(obs, "wc_step"))
