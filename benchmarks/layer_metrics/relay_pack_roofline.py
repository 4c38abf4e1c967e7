"""The relay's pack program against the chip's memory roofline (least
bytes: ``roofline_plan.pack_bytes``)."""

from layer_metrics._common import roofline_share
from layer_metrics._plan import for_kernel


def read(obs):
    return roofline_share(for_kernel(obs, "relay_pack"))
