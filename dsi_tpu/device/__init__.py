"""Device-resident accumulator service.

The layer between the SPMD kernels and the host accumulators: persistent
on-device state that absorbs per-step results with compiled fold/append
programs and meets the host only at sync points — the cross-step
amortization ROADMAP's top open item calls for, and the same shape a
training-stack optimizer/metrics loop needs (device state + periodic
host visibility).

* :mod:`~dsi_tpu.device.table` — :class:`DeviceTable`, the merged
  word/count table the streaming word count folds into.
* :mod:`~dsi_tpu.device.postings` — :class:`DevicePostings`, the
  append-only postings buffer the TF-IDF wave walk batches pulls with.
* :mod:`~dsi_tpu.device.topk` — :class:`DeviceTopK` and
  :class:`DeviceHistogram`, the top-k-by-count table and match-count
  histogram the grep/indexer streaming engines fold into.
* :mod:`~dsi_tpu.device.policy` — :class:`SyncPolicy`, the one owner of
  the every-K-folds pull cadence.
* :mod:`~dsi_tpu.device.relay` — :class:`DeviceRelay` /
  :class:`HostRelay`, the plan layer's inter-stage byte handoff (stage
  N+1's upload IS stage N's device-resident output).
"""

from dsi_tpu.device.policy import (SyncPolicy, mesh_shards_default,
                                   sync_every_default)
from dsi_tpu.device.table import (
    DeviceTable,
    warm_device_fold,
)
from dsi_tpu.device.postings import DevicePostings
from dsi_tpu.device.relay import DeviceRelay, HostRelay
from dsi_tpu.device.topk import (
    DeviceHistogram,
    DeviceTopK,
    KeyCounts,
    warm_histogram,
    warm_topk_service,
)

__all__ = [
    "DeviceHistogram",
    "DevicePostings",
    "DeviceRelay",
    "DeviceTable",
    "DeviceTopK",
    "HostRelay",
    "KeyCounts",
    "SyncPolicy",
    "mesh_shards_default",
    "sync_every_default",
    "warm_device_fold",
    "warm_histogram",
    "warm_topk_service",
]
