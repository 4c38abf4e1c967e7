"""Checkpoint/restore for the streaming engines.

The reference system's whole fault-tolerance story is re-execution: a
task that dies is re-run from its input files (10 s presumed-dead
timeout, ``mr/coordinator.go``), and the control-plane journal
(``mr/journal.py``) extends that to coordinator death.  The streaming
engines broke that model's assumption — their value IS the gigabytes of
cross-step state held on device (`dsi_tpu/device/`) with ``step_pulls=0``
— so a worker death lost the whole stream and the only recovery was a
full replay.  This package closes that gap:

* :mod:`~dsi_tpu.ckpt.policy` — :class:`CheckpointPolicy`, the cadence
  (every K confirmed steps and/or T seconds), mirroring
  ``device/policy.SyncPolicy``;
* :mod:`~dsi_tpu.ckpt.store` — :class:`CheckpointStore`, the durable
  versioned (payload, manifest) pairs with CRC sidecars, parent-dir
  fsync, newest-valid-wins loading and last-two retention;
* :mod:`~dsi_tpu.ckpt.fault` — :func:`fault_point`, the named
  kill-points (``DSI_FAULT_POINT``/``DSI_FAULT_STEP``) that let tests
  prove resume against REAL crashes;
* :mod:`~dsi_tpu.ckpt.writer` — :class:`CheckpointWriter`, the
  capture/commit split (``--ckpt-async``: snapshot pulls overlap the
  next pipeline window, a background writer runs the durable path);
* :mod:`~dsi_tpu.ckpt.delta` — the incremental payload format
  (``--ckpt-delta``: a save ships only the confirmed step payloads
  appended since the previous one; the store chains ``delta-<seq>``
  manifests onto their base, restore = base + ordered deltas).

The consistency contract, owned here and honored by every engine
(``parallel/streaming.py``, ``parallel/grepstream.py``,
``parallel/tfidf.py``): a checkpoint is taken only at a CONFIRMED-step
boundary and contains (a) the host accumulators, (b) drain-free images
of every live device service (flushed of lagged flags, pulled but NOT
cleared), (c) the sticky dispatch-rung state, and (d) the input cursor
of the last confirmed step.  Steps in the in-flight window — dispatched
but with deferred checks unread — are deliberately EXCLUDED: their
outputs were never merged, so re-reading the input from the cursor and
re-processing them preserves exactly-once through the same
replay-at-sticky-rungs ladder that makes the pipelined engines
bit-identical to ``depth=1``.  Resume therefore yields bit-identical
final output to an uninterrupted run — the parity gate
tests/test_checkpoint.py enforces per engine, fault point, depth, and
device_accumulate mode.
"""

from dsi_tpu.ckpt.fault import (
    CHAOS_EXIT,
    FAULT_EXIT,
    FAULT_POINTS,
    FaultInjected,
    chaos_kill_point,
    fault_point,
    reset_chaos,
    reset_faults,
)
from dsi_tpu.ckpt.delta import (
    Deferred,
    DeltaSteps,
    HostDeltaLog,
    drain_packed_steps,
    drain_posting_steps,
    iter_delta_steps,
)
from dsi_tpu.ckpt.policy import (
    CheckpointPolicy,
    checkpoint_async_default,
    checkpoint_compress_default,
    checkpoint_delta_default,
    checkpoint_every_default,
    checkpoint_rebase_default,
    checkpoint_secs_default,
)
from dsi_tpu.ckpt.store import (
    CKPT_VERSION,
    CheckpointMismatch,
    CheckpointStore,
    skip_stream,
)
from dsi_tpu.ckpt.writer import CheckpointWriter

__all__ = [
    "CKPT_VERSION",
    "CheckpointMismatch",
    "CheckpointPolicy",
    "CheckpointStore",
    "CheckpointWriter",
    "Deferred",
    "DeltaSteps",
    "HostDeltaLog",
    "CHAOS_EXIT",
    "FAULT_EXIT",
    "FAULT_POINTS",
    "FaultInjected",
    "chaos_kill_point",
    "reset_chaos",
    "checkpoint_async_default",
    "checkpoint_compress_default",
    "checkpoint_delta_default",
    "checkpoint_every_default",
    "checkpoint_rebase_default",
    "checkpoint_secs_default",
    "drain_packed_steps",
    "drain_posting_steps",
    "fault_point",
    "iter_delta_steps",
    "reset_faults",
    "skip_stream",
]
