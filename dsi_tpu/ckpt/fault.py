"""Fault injection: kill the process at named engine points.

The crash-resume guarantee is only evidence if the crashes are real —
a mocked "restore from dict" test cannot catch a snapshot that forgot
to fsync, a manifest torn mid-rename, or device state that was captured
while a fold was still in flight.  This module lets the test grid
kill a live engine at the points where those bugs would hide:

* ``post-dispatch`` — right after a step/wave kernel is dispatched (the
  in-flight window holds unconfirmed work that a checkpoint must NOT
  contain);
* ``mid-fold``     — right after a confirmed step's merge/fold is
  issued, before the cursor advances (the classic torn-update point);
* ``pre-sync``     — immediately before a device-service drain/sync
  pull (host and device state maximally divergent);
* ``post-ckpt``    — right after a checkpoint manifest commits (resume
  must pick THIS checkpoint, and replay exactly the uncheckpointed
  tail);
* ``mid-capture``  — inside an async save, after the snapshot's device
  pulls are dispatched but before the capture is handed to the commit
  writer (nothing of this save may be visible to a resume);
* ``mid-commit``   — in the commit writer, after the capture
  materialized but before the payload/manifest pair lands (a
  half-written delta or image must LOSE to the previous complete
  chain — the newest-valid-wins walk's async edge);
* ``mid-serve``    — in the partition server, after the FIRST chunk of
  a streamed fetch hits the socket (the consumer sees a half-sent
  payload and a dead peer — the network data plane's re-fetch-from-
  replacement trigger, ISSUE 17).

Knobs (all read per call, so a subprocess inherits them from its env):

* ``DSI_FAULT_POINT`` — one of the names above; unset = disabled.
* ``DSI_FAULT_STEP``  — fire on the n-th occurrence of that point in
  this process (default 1).
* ``DSI_FAULT_MODE``  — ``exit`` (default): ``os._exit(FAULT_EXIT)``,
  a real crash with no teardown, no atexit, no flushes — exactly what
  a SIGKILL'd worker looks like; ``raise``: raise
  :class:`FaultInjected` instead, for the in-process parity grid
  (tests/test_checkpoint.py) where spawning an interpreter per grid
  cell would not fit the tier-1 budget.  The subprocess tests and the
  CI/evidence smoke steps use ``exit`` — real crashes, not mocks.
"""

from __future__ import annotations

import os
import sys
from typing import Dict

#: The injected-crash exit code — distinct from every code the CLIs use
#: (0/1/2) and from SIGKILL's 137, so a harness can assert "the fault
#: fired" rather than "something died".
FAULT_EXIT = 87

#: The chaos-kill exit code (``DSI_CHAOS_WORKER_KILL``) — distinct from
#: FAULT_EXIT so a grid can tell a scripted point-kill from a random
#: boundary-kill in the same run.
CHAOS_EXIT = 88

#: The ENGINE-level points — the crash-resume parity grid
#: (tests/test_checkpoint.py) parametrizes over exactly this tuple, so
#: points that fire outside an engine run (``mid-serve`` in the
#: partition server, the plan layer's ``plan-stage<i>-advance``) are
#: deliberately not listed; they fire by name through
#: :func:`fault_point` all the same.
FAULT_POINTS = ("post-dispatch", "mid-fold", "pre-sync", "post-ckpt",
                "mid-capture", "mid-commit")

_counters: Dict[str, int] = {}


class FaultInjected(RuntimeError):
    """Raised instead of exiting under ``DSI_FAULT_MODE=raise``."""


def reset_faults() -> None:
    """Forget per-point occurrence counts (in-process test isolation)."""
    _counters.clear()


def fault_point(point: str) -> None:
    """Note one occurrence of ``point``; crash if the env says so.

    Free when ``DSI_FAULT_POINT`` is unset (one env read); the per-point
    counter only advances for the armed point, so unrelated engines in
    the same process don't consume the budget.
    """
    armed = os.environ.get("DSI_FAULT_POINT")
    if not armed or armed != point:
        return
    n = _counters.get(point, 0) + 1
    _counters[point] = n
    try:
        at = int(os.environ.get("DSI_FAULT_STEP", "1"))
    except ValueError:
        at = 1
    if n != max(1, at):
        return
    if os.environ.get("DSI_FAULT_MODE") == "raise":
        raise FaultInjected(f"injected fault at {point} #{n}")
    print(f"FAULT: injected crash at {point} #{n}", file=sys.stderr,
          flush=True)
    # Commit the trace buffer BEFORE dying: the tracer flush rides the
    # same atomicio durable-write path as the checkpoints, so a traced
    # crash leaves a complete, loadable trace.json — the observability
    # half of the crash-resume evidence.  Never let tracing break the
    # fault itself.
    try:
        from dsi_tpu.obs import trace as _obs_trace

        tracer = _obs_trace.get_tracer()
        tracer.event("fault", point=point, n=n)
        tracer.flush()
    except Exception:
        pass
    # A real crash: no interpreter unwind, no atexit, no buffered-IO
    # flush — anything the checkpoint path did not make durable BEFORE
    # this instant is gone, which is the whole point.
    os._exit(FAULT_EXIT)


# ── chaos injection (ISSUE 15 satellite) ──────────────────────────────
#
# ``DSI_CHAOS_WORKER_KILL=p[,seed]`` makes a worker ``os._exit`` with
# probability ``p`` at task boundaries — the scriptable kill/recovery
# grid knob.  Determinism: the per-process RNG is seeded from (seed,
# ``DSI_CHAOS_WORKER_INDEX``) — the spawner stamps each worker with its
# fleet index — so a grid re-run draws the SAME kill sequence per
# worker regardless of pids or wall time.  Same discipline as
# ``fault_point``: trace-flush before the exit, then a real
# ``os._exit`` with no unwind.

_chaos_rng = None
_chaos_key = None


def parse_chaos_spec(spec: str):
    """``"p"`` or ``"p,seed"`` → ``(p, seed)``; malformed specs read as
    disabled (0.0, 0) — chaos must never crash the worker by itself."""
    try:
        parts = spec.split(",")
        p = float(parts[0])
        seed = int(parts[1]) if len(parts) > 1 and parts[1].strip() else 0
    except (ValueError, IndexError):
        return 0.0, 0
    return (p, seed) if 0.0 < p <= 1.0 else (0.0, 0)


def chaos_decision(p: float, seed: int, index: str, draw: int) -> bool:
    """Whether the ``draw``-th boundary of worker ``index`` under
    (p, seed) dies — a pure function, so grids are predictable and the
    unit tests can pin the schedule without spawning processes."""
    import random

    rng = random.Random(f"{seed}:{index}")
    hit = False
    for _ in range(draw):
        hit = rng.random() < p
    return hit


def chaos_kill_point(boundary: str = "task") -> None:
    """Note one task boundary; die with probability p when
    ``DSI_CHAOS_WORKER_KILL`` is armed.  Free when unset (one env
    read)."""
    global _chaos_rng, _chaos_key
    spec = os.environ.get("DSI_CHAOS_WORKER_KILL")
    if not spec:
        return
    p, seed = parse_chaos_spec(spec)
    if p <= 0.0:
        return
    import random

    index = os.environ.get("DSI_CHAOS_WORKER_INDEX", "0")
    key = (spec, index)
    if _chaos_rng is None or _chaos_key != key:
        _chaos_rng = random.Random(f"{seed}:{index}")
        _chaos_key = key
    if _chaos_rng.random() >= p:
        return
    print(f"CHAOS: killing worker (index={index}) at {boundary} "
          f"boundary (p={p})", file=sys.stderr, flush=True)
    try:  # same trace-flush-then-die discipline as fault_point
        from dsi_tpu.obs import trace as _obs_trace

        tracer = _obs_trace.get_tracer()
        tracer.event("chaos_kill", boundary=boundary, index=index)
        tracer.flush()
    except Exception:
        pass
    os._exit(CHAOS_EXIT)


def reset_chaos() -> None:
    """Forget the per-process chaos RNG (in-process test isolation)."""
    global _chaos_rng, _chaos_key
    _chaos_rng = None
    _chaos_key = None
