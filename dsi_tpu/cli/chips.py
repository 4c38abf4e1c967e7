"""One device process per chip: the launchers' shared assignment rule.

A TPU chip belongs to one process at a time.  A launcher that starts N
children which each initialise JAX hands the chip to whichever child wins
and — JAX being lenient — lets the losers run their kernels on the CPU,
silently.  So the launchers (``mrrun``, ``shardrun``) never import JAX
themselves (a parent that holds the chip starves its children), learn the
chip count from a probe child that exits before any worker starts, and
start at most one device process per chip, each pinned to its chip through
the environment libtpu reads (:func:`chip_env`).

With the CPU asked for by name (``JAX_PLATFORMS=cpu`` /
``DSI_JAX_PLATFORM=cpu``) there is no chip to share: every worker may be a
device-backend worker, as the tests and the verify recipe run them.
"""

from __future__ import annotations

import json
import subprocess
import sys
from typing import Dict, Optional

from dsi_tpu.obs import span as _span
from dsi_tpu.utils.platformpin import cpu_requested

_PROBE = ("import json, jax; d = jax.devices(); "
          "print('DSI_DEVICE ' + json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")


def probe_device(env: Dict[str, str],
                 timeout: float = 300.0) -> Optional[dict]:
    """``{"platform", "kind", "count"}`` as a fresh JAX process reports
    its devices, or None when it reports nothing (backend init failed or
    hung).  Runs in a child that exits before this returns, so the caller
    never holds a chip."""
    try:
        out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                             capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        return None
    for line in out.stdout.splitlines():
        if line.startswith("DSI_DEVICE "):
            return json.loads(line[len("DSI_DEVICE "):])
    return None


def probe_chip_count(env: Dict[str, str]) -> int:
    """Number of TPU chips a fresh process sees, 0 when it sees none."""
    device = probe_device(env)
    return device["count"] if device and device["platform"] == "tpu" else 0


def chip_env(env: Dict[str, str], chip: int, n_chips: int) -> Dict[str, str]:
    """``env`` for the one process that owns ``chip``.  On a one-chip
    machine nothing is set: the single device process takes the chip.
    On a multi-chip host each process is told, through the variables
    libtpu reads, that it is a one-chip topology over its own chip
    (docs/OPERATIONS.md, "One process per chip")."""
    out = dict(env)
    if n_chips > 1:
        out["TPU_VISIBLE_CHIPS"] = str(chip)
        out["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        out["TPU_PROCESS_BOUNDS"] = "1,1,1"
        # Each process runs its own single-process runtime; distinct
        # ports keep their local coordination services apart.
        out["TPU_PROCESS_PORT"] = str(8476 + chip)
        out["TPU_PROCESS_ADDRESSES"] = f"localhost:{8476 + chip}"
        out["CLOUD_TPU_TASK_ID"] = "0"
    return out


def plan_device_workers(n_workers: int, env: Dict[str, str], who: str,
                        chips: Optional[int] = None):
    """Decide which worker slots get a device.

    Returns ``(slots, n_chips)`` where ``slots[i]`` is the chip index of
    worker ``i``, or ``None`` past the last chip (no device: a host
    worker, or — for a launcher whose every worker needs a device — one
    worker too many).  With the CPU requested by name every slot
    is ``0`` and ``n_chips`` is ``0`` (no pinning, no limit).  Otherwise
    the chips are counted (``chips`` overrides the probe — the tests'
    hook) and a count of zero raises ``SystemExit`` naming the missing
    chip."""
    with _span("probe", lane="launch") as sp:
        if cpu_requested(env):
            sp.set(chips=0)
            return [0] * n_workers, 0
        n_chips = probe_chip_count(env) if chips is None else chips
        sp.set(chips=n_chips)
    if n_chips <= 0:
        raise SystemExit(
            f"{who}: no TPU: a probe process found no chip (missing, or "
            "held by another process). Set JAX_PLATFORMS=cpu to run the "
            "device backend on the CPU on purpose.")
    return [i if i < n_chips else None for i in range(n_workers)], n_chips
