"""One device process per chip: who gets it, and how each is pinned.

A TPU chip is held by one process at a time (libtpu takes a lockfile; a
second process fails with "TPU is already in use").  A launcher that starts
N children which each initialise JAX hands the chip to whichever child wins
and — JAX being lenient — lets the losers run their kernels on the CPU,
silently.  So the launchers (``mrrun``, ``shardrun``) never import JAX
themselves (a parent that holds the chip starves its children), count the
chips before any worker starts, and start at most one device process per
chip, each pinned to its chip through the environment libtpu reads
(:func:`chip_env`).

**How the chips are counted.**  From the PCI bus, as JAX itself decides
whether a machine has a TPU (``jax/_src/hardware_utils.py``): the functions
under ``/sys/bus/pci/devices`` whose vendor is Google's and whose device id
is a TPU's.  Of those, the ones whose IOMMU group has its device under
``/dev/vfio``, which is what libtpu opens: a container on a shared host sees
all of the host's functions in sysfs and is given the groups of its own
chips (on the chip tool's one-chip machine: four functions, one group, and
``jax.device_count()`` is 1).  A ``TPU_VISIBLE_CHIPS`` that names one of
them makes the count 1 (:func:`count_chips_on_bus`).  That reads a few dozen
small files and starts no runtime.

Where the bus cannot tell — no sysfs, no TPU function with a VFIO group (a
TPU behind another driver, or not a local PCI device, looks the same),
another platform named first, a slice of more than one host, a
``TPU_VISIBLE_CHIPS`` list of several chips, or a ``TPU_*`` /
``CLOUD_TPU_*`` variable that may re-shape what a process sees (process
bounds, a name this code does not know) — a probe child imports JAX, prints
``jax.devices()`` and exits (:func:`probe_device`, 13-17 s on a v5e: a whole
runtime start and teardown), and its count is taken.  The ``probe`` span
around the decision records which way it went (``how``: ``pci``, ``child``,
``cpu``, ``given``).

**Who proves the chip can be claimed.**  The bus counts a chip that another
process holds.  The device worker meets that at its backend start
(``utils/platformpin.require_device``) and exits with
``NO_ACCELERATOR_EXIT`` and a message naming the chip; a launcher's respawn
loop asks :func:`lost_chip` about every dead worker and ends the job on that
code at once, non-zero, instead of respawning.  A count of zero (from the
child; the bus never says zero) still ends the launcher before any spawn.

With the CPU asked for by name (``JAX_PLATFORMS=cpu`` /
``DSI_JAX_PLATFORM=cpu``) there is no chip to share: every worker may be a
device-backend worker, as the tests and the verify recipe run them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, Optional

from dsi_tpu.obs import span as _span
from dsi_tpu.utils.platformpin import (NO_ACCELERATOR_EXIT, cpu_requested,
                                       requested_platform)

_PROBE = ("import json, jax; d = jax.devices(); "
          "print('DSI_DEVICE ' + json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")

_PCI_DEVICES = "/sys/bus/pci/devices"
_VFIO_GROUPS = "/dev/vfio"
_GOOGLE_PCI_VENDOR = "0x1ae0"
# jax/_src/hardware_utils.py's table: v3, plc, v4, v5p, v5e, v6e, 7x.
_TPU_PCI_DEVICES = frozenset({"0x0027", "0x0056", "0x005e", "0x0062",
                              "0x0063", "0x006f", "0x0076"})
# TPU_* names that leave the devices of a process as the bus has them.
_COUNT_NEUTRAL_ENV = frozenset({
    "TPU_VISIBLE_CHIPS",  # understood, in count_chips_on_bus
    "TPU_LIBRARY_PATH", "TPU_LOG_DIR", "TPU_STDERR_LOG_LEVEL",
    "TPU_MIN_LOG_LEVEL", "TPU_VMODULE", "TPU_ML_PLATFORM",
    "TPU_ML_PLATFORM_VERSION", "TPU_SKIP_MDS_QUERY",
    "TPU_RUNTIME_METRICS_PORTS"})
# What a TPU VM's environment says of its slice.  Understood where it
# describes one host (TPU_HOST_BOUNDS 1,1,1, one worker host name): the
# devices of a process are then this host's chips.
_SLICE_ENV = frozenset({
    "TPU_ACCELERATOR_TYPE", "TPU_TOPOLOGY", "TPU_TOPOLOGY_ALT",
    "TPU_TOPOLOGY_WRAP", "TPU_HOST_BOUNDS", "TPU_CHIPS_PER_HOST_BOUNDS",
    "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID"})


def _read(path: str) -> str:
    with open(path, encoding="ascii") as f:
        return f.read().strip()


def _env_keeps_the_bus_count(env: Dict[str, str]) -> bool:
    """Whether, under ``env``, a fresh JAX process reports the TPU chips
    of this machine and nothing else."""
    if requested_platform(env).split(",")[0] not in ("", "tpu"):
        return False
    shaping = {k for k, v in env.items()
               if k.startswith(("TPU_", "CLOUD_TPU_")) and v
               and k not in _COUNT_NEUTRAL_ENV}
    if not shaping:
        return True
    # Anything else (process bounds and addresses, a task id, a name this
    # code has never seen) may re-shape what a process sees.
    return (shaping <= _SLICE_ENV
            and env.get("TPU_HOST_BOUNDS") == "1,1,1"
            and "," not in env.get("TPU_WORKER_HOSTNAMES", ""))


def count_chips_on_bus(env: Dict[str, str], pci_devices: str = _PCI_DEVICES,
                       vfio_groups: str = _VFIO_GROUPS) -> Optional[int]:
    """Number of TPU chips a fresh JAX process would report under ``env``,
    read from the PCI bus without starting a runtime; ``None`` when the
    bus cannot tell (the module docstring lists the cases) and the caller
    has to ask a probe child.  Never zero: no chip found is "cannot
    tell"."""
    if not _env_keeps_the_bus_count(env):
        return None
    chips = 0
    try:
        for function in os.listdir(pci_devices):
            at = os.path.join(pci_devices, function)
            if (_read(os.path.join(at, "vendor")) != _GOOGLE_PCI_VENDOR
                    or _read(os.path.join(at, "device"))
                    not in _TPU_PCI_DEVICES):
                continue
            # A container on a shared host sees every function in sysfs and
            # is given the VFIO group of its own chips only.
            group = os.path.basename(
                os.readlink(os.path.join(at, "iommu_group")))
            if os.path.exists(os.path.join(vfio_groups, group)):
                chips += 1
    except OSError:
        return None
    if chips == 0:
        return None
    visible = env.get("TPU_VISIBLE_CHIPS", "")
    if not visible:
        return chips
    # One chip of several starts as it is named.  A longer list does not
    # without process bounds to match (on a four-chip v5e "0,1" alone fails
    # at backend start), and those this scan does not read.
    return 1 if visible.isdecimal() and int(visible) < chips else None


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


def lost_chip(proc: subprocess.Popen, who: str) -> bool:
    """Whether ``proc``, a worker that has exited, ended because it could
    claim no chip (``NO_ACCELERATOR_EXIT``; its own message, which names
    the chip, is already on the stderr it shares with the launcher).  A
    launcher ends its job on that instead of respawning: the next start
    would meet the same chip."""
    if proc.returncode != NO_ACCELERATOR_EXIT:
        return False
    print(f"{who}: device worker pid={proc.pid} could claim no chip (exit "
          f"{NO_ACCELERATOR_EXIT}, its message is above); ending the job "
          "without a respawn", file=sys.stderr, flush=True)
    return True


def plan_device_workers(n_workers: int, env: Dict[str, str], who: str,
                        chips: Optional[int] = None):
    """Decide which worker slots get a device.

    Returns ``(slots, n_chips)`` where ``slots[i]`` is the chip index of
    worker ``i``, or ``None`` past the last chip (no device: a host
    worker, or — for a launcher whose every worker needs a device — one
    worker too many).  With the CPU requested by name every slot
    is ``0`` and ``n_chips`` is ``0`` (no pinning, no limit).  Otherwise
    the chips are counted: ``chips`` where the caller gives it (the tests'
    hook), else the PCI bus, else a probe child.  A count of zero raises
    ``SystemExit`` naming the missing chip.  The ``probe`` span holds the
    count and ``how`` it was come by."""
    with _span("probe", lane="launch") as sp:
        if cpu_requested(env):
            sp.set(chips=0, how="cpu")
            return [0] * n_workers, 0
        if chips is not None:
            n_chips, how = chips, "given"
        else:
            n_chips, how = count_chips_on_bus(env), "pci"
            if n_chips is None:
                n_chips, how = probe_chip_count(env), "child"
        sp.set(chips=n_chips, how=how)
    if n_chips <= 0:
        raise SystemExit(
            f"{who}: no TPU: a probe process found no chip (missing, or "
            "held by another process). Set JAX_PLATFORMS=cpu to run the "
            "device backend on the CPU on purpose.")
    return [i if i < n_chips else None for i in range(n_workers)], n_chips
