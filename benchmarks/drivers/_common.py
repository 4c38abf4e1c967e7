"""What several drivers share: claiming the chips for an in-process
driver, the device report and trace reduction after its window, and what
every driver does with a reduced trace."""

from __future__ import annotations

import glob
import json
import os
import sys

import jaxwatch  # benchmarks/ is on sys.path: run.py put it there
import tracereduce


def adopt_trace(cell, pb_path: str, reduced: dict) -> None:
    """Hand a traced job's reduction to the readers and to the result's
    ``device`` and ``breakdown``; keep the raw file where a builder asked
    for it (``BENCH_KEEP_TRACE=<dir>``)."""
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:
        os.makedirs(keep, exist_ok=True)
        os.replace(pb_path, os.path.join(keep, f"{cell.name}.xplane.pb"))
    cell.obs["trace"] = reduced
    cell.device["busy_s"] = reduced["busy_s"]
    cell.device["window_s"] = reduced["window_s"]
    cell.obs["breakdown"] = reduced["breakdown"]
    print(json.dumps({"trace": {k: reduced[k] for k in (
        "window_s", "busy_s", "devices", "modules", "categories", "a2a_s",
        "a2a_exposed_s")}}), flush=True)


def claim_device(cell) -> None:
    """Initialise JAX in this process and check the chips are there (the
    in-process drivers: the harness holds the chips for the whole run, as
    a user's stream process does for its job)."""
    if cell.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={cell.chips}"
        if cell.chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cell.jax_cache)
    if cell.root not in sys.path:
        sys.path.insert(0, cell.root)
    import jax

    jaxwatch.install()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        devices = []
        print(f"benchmarks: JAX found no backend: {e}", file=sys.stderr)
    cell.device = {"platform": devices[0].platform if devices else "none",
                   "kind": devices[0].device_kind if devices else "none",
                   "count": len(devices)}


def finish(cell, jobs: list) -> None:
    """An in-process driver's device report (the peak on the fullest chip)
    and, in a traced run, the first job's profile reduced."""
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()]
    cell.device["memory_peak_bytes"] = max(peaks)
    pbs = glob.glob(os.path.join(cell.workroot, "profile", "**",
                                 "*.xplane.pb"), recursive=True)
    if not pbs:
        return
    reduced = tracereduce.reduce_file(pbs[0])
    if reduced:
        cell.obs["traced_job"] = jobs[0]
        adopt_trace(cell, pbs[0], reduced)
