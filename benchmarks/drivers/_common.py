"""What both drivers do with a reduced trace."""

from __future__ import annotations

import json
import os


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
