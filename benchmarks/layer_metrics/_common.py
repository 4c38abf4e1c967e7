"""What several readers share.  A reader is ``read(obs) -> float | None``
in a file named after its metric; ``obs`` is what the run observed
(``benchmarks/README.md`` lists its keys).  None means "nothing to read
here", and the harness leaves the metric out."""

from __future__ import annotations

import re
import statistics
from typing import List, Optional


def span_events(obs: dict, name: str, **match) -> List[dict]:
    """Events of the traced batch job's task trace, by name and fields."""
    job = obs.get("traced_job") or {}
    events = (job.get("spans") or {}).get("events", [])
    return [e for e in events if e["name"] == name
            and all(e.get(k) == v for k, v in match.items())]


def pipeline_stats(obs: dict) -> List[dict]:
    """``pipeline_stats`` of every whole stream job, with its wall."""
    return [dict(j["pipeline_stats"], wall_s=j["t_end"] - j["t_start"])
            for j in obs.get("jobs", [])
            if j.get("pipeline_stats") and not j.get("problems")]


def median_of(values: list) -> Optional[float]:
    return statistics.median(values) if values else None


def kernel(obs: dict) -> Optional[dict]:
    """The cell's kernel block: the traffic mix names which of the
    configuration's kernels its jobs run."""
    return obs["config"].get("kernels", {}).get(obs["traffic"].get("kernel"))


def kernel_runs(obs: dict) -> Optional[dict]:
    """``{"runs", "seconds"}`` of the cell's kernel in the device trace:
    the traced HLO modules whose name matches the kernel's ``module``
    pattern, or None without a trace."""
    trace, k = obs.get("trace"), kernel(obs)
    if not trace or not k:
        return None
    pat = re.compile(k["module"])
    hits = [m for name, m in trace["modules"].items() if pat.search(name)]
    if not hits:
        return None
    return {"runs": sum(m["runs"] for m in hits),
            "seconds": sum(m["seconds"] for m in hits)}


def kernel_ms_per_mib(obs: dict) -> Optional[float]:
    runs, k = kernel_runs(obs), kernel(obs)
    if not runs or not runs["runs"]:
        return None
    mib = k["shapes"]["input_bytes"] / float(1 << 20)
    return 1e3 * runs["seconds"] / runs["runs"] / mib


def roofline_share(obs: dict) -> Optional[float]:
    import roofline

    runs, k = kernel_runs(obs), kernel(obs)
    if not runs or not runs["runs"] or "peaks" not in obs:
        return None
    return roofline.share(k, obs["peaks"], runs["seconds"] / runs["runs"])


def category_share(obs: dict, category: str) -> Optional[float]:
    """Percent of device busy time in ops of one category."""
    trace = obs.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["categories"].get(category, 0.0) / trace["busy_s"]


def device_idle(obs: dict) -> Optional[float]:
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
