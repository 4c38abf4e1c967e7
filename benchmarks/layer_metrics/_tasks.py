"""What the readers of the program's own task and launch spans, and of the
stream's newer phase keys, share.

The spans are those of the traced batch job (``traced_job.spans``, every
process of the job on the epoch clock).  A span of this kind has an ``id``
and names its ``parent``; a program whose tracer does not give them (a
checkout from before its spans were opened where the work happens) has
nothing here to read, and every reader returns None."""

from __future__ import annotations

from typing import List, Optional

from layer_metrics._common import median_of, pipeline_stats, span_events


def device_maps(obs: dict) -> List[dict]:
    """The device worker's ``worker.map`` spans, those that can have
    children."""
    return [e for e in span_events(obs, "worker.map")
            if e.get("id") is not None]


def map_part_s(obs: dict, names: tuple) -> Optional[float]:
    """Median over the maps of the seconds a map spends in its direct
    child spans of these names."""
    job = obs.get("traced_job") or {}
    events = (job.get("spans") or {}).get("events", [])
    maps = device_maps(obs)
    if not maps or not any(e.get("parent") is not None for e in events):
        return None
    return median_of([
        sum(e["dur"] for e in events
            if e["ph"] == "X" and e["name"] in names
            and e["pid"] == m["pid"] and e.get("parent") == m["id"])
        for m in maps])


def launch_events(obs: dict, name: str) -> List[dict]:
    """Events and spans of the launch lane, by name."""
    return [e for e in span_events(obs, name) if e.get("lane") == "launch"]


def phase_s(obs: dict, key: str) -> Optional[float]:
    """Median over a run's stream jobs of one key of ``pipeline_stats``;
    None where the program does not report the key."""
    return median_of([p[key] for p in pipeline_stats(obs) if key in p])
