#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers: the benchmark's one
reduction, so that every PR computes device time the same way.

Reads the file with ``jax.profiler.ProfileData`` (nothing but JAX) and
returns a plain dict:

* ``window_s``: the traced window: first to last event over all planes,
  clipped to the return of the profiler's ``start_trace`` and the call of its
  ``stop_trace`` where the trace shows those frames (starting and stopping
  the profiler is not the program's time);
* ``devices``: device planes found (``/device:TPU:<n>``);
* ``busy_s``: seconds in which an operation ran on a device (union of the
  op intervals of its ``XLA Ops`` line), averaged over the devices;
* ``ops``: self seconds per op name (an op that encloses others, such as a
  ``while``, is charged only what its children leave), averaged over
  devices; ``categories``: the same per category (``sort``, ``all-to-all``,
  ``collective``, ``fusion``, ``copy``, ...), from the op's opcode;
* ``modules``: per HLO module (``XLA Modules`` line) its runs and device
  seconds, averaged over devices;
* ``a2a_s`` / ``a2a_exposed_s``: seconds in ``all-to-all`` ops, and the part
  of them during which no other op ran on that device;
* ``breakdown``: ``device_ops`` (the 10 ops with most self time) and
  ``idle_gaps``: the device's longest idle gaps, each named by what the
  host was doing in it (the innermost traced Python frame that spans the
  gap), summed by that name, 10 entries.

As a command it is the batch driver's child (this keeps the harness process
off JAX's backends): ``tracereduce.py <file.xplane.pb> <out.json>``; with
``--dump`` it prints planes, lines and their first events instead, for
looking at a trace by hand.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
TOP_GAPS = 64        # gaps attributed one by one; the rest are lumped
SPAN_SHARE = 0.9     # a frame "spans" a gap when it covers this much of it

_NAME_CATEGORIES = (
    (re.compile(r"all-to-all|alltoall", re.I), "all-to-all"),
    (re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute"
                r"|collective", re.I), "collective"),
    (re.compile(r"^sort|[^a-z]sort", re.I), "sort"),
    (re.compile(r"^copy|copy-start|copy-done", re.I), "copy"),
    (re.compile(r"gather|scatter|dynamic-slice|dynamic-update", re.I),
     "gather-scatter"),
    (re.compile(r"fusion", re.I), "fusion"),
    (re.compile(r"^while|^conditional|^call", re.I), "control"),
)


def category(name: str) -> str:
    """One coarse category per op, from its opcode or name (``sort``,
    ``all-to-all``, ``fusion`` ...; a TPU trace carries no category of its
    own on the op events)."""
    for pat, cat in _NAME_CATEGORIES:
        if pat.search(name):
            return cat
    return "other"


_HLO_TEXT = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}|/\*[^*]*\*/|\s")


def short_op(text: str) -> Tuple[str, str]:
    """``(label, opcode)`` of a device op event.  A TPU trace names an op by
    its whole HLO instruction text; the label keeps the instruction's name,
    opcode, result shape without layouts and fusion kind."""
    m = _HLO_TEXT.match(text)
    if not m:
        return text[:100], ""
    name, shapes, opcode = m.groups()
    kind = re.search(r"kind=(k\w+)", text)
    label = f"{name} {opcode} {_LAYOUT.sub('', shapes)[:70]}"
    return (label + (f" {kind.group(1)}" if kind else ""))[:120], opcode


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name of events on ONE line: an event that lies inside
    another is the other's child, and the parent keeps only what its
    children leave."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [end, name, child_seconds]

    def close(top: List, start: float) -> None:
        dur = top[0] - start
        out[top[1]] = out.get(top[1], 0.0) + max(0.0, dur - top[2])

    starts: List[float] = []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop(), starts.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0.0])
        starts.append(s)
    while stack:
        close(stack.pop(), starts.pop())
    return out


def _covered(intervals: List[Tuple[float, float]],
             by: List[Tuple[float, float]]) -> float:
    """Seconds of ``intervals`` (merged) that ``by`` (merged) covers."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(by) and by[j][1] <= s:
            j += 1
        k = j
        while k < len(by) and by[k][0] < e:
            total += min(e, by[k][1]) - max(s, by[k][0])
            k += 1
    return total


def _device_plane(plane) -> Optional[dict]:
    """One device's ops and modules, times in seconds."""
    ops, modules, labels, a2a_async = [], [], {}, []
    for line in plane.lines:
        if line.name in (OPS_LINE, ASYNC_LINE):
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                if ev.name not in labels:
                    label, opcode = short_op(ev.name)
                    labels[ev.name] = (label, category(opcode or label))
                label, cat = labels[ev.name]
                s = ev.start_ns * 1e-9
                if line.name == OPS_LINE:
                    ops.append((s, s + ev.duration_ns * 1e-9, label, cat))
                elif cat == "all-to-all":   # an async collective in flight
                    a2a_async.append((s, s + ev.duration_ns * 1e-9))
        elif line.name == MODULES_LINE:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                modules.append((s, s + ev.duration_ns * 1e-9, ev.name))
    if not ops:
        return None
    return {"ops": ops, "modules": sorted(modules), "a2a_async": a2a_async}


def _module_name(name: str) -> str:
    """``jit_f(1234567)`` -> ``jit_f``: the run's fingerprint goes."""
    return re.sub(r"\(\d+\)$", "", name)


def _host_frames(data) -> List[Tuple[float, float, str]]:
    """Every traced host event with a duration (Python frames of the
    profiler's python tracer, TraceMe spans), times in seconds."""
    frames = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    s = ev.start_ns * 1e-9
                    frames.append((s, s + ev.duration_ns * 1e-9, ev.name))
    frames.sort()
    return frames


def _name_gap(gap: Tuple[float, float], frames, starts) -> str:
    """What the host was doing in ``gap``: the shortest traced frame that
    covers ``SPAN_SHARE`` of it; failing that, the one that overlaps it
    most."""
    a, b = gap
    need = SPAN_SHARE * (b - a)
    best_span, best_overlap = None, None
    hi = bisect.bisect_right(starts, b)
    for s, e, name in frames[:hi]:
        if e <= a:
            continue
        overlap = min(e, b) - max(s, a)
        if overlap >= need:
            if best_span is None or e - s < best_span[0]:
                best_span = (e - s, name)
        elif best_overlap is None or overlap > best_overlap[0]:
            best_overlap = (overlap, name)
    pick = best_span or best_overlap
    return pick[1][:120] if pick else "host: nothing traced"


def reduce(data) -> Optional[dict]:
    """The reduction proper, on a ``ProfileData``; None when the trace has
    no device plane with ops (a CPU trace: nothing to read)."""
    t_min, t_max = float("inf"), float("-inf")
    started = stopped = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                t_min = min(t_min, ev.start_ns)
                t_max = max(t_max, ev.start_ns + ev.duration_ns)
                # the profiler's own frames bound the program's time: the
                # window opens when start_trace returns and closes when
                # stop_trace is called
                if ev.name.startswith("$profiler.py"):
                    if ev.name.endswith(" start_trace"):
                        started = ev.start_ns + ev.duration_ns
                    elif ev.name.endswith(" stop_trace"):
                        stopped = ev.start_ns
    if started is not None:
        t_min = max(t_min, started)
    if stopped is not None:
        t_max = min(t_max, stopped)
    devices = [d for d in (
        _device_plane(p) for p in data.planes if DEVICE_PLANE.match(p.name))
        if d]
    if not devices:
        return None
    n = float(len(devices))
    window_s = (t_max - t_min) * 1e-9
    lo, hi = t_min * 1e-9, t_max * 1e-9
    for dev in devices:   # what ran outside the window does not count
        dev["ops"] = [(max(s, lo), min(e, hi), name, cat)
                      for s, e, name, cat in dev["ops"] if e > lo and s < hi]
    busy = 0.0
    ops: Dict[str, float] = {}
    cats: Dict[str, float] = {}
    mods: Dict[str, Dict[str, float]] = {}
    a2a_s = a2a_exposed_s = 0.0
    for dev in devices:
        busy += union_seconds([(s, e) for s, e, _n, _c in dev["ops"]])
        op_cat = {name: cat for _s, _e, name, cat in dev["ops"]}
        for name, secs in self_times(
                [(s, e, name) for s, e, name, _c in dev["ops"]]).items():
            ops[name] = ops.get(name, 0.0) + secs
            c = op_cat[name]
            cats[c] = cats.get(c, 0.0) + secs
        a2a = merge([(s, e) for s, e, name, _c in dev["ops"]
                     if op_cat[name] == "all-to-all"] + dev["a2a_async"])
        rest = merge([(s, e) for s, e, name, _c in dev["ops"]
                      if op_cat[name] not in ("all-to-all", "control")])
        secs = sum(e - s for s, e in a2a)
        a2a_s += secs
        a2a_exposed_s += secs - _covered(a2a, rest)
        for s, e, name in dev["modules"]:
            rec = mods.setdefault(_module_name(name),
                                  {"runs": 0.0, "seconds": 0.0})
            rec["runs"] += 1
            rec["seconds"] += e - s
    first = devices[0]
    busy0 = merge([(s, e) for s, e, _n, _c in first["ops"]])
    t0, t1 = t_min * 1e-9, t_max * 1e-9
    edges = [t0] + [x for iv in busy0 for x in iv] + [t1]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])
    frames = _host_frames(data)
    starts = [f[0] for f in frames]
    by_name: Dict[str, float] = {}
    for gap in gaps[:TOP_GAPS]:
        name = _name_gap(gap, frames, starts)
        by_name[name] = by_name.get(name, 0.0) + gap[1] - gap[0]
    if gaps[TOP_GAPS:]:
        by_name[f"{len(gaps) - TOP_GAPS} shorter gaps"] = sum(
            b - a for a, b in gaps[TOP_GAPS:])

    def top(d: Dict[str, float], scale: float) -> List[List]:
        return [[k, v / scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window_s, "devices": int(n), "busy_s": busy / n,
        "ops": {k: v / n for k, v in ops.items()},
        "categories": {k: v / n for k, v in sorted(cats.items())},
        "modules": {k: {"runs": v["runs"] / n, "seconds": v["seconds"] / n}
                    for k, v in sorted(mods.items())},
        "a2a_s": a2a_s / n, "a2a_exposed_s": a2a_exposed_s / n,
        "breakdown": {"device_ops": top(ops, n),
                      "idle_gaps": top(by_name, 1.0)},
    }


def reduce_file(path: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))


def dump(path: str, per_line: int = 5) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:per_line]:
                print("     ", ev.name[:100], ev.start_ns, ev.duration_ns)


def main(argv: List[str]) -> int:
    if len(argv) == 2 and argv[0] == "--dump":
        dump(argv[1])
        return 0
    if len(argv) != 2:
        print(__doc__.split("As a command")[1], file=sys.stderr)
        return 2
    reduced = reduce_file(argv[0])
    with open(argv[1], "w") as f:
        json.dump(reduced, f)
    return 0 if reduced else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
