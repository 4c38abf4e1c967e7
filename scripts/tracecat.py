#!/usr/bin/env python
"""Render a dsi_tpu/obs trace as text: flame summary, slowest steps,
straggler table, control-plane digest.

Input is whatever a traced run left behind — a ``trace.jsonl`` (or
``.json``) file, or a directory of them (``mrrun --trace-dir`` leaves
one ``trace-<pid>.*`` pair per coordinator/worker process; all are
merged).  No jax, no repo imports: this reads the artifacts alone, so
it runs anywhere the trace files land (including a laptop far from the
chip that produced them).

Sections:

* header      — event counts, wall span, dropped events, counters, and
                the metrics-registry snapshot (per-engine unified phase
                dicts) embedded at flush time;
* flame       — per span-name totals (total seconds, self seconds =
                total less the direct children, count, mean, max) with
                text bars, sorted by total, and under each name what
                its direct children sum to: WHERE the wall went;
* top steps   — the N slowest per-step ``finish`` spans (the pipeline
                core's per-step retire wall: deferred flag wait + merge
                or replay), with engine and step ordinal;
* stragglers  — finish spans beyond max(2x median, mean + 3 sigma): the
                outliers a speculative-execution pass would back up;
* control     — requeue/fault/assign/complete/stall/aot_load event
                digest and the per-worker heartbeat-age gauge, when
                present;
* tasks       — the batch plane's tasks (``worker.map``/
                ``worker.reduce``) with what each spent in its child
                spans (read, materialize, upload, kernel, pull, decode,
                write) and
                what no span covers;
* launch      — the launch lane across the job's processes on one
                clock: ``mrrun`` start, the chip probe, each spawn, each
                worker's start, backend init and backend up;
* shuffle     — the mesh-sharded fold lane (PR 7): fold-span wall,
                ``shard_widens``/``shard_imbalance``/``pull_bytes``
                counters and per-event hot-shard details;
* ckpt        — the capture/commit split (PR 8): per-half span wall
                and the ``ckpt_barrier_s``/saves/deltas/bytes
                counters;
* histograms  — the live-telemetry stage latency percentile table
                (count/p50/p90/p99/max per hot stage) embedded in the
                registry snapshot at flush.

Usage: python scripts/tracecat.py TRACE_OR_DIR [--top N]
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys


def _load_jsonl(path: str):
    meta, events = {}, []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail line of a killed writer
            if rec.get("type") == "meta":
                meta = rec
            else:
                events.append(rec)
    return meta, events


def _load_chrome(path: str):
    """Fallback reader for the Perfetto ``.json`` when no ``.jsonl`` is
    around (e.g. only the Chrome file was copied off the box)."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    meta = doc.get("otherData", {})
    events = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i", "C"):
            continue
        rec = {"ph": "I" if ev["ph"] == "i" else ev["ph"],
               "name": ev.get("name", "?"), "lane": ev.get("cat", "?"),
               "ts": ev.get("ts", 0) / 1e6, "dur": ev.get("dur", 0) / 1e6,
               "depth": 0}
        rec.update(ev.get("args") or {})
        events.append(rec)
    return meta, events


def load(path: str):
    """(metas, events) from a file or a directory of trace artifacts."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "*.jsonl")))
        # The live sampler's ring (obs/live.py) shares the trace dir
        # but holds wall-clock snapshots, not span events — summarized
        # separately in main(), never merged into the timeline.
        files = [f for f in files
                 if os.path.basename(f) != "live.jsonl"]
        if not files:
            files = sorted(glob.glob(os.path.join(path, "*.json")))
            files = [f for f in files if not f.endswith(".crc32")]
        if not files:
            sys.exit(f"tracecat: no trace artifacts under {path}")
    else:
        files = [path]
    metas, events = [], []
    for f in files:
        meta, evs = (_load_jsonl(f) if f.endswith(".jsonl")
                     else _load_chrome(f))
        if meta:
            meta["_file"] = os.path.basename(f)
            metas.append(meta)
        for e in evs:
            e["_file"] = os.path.basename(f)
            # the epoch clock: what puts several processes on one axis
            e["_wall"] = meta.get("wall0", 0.0) + e.get("ts", 0.0)
        events.extend(evs)
    return metas, events


def _bar(frac: float, width: int = 28) -> str:
    n = int(round(max(0.0, min(1.0, frac)) * width))
    return "#" * n + "." * (width - n)


def flame(events, out) -> None:
    """Per span name: total, self time (the total less the direct
    children's, so a span with nothing inside it reads its whole total),
    ``dry_s`` (of that self time, what the starvation account of the
    job's main thread charged it: the chip had nothing queued) and,
    indented under it, what its direct children sum to."""
    spans = [e for e in events if e.get("ph") == "X"]
    by_id = {(e.get("_file"), e["id"]): e for e in spans
             if e.get("id") is not None}
    rows, kids = {}, {}
    for e in spans:
        key = (e.get("lane", "?"), e["name"])
        dur = e.get("dur", 0.0)
        r = rows.setdefault(key, [0.0, 0, 0.0, 0.0])
        r[0] += dur
        r[1] += 1
        r[2] = max(r[2], dur)
        r[3] += e.get("dry", 0.0)
        up = by_id.get((e.get("_file"), e.get("parent")))
        if up is not None:
            k = kids.setdefault((up.get("lane", "?"), up["name"]),
                                {}).setdefault(key, [0.0, 0])
            k[0] += dur
            k[1] += 1
    if not rows:
        print("  (no spans)", file=out)
        return

    def label(key) -> str:
        lane, name = key
        return f"{lane}/{name}" if lane != name else name

    top = max(r[0] for r in rows.values()) or 1.0
    print(f"  {'lane/span':<24} {'total_s':>9} {'self_s':>9} {'dry_s':>9} "
          f"{'count':>7} {'mean_ms':>9} {'max_ms':>9}", file=out)
    for key, (tot, cnt, mx, dry) in sorted(rows.items(),
                                           key=lambda kv: -kv[1][0]):
        inside = kids.get(key, {})
        self_s = tot - sum(k[0] for k in inside.values())
        print(f"  {label(key):<24} {tot:>9.3f} {self_s:>9.3f} {dry:>9.3f} "
              f"{cnt:>7} {1e3 * tot / cnt:>9.2f} {1e3 * mx:>9.2f}  "
              f"{_bar(tot / top)}", file=out)
        for kkey, (ktot, kcnt) in sorted(inside.items(),
                                         key=lambda kv: -kv[1][0]):
            print(f"    > {label(kkey):<20} {ktot:>9.3f} {'':>19} "
                  f"{kcnt:>7}", file=out)


def _finish_spans(events):
    return [e for e in events
            if e.get("ph") == "X" and e.get("name") == "finish"]


def top_steps(events, n: int, out) -> None:
    fin = sorted(_finish_spans(events), key=lambda e: -e.get("dur", 0.0))
    if not fin:
        print("  (no per-step finish spans — not a pipeline trace?)",
              file=out)
        return
    print(f"  {'engine':<10} {'step':>6} {'dur_ms':>10}  file", file=out)
    for e in fin[:n]:
        print(f"  {e.get('engine') or '?':<10} {e.get('step', '?'):>6} "
              f"{1e3 * e.get('dur', 0.0):>10.2f}  {e.get('_file', '')}",
              file=out)


def stragglers(events, out) -> None:
    fin = _finish_spans(events)
    if len(fin) < 4:
        print("  (too few steps for outlier statistics)", file=out)
        return
    durs = sorted(e.get("dur", 0.0) for e in fin)
    n = len(durs)
    median = durs[n // 2]
    mean = sum(durs) / n
    sigma = math.sqrt(sum((d - mean) ** 2 for d in durs) / n)
    cut = max(2 * median, mean + 3 * sigma)
    bad = [e for e in fin if e.get("dur", 0.0) > cut]
    print(f"  steps={n} median={1e3 * median:.2f}ms mean={1e3 * mean:.2f}ms"
          f" sigma={1e3 * sigma:.2f}ms cutoff={1e3 * cut:.2f}ms", file=out)
    if not bad:
        print("  no stragglers past the cutoff", file=out)
        return
    for e in sorted(bad, key=lambda e: -e.get("dur", 0.0)):
        print(f"  STRAGGLER {e.get('engine') or '?'} step "
              f"{e.get('step', '?')}: {1e3 * e.get('dur', 0.0):.2f}ms "
              f"({e.get('dur', 0.0) / median:.1f}x median)", file=out)


def control(events, metas, out) -> None:
    interesting = ("requeue", "fault", "assign", "complete",
                   "duplicate_completion", "ckpt_save", "ckpt_restore",
                   "table_widen", "shard_widen", "stall", "aot_load")
    counts: dict = {}
    for e in events:
        if e.get("ph") == "I" and e.get("name") in interesting:
            counts[e["name"]] = counts.get(e["name"], 0) + 1
    if counts:
        print("  events: " + "  ".join(
            f"{k}={v}" for k, v in sorted(counts.items())), file=out)
    for e in events:
        if e.get("ph") == "I" and e.get("name") in ("requeue", "fault",
                                                    "stall"):
            extras = {k: v for k, v in e.items()
                      if k not in ("ph", "name", "lane", "ts", "dur",
                                   "depth", "parent", "_file", "_wall")}
            tag = "STALL" if e["name"] == "stall" else e["name"]
            print(f"  {tag} @ {e.get('ts', 0):.3f}s: {extras}",
                  file=out)
    for meta in metas:
        gauges = (meta.get("registry") or {}).get("gauges") or {}
        hb = gauges.get("mr_worker_heartbeat_age_s")
        if hb:
            print(f"  heartbeat ages [{meta.get('_file', '?')}]: "
                  + "  ".join(f"{w}={a}s" for w, a in sorted(hb.items())),
                  file=out)
        hbh = gauges.get("mr_worker_heartbeat_hist")
        if hbh:
            for w, h in sorted(hbh.items()):
                print(f"  heartbeat gaps {w}: count={h.get('count')} "
                      f"p50={h.get('p50_ms')}ms p99={h.get('p99_ms')}ms "
                      f"max={h.get('max_ms')}ms", file=out)


_TASK_PARTS = ("read", "materialize", "upload", "kernel", "pull", "decode",
               "write")


def tasks(events, out) -> bool:
    """The batch plane's tasks, and what a map consists of: seconds in
    each kind of child span (direct children, by ``parent`` id within
    the task's file) and the remainder no span covers."""
    spans = [e for e in events if e.get("ph") == "X"]
    rows = sorted((e for e in spans
                   if e["name"] in ("worker.map", "worker.reduce")),
                  key=lambda e: e["_wall"])
    if not rows:
        return False
    # seconds per (file, parent span, part name), over direct children
    part_s: dict = {}
    for e in spans:
        if e["name"] in _TASK_PARTS and e.get("parent") is not None:
            key = (e["_file"], e["parent"], e["name"])
            part_s[key] = part_s.get(key, 0.0) + e.get("dur", 0.0)
    print(f"  {'task':<12} {'dur_s':>8} "
          + " ".join(f"{p[:8]:>8}" for p in _TASK_PARTS)
          + f" {'other':>8}  file", file=out)
    for t in rows:
        part = [part_s.get((t["_file"], t.get("id"), p), 0.0)
                for p in _TASK_PARTS]
        label = f"{t['name'].split('.')[-1]} {t.get('task', '?')}"
        print(f"  {label:<12} {t.get('dur', 0.0):>8.3f} "
              + " ".join(f"{s:>8.3f}" for s in part)
              + f" {t.get('dur', 0.0) - sum(part):>8.3f}  {t['_file']}",
              file=out)
    return True


def launch(events, out) -> bool:
    """The launch lane of every process on the epoch clock, in seconds
    since its first event."""
    lane = sorted((e for e in events if e.get("lane") == "launch"),
                  key=lambda e: e["_wall"])
    if not lane:
        return False
    t0 = lane[0]["_wall"]
    skip = ("ph", "name", "lane", "ts", "dur", "depth", "id", "parent",
            "_file", "_wall")
    for e in lane:
        extras = " ".join(f"{k}={v}" for k, v in e.items()
                          if k not in skip)
        dur = f" dur={e['dur']:.3f}s" if e.get("ph") == "X" else ""
        print(f"  +{e['_wall'] - t0:>8.3f}s {e['name']:<15}{dur} "
              f"{extras}  [{e['_file']}]", file=out)
    return True


def _span_totals(events, names, lane=None) -> dict:
    tot: dict = {}
    for e in events:
        if lane is not None and e.get("lane") != lane:
            continue
        if e.get("ph") == "X" and e.get("name") in names:
            r = tot.setdefault(e["name"], [0.0, 0])
            r[0] += e.get("dur", 0.0)
            r[1] += 1
    return tot


def shuffle(events, metas, out) -> bool:
    """The mesh-sharded fold lane (PR 7): invisible to the original
    digest because the lane landed after it.  Returns True when there
    was anything to show."""
    folds = [e for e in events if e.get("ph") == "X"
             and e.get("lane") == "shuffle"]
    widens = [e for e in events if e.get("ph") == "I"
              and e.get("name") == "shard_widen"]
    rows = []
    for meta in metas:
        engines = (meta.get("registry") or {}).get("engines") or {}
        for eng, ph in sorted(engines.items()):
            if ph.get("mesh_shards"):
                rows.append((meta.get("_file", "?"), eng, ph))
    if not (folds or widens or rows):
        return False
    if folds:
        tot = sum(e.get("dur", 0.0) for e in folds)
        print(f"  fold spans in lane: {len(folds)}  wall={tot:.3f}s",
              file=out)
    for fname, eng, ph in rows:
        sw = ph.get("shard_widens")
        print(f"  {eng} [{fname}]: mesh_shards={ph.get('mesh_shards')} "
              f"pull_bytes={ph.get('pull_bytes')} "
              f"shard_widens={sw} (sum={sum(sw) if sw else 0}) "
              f"shard_imbalance={ph.get('shard_imbalance')}", file=out)
    for e in widens:
        extras = {k: v for k, v in e.items()
                  if k not in ("ph", "name", "lane", "ts", "dur",
                               "depth", "parent", "_file", "_wall")}
        print(f"  shard_widen @ {e.get('ts', 0):.3f}s: {extras}",
              file=out)
    return True


def ckpt(events, metas, out) -> bool:
    """The async checkpoint capture/commit split (PR 8) — per-half
    wall from the spans, barrier/saves/bytes from the phase dicts."""
    tot = _span_totals(events, ("ckpt", "ckpt_capture", "ckpt_commit"))
    keys = ("ckpt_saves", "ckpt_deltas", "ckpt_barrier_s",
            "ckpt_capture_s", "ckpt_commit_s", "ckpt_full_bytes",
            "ckpt_delta_bytes", "resume_gap_s")
    rows = []
    for meta in metas:
        engines = (meta.get("registry") or {}).get("engines") or {}
        for eng, ph in sorted(engines.items()):
            kv = {k: ph[k] for k in keys if ph.get(k)}
            if kv:
                rows.append((meta.get("_file", "?"), eng, kv))
    if not (tot or rows):
        return False
    for name in ("ckpt", "ckpt_capture", "ckpt_commit"):
        if name in tot:
            t, n = tot[name]
            print(f"  {name:<14} total={t:.3f}s count={n} "
                  f"mean={1e3 * t / n:.2f}ms", file=out)
    for fname, eng, kv in rows:
        print(f"  {eng} [{fname}]: " + " ".join(
            f"{k}={round(v, 4) if isinstance(v, float) else v}"
            for k, v in kv.items()), file=out)
    return True


def wire(events, metas, out) -> bool:
    """The compressed-wire + parallel-ingest keys (ISSUE 13): decode
    span totals plus the codec/reader-pool counters from the phase
    dicts."""
    # the codec's decode spans ride the upload lane; a map task's
    # decode (host lane) is the tasks section's
    tot = _span_totals(events, ("decode",), lane="upload")
    keys = ("wire_steps", "wire_raw_steps", "wire_packed_bytes",
            "wire_ratio", "decode_s", "ingest_readers", "ingest_blocks",
            "readahead_hit_pct", "ingest_wait_s", "ckpt_compress",
            "ckpt_delta_raw_bytes", "ckpt_compress_s")
    rows = []
    for meta in metas:
        engines = (meta.get("registry") or {}).get("engines") or {}
        for eng, ph in sorted(engines.items()):
            kv = {k: ph[k] for k in keys if ph.get(k)}
            if kv:
                rows.append((meta.get("_file", "?"), eng, kv))
    if not (tot or rows):
        return False
    if "decode" in tot:
        t, n = tot["decode"]
        print(f"  {'decode':<14} total={t:.3f}s count={n} "
              f"mean={1e3 * t / n:.2f}ms", file=out)
    for fname, eng, kv in rows:
        print(f"  {eng} [{fname}]: " + " ".join(
            f"{k}={round(v, 4) if isinstance(v, float) else v}"
            for k, v in kv.items()), file=out)
    return True


def plan(events, metas, out) -> bool:
    """The plan layer (ISSUE 14): per-stage walls from the ``plan``
    lane's spans plus the handoff accounting — how many intermediate
    bytes the chain carried and how many of them were SAVED from the
    host round-trip (handoff minus host-crossing)."""
    walls = []
    for e in events:
        if e.get("ph") == "X" and e.get("name") == "plan":
            walls.append((e.get("stage", "?"), e.get("dur", 0.0)))
    tot = _span_totals(events, ("stage_commit",))
    keys = ("plan_stages", "plan_handoff", "plan_handoff_bytes",
            "plan_intermediate_bytes", "plan_commit_bytes",
            "plan_relay_buffers", "plan_spilled_bytes",
            "plan_restored_bytes", "plan_resumed_stages")
    rows = []
    for meta in metas:
        engines = (meta.get("registry") or {}).get("engines") or {}
        ph = engines.get("plan") or {}
        kv = {k: ph[k] for k in keys if k in ph}
        if kv:
            rows.append((meta.get("_file", "?"), kv))
    if not (walls or rows):
        return False
    for stage, dur in walls:
        print(f"  stage {stage:<14} wall={dur:.3f}s", file=out)
    if "stage_commit" in tot:
        t, n = tot["stage_commit"]
        print(f"  {'stage_commit':<20} total={t:.3f}s count={n}",
              file=out)
    for fname, kv in rows:
        saved = (kv.get("plan_handoff_bytes", 0)
                 - kv.get("plan_intermediate_bytes", 0))
        print(f"  plan [{fname}]: handoff_bytes_saved={saved} " + " ".join(
            f"{k}={v}" for k, v in kv.items()
            if not isinstance(v, dict)), file=out)
    return True


def elastic(events, metas, out) -> bool:
    """Elastic dataflow (ISSUE 16): the seal-driven stage-overlap wall
    (``stage_overlap`` spans + ``plan_overlap_s``) and the dynamic
    re-split control events — which shard split, at what cursor, into
    which sub-ranges, and how each sub-range race resolved."""
    tot = _span_totals(events, ("stage_overlap", "resplit"))
    rows = []
    for meta in metas:
        engines = (meta.get("registry") or {}).get("engines") or {}
        ph = engines.get("plan") or {}
        kv = {k: ph[k] for k in ("plan_pipelined", "plan_stage_shards",
                                 "plan_overlap_s") if k in ph}
        if kv.get("plan_pipelined") or kv.get("plan_stage_shards"):
            rows.append((meta.get("_file", "?"), kv))
    splits = [e for e in events if e.get("ph") == "I"
              and e.get("name") == "resplit_dispatch"]
    subs = {}
    for e in events:
        if e.get("ph") == "I" and e.get("name") in ("subshard_commit",
                                                    "subshard_commit_lose"):
            key = (e.get("task"), e.get("sub"))
            subs.setdefault(key, []).append(e)
    if not (tot or rows or splits or subs):
        return False
    if "stage_overlap" in tot:
        t, n = tot["stage_overlap"]
        print(f"  {'stage_overlap':<20} total={t:.3f}s count={n}",
              file=out)
    for fname, kv in rows:
        print(f"  plan [{fname}]: " + " ".join(
            f"{k}={v}" for k, v in kv.items()), file=out)
    for e in splits:
        print(f"  resplit shard {e.get('task')} @ {e.get('ts', 0):.3f}s"
              f" reason={e.get('reason')} cursor={e.get('cursor')}"
              f" straggler=a{e.get('straggler_attempt')}"
              f" ranges={e.get('ranges')}", file=out)
    for (task, sub), es in sorted(subs.items(),
                                  key=lambda kv: (str(kv[0][0]),
                                                  str(kv[0][1]))):
        wins = sum(1 for e in es if e["name"] == "subshard_commit")
        loses = len(es) - wins
        resolved = any(e.get("resolved") for e in es)
        print(f"  sub {task}.s{sub}: commits={wins} losses={loses}"
              + (" [shard resolved split]" if resolved else ""),
              file=out)
    return True


def replica(events, metas, out) -> bool:
    """The replicated control plane (ISSUE 20): terms, elections,
    app rebuild walls, the measured failover gap (last event of the
    dying term -> the next ``replica.elected``), and per-replica
    replication lag from the ``dsi_replica_applied_index`` gauges."""
    evs = sorted((e for e in events
                  if str(e.get("name", "")).startswith("replica.")),
                 key=lambda e: e.get("ts", 0.0))
    applied = []
    for meta in metas:
        gauges = (meta.get("registry") or {}).get("gauges") or {}
        if "dsi_replica_applied_index" in gauges:
            applied.append((meta.get("_file", "?"),
                            gauges.get("dsi_replica_applied_index"),
                            gauges.get("dsi_replica_term"),
                            gauges.get("dsi_replica_elections")))
    if not (evs or applied):
        return False
    terms = sorted({int(e.get("term", 0)) for e in evs})
    elected = [e for e in evs if e["name"] == "replica.elected"]
    steps = sum(1 for e in evs if e["name"] == "replica.stepdown")
    print(f"  terms seen: {terms}  elections={len(elected)} "
          f"stepdowns={steps}", file=out)
    for e in elected:
        # Failover wall as the trace sees it: the gap from the last
        # event of ANY older term to this election.  A kill -9 leader
        # emits nothing on death, so this spans the election timeout.
        prev = [p for p in evs if p.get("ts", 0.0) < e.get("ts", 0.0)
                and int(p.get("term", 0)) < int(e.get("term", 0))]
        gap = (e.get("ts", 0.0) - prev[-1].get("ts", 0.0)) if prev \
            else None
        ups = [u for u in evs if u["name"] == "replica.app_up"
               and int(u.get("term", 0)) == int(e.get("term", 0))]
        build = ups[0].get("build_s") if ups else None
        line = (f"  term {e.get('term')}: replica {e.get('node')} "
                f"elected @ {e.get('ts', 0.0):.3f}s "
                f"barrier={e.get('barrier')}")
        if gap is not None:
            line += f" failover_gap={gap:.3f}s"
        if build is not None:
            line += f" app_build={build:.3f}s"
        print(line, file=out)
    if applied:
        top = max(a[1] or 0 for a in applied)
        for fname, idx, term, elections in sorted(applied):
            lag = top - (idx or 0)
            print(f"  {fname}: applied_index={idx} term={term} "
                  f"elections_won={elections}"
                  + (f" lag={lag}" if lag else ""), file=out)
    return True


def histograms(metas, out) -> bool:
    """The stage latency percentile table (obs/hist.py) embedded in
    each trace's registry snapshot."""
    any_rows = False
    for meta in metas:
        hists = (meta.get("registry") or {}).get("histograms") or {}
        if not hists:
            continue
        if not any_rows:
            print(f"  {'stage':<14} {'count':>8} {'p50_ms':>10} "
                  f"{'p90_ms':>10} {'p99_ms':>10} {'max_ms':>10}  file",
                  file=out)
        any_rows = True
        for stage, h in sorted(hists.items()):
            print(f"  {stage:<14} {h.get('count', 0):>8} "
                  f"{h.get('p50_ms', 0):>10.3f} "
                  f"{h.get('p90_ms', 0):>10.3f} "
                  f"{h.get('p99_ms', 0):>10.3f} "
                  f"{h.get('max_ms', 0):>10.3f}  "
                  f"{meta.get('_file', '?')}", file=out)
    return any_rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace.jsonl / trace.json, or a "
                                  "--trace-dir directory")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest steps to list (default 10)")
    args = ap.parse_args(argv)
    metas, events = load(args.trace)
    out = sys.stdout

    spans = sum(1 for e in events if e.get("ph") == "X")
    wall = max((e.get("ts", 0) + e.get("dur", 0) for e in events),
               default=0.0)
    dropped = sum(m.get("dropped_events", 0) for m in metas)
    print(f"== tracecat: {args.trace} ==", file=out)
    print(f"  files={len(metas) or 1} events={len(events)} spans={spans} "
          f"wall={wall:.3f}s dropped={dropped}", file=out)
    ring = (os.path.join(args.trace, "live.jsonl")
            if os.path.isdir(args.trace) else None)
    if ring and os.path.exists(ring):
        try:
            with open(ring, encoding="utf-8") as f:
                samples = [l for l in f if l.strip()]
            last = json.loads(samples[-1]) if samples else {}
            print(f"  live ring: {len(samples)} samples (live.jsonl), "
                  f"last at uptime {last.get('uptime_s', '?')}s, "
                  f"pipelines={last.get('pipelines')}", file=out)
        except (OSError, ValueError):
            pass
    for meta in metas:
        if meta.get("counters"):
            print(f"  counters [{meta.get('_file', '?')}]: "
                  f"{meta['counters']}", file=out)
        engines = (meta.get("registry") or {}).get("engines") or {}
        for eng, phases in sorted(engines.items()):
            ph = {k: v for k, v in phases.items()
                  if k.endswith("_s") and isinstance(v, (int, float))
                  and v > 0}
            if ph:
                print(f"  {eng} phases [{meta.get('_file', '?')}]: "
                      + " ".join(f"{k}={round(v, 3)}"
                                 for k, v in sorted(ph.items())),
                      file=out)
    print("\n-- flame (per span name) --", file=out)
    flame(events, out)
    print(f"\n-- top {args.top} slowest steps --", file=out)
    top_steps(events, args.top, out)
    print("\n-- stragglers --", file=out)
    stragglers(events, out)
    import io

    for title, fn in (("launch lane", lambda o: launch(events, o)),
                      ("tasks", lambda o: tasks(events, o)),
                      ("shuffle lane", lambda o: shuffle(events, metas, o)),
                      ("ckpt capture/commit", lambda o: ckpt(events, metas,
                                                             o)),
                      ("wire codec / ingest pool",
                       lambda o: wire(events, metas, o)),
                      ("plan layer",
                       lambda o: plan(events, metas, o)),
                      ("elastic dataflow",
                       lambda o: elastic(events, metas, o)),
                      ("replica control plane",
                       lambda o: replica(events, metas, o)),
                      ("stage latency histograms",
                       lambda o: histograms(metas, o))):
        buf = io.StringIO()
        if fn(buf):  # sections that landed after the original digest:
            print(f"\n-- {title} --", file=out)  # shown only with data
            out.write(buf.getvalue())
    print("\n-- control plane --", file=out)
    control(events, metas, out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
