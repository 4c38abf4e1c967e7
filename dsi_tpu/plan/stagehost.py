"""Net-served plan stages: one process per stage, relays over TCP.

The plan layer's share-nothing harness (ISSUE 18).  ``planrun --hosts``
runs every stage of a multi-stage plan in its OWN process with a
PRIVATE working directory: a stage host rebuilds the plan from a spec,
fetches its dependencies' sealed stage payloads from the predecessors'
partition servers over the stream transport (the same ``Fetch`` verb +
one-byte wirecodec flag the shuffle uses, prefetch-pipelined when a
stage has several deps), reconstructs them with the stage-commit codec
(``driver._load_commit`` — the checkpoint/resume machinery, so parity
with the in-process modes holds by construction), runs its stage, and
registers its OWN sealed output (``driver._commit_payload`` serialized
to one payload blob) with its partition server.  No stage ever reads
another stage's directory: the only bytes that cross stage boundaries
cross them over TCP.

Payload blob format (``pack_commit``/``unpack_commit``)::

    b"DSP1" [4-byte BE meta length] [meta JSON] [np.savez archive]

``allow_pickle=False`` on load — the payload crosses a network
boundary.

The parent (``cli/planrun.py --hosts``) spawns stage hosts in topo
order, hands each a ``spec.json`` carrying the plan-rebuild arguments
plus its deps' ``{addr, name, crc}``, waits for the stage's
``ready.json``, and finally collects every stage's payload over TCP to
assemble the :class:`~dsi_tpu.plan.driver.PlanResult`.  After writing
``ready.json`` a stage host LINGERS as a server (mrworker discipline)
until the parent terminates it — consumers may not have fetched yet.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import struct
import time
import zlib
from typing import Dict, Tuple

import numpy as np

_MAGIC = b"DSP1"
_LEN = struct.Struct(">I")


def pack_commit(arrays: Dict[str, np.ndarray], meta: Dict) -> bytes:
    """One stage commit (``_commit_payload`` output) as one blob."""
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    mb = json.dumps(meta, sort_keys=True).encode("utf-8")
    return _MAGIC + _LEN.pack(len(mb)) + mb + buf.getvalue()


def unpack_commit(blob: bytes) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Inverse of :func:`pack_commit`; raises ``ValueError`` on a
    foreign or torn blob (the caller treats it like a CRC failure)."""
    if blob[:4] != _MAGIC:
        raise ValueError(f"not a stage payload (magic {blob[:4]!r})")
    (n,) = _LEN.unpack(blob[4:8])
    meta = json.loads(blob[8:8 + n].decode("utf-8"))
    with np.load(io.BytesIO(blob[8 + n:]), allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    return arrays, meta


def payload_name(i: int, stage_name: str) -> str:
    return f"plan-{i}-{stage_name}"


def build_plan(spec: Dict):
    """Rebuild the canonical plan a spec describes — shared by
    ``planrun`` (which derives the spec from argv) and every stage host
    (which must see the IDENTICAL plan graph)."""
    from dsi_tpu.plan import (agg_plan, grep_cascade_plan,
                              grep_wordcount_plan, indexer_join_plan,
                              join_plan, sort_plan, wordcount_topk_plan)

    defaults = dict(chunk_bytes=spec.get("chunk_bytes", 1 << 20),
                    depth=spec.get("depth"),
                    device_accumulate=bool(
                        spec.get("device_accumulate", False)),
                    sync_every=spec.get("sync_every"),
                    mesh_shards=spec.get("mesh_shards"),
                    aot=bool(spec.get("aot", False)),
                    n_reduce=spec.get("n_reduce", 10),
                    u_cap=spec.get("u_cap", 1 << 12),
                    topk=spec.get("topk", 16))
    chain = spec["chain"]
    files = list(spec.get("files") or ())
    if chain == "grep-wc":
        return grep_wordcount_plan(spec["pattern"], paths=files,
                                   **defaults)
    if chain == "grep-grep":
        return grep_cascade_plan(spec["pattern"], spec["pattern2"],
                                 paths=files, **defaults)
    if chain == "wc-topk":
        return wordcount_topk_plan(defaults["topk"], paths=files,
                                   **defaults)
    if chain == "indexer":
        # Lengths now, bytes when the walk (or a signature) asks: the
        # sequence reads them ahead of the walk, and a stage host that
        # indexes nothing reads none.  Whoever built the plan closes it.
        from dsi_tpu.utils.ioread import ReadAheadDocs

        return indexer_join_plan(ReadAheadDocs(files), pack_docs=bool(
            spec.get("pack_docs", False)), **defaults)
    if chain == "sort":
        return sort_plan(files, sample=spec.get("sample", 100_000),
                         **defaults)
    if chain == "agg":
        return agg_plan(files, prefix=spec.get("agg_prefix", 0), **defaults)
    if chain == "join":
        return join_plan(spec["join_build"], files,
                         dates=spec["join_dates"], **defaults)
    raise ValueError(f"unknown chain {chain!r}")


def fetch_stage_payload(addr: str, name: str, crc: int, *, stats=None,
                        timeout: float = 30.0) -> Tuple[Dict, Dict]:
    """Fetch + verify + decode one stage payload from a peer's
    partition server."""
    from dsi_tpu.net.fetch import FetchFailure, fetch_partition

    raw = fetch_partition(addr, name, stats=stats, timeout=timeout)
    if crc and zlib.crc32(raw) != crc:
        raise FetchFailure(-1, addr, name,
                           ValueError("stage payload crc mismatch"))
    return unpack_commit(raw)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--spec", required=True,
                   help="spec.json: plan-rebuild args + stage_index + "
                        "deps' {addr,name,crc} + spool/ready paths")
    args = p.parse_args(argv)
    with open(args.spec, "r", encoding="utf-8") as f:
        spec = json.load(f)

    from dsi_tpu.utils.platformpin import require_device

    require_device("stagehost")

    from dsi_tpu.net.fetch import (FetchPipeline, fetch_window_from_env)
    from dsi_tpu.net.partsrv import PartitionServer
    from dsi_tpu.obs import metrics_scope, span
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.plan.driver import (_commit_payload, _load_commit,
                                     _run_stage)
    from dsi_tpu.utils.atomicio import atomic_write

    plan = build_plan(spec["plan"])
    order = plan.ordered()
    i = int(spec["stage_index"])
    stage = order[i]
    mesh = default_mesh(spec["plan"].get("devices"))
    sc = metrics_scope("plan")
    sc["stage_stats"] = {}
    net_io = metrics_scope("net")
    srv = PartitionServer(spec["spool"],
                          bind=os.environ.get("DSI_NET_BIND", ""))
    srv.start()
    try:
        # Dependencies: sealed stage payloads from the predecessors'
        # servers — prefetch-pipelined when there are several.
        stage_by_name = {s.name: (j, s) for j, s in enumerate(order)}
        deps = spec.get("deps") or {}
        ctx: Dict = {}

        def absorb(dep_name: str, raw: bytes) -> None:
            from dsi_tpu.net.fetch import FetchFailure

            d = deps[dep_name]
            if d.get("crc") and zlib.crc32(raw) != int(d["crc"]):
                raise FetchFailure(
                    -1, d["addr"], d["name"],
                    ValueError("stage payload crc mismatch"))
            arrays, meta = unpack_commit(raw)
            _j, dep_stage = stage_by_name[dep_name]
            with span("decode", lane="net", part=d["name"]):
                ctx[dep_name] = _load_commit(plan, dep_stage, meta,
                                             arrays, mesh, True, sc)

        window = fetch_window_from_env()
        dep_names = sorted(deps, key=lambda n: stage_by_name[n][0])
        if len(dep_names) > 1 and window > 1:
            items = [(stage_by_name[n][0], deps[n]["addr"],
                      deps[n]["name"]) for n in dep_names]
            by_index = {stage_by_name[n][0]: n for n in dep_names}
            pipe = FetchPipeline(items, window=window, stats=net_io)
            for j, raw in pipe:
                absorb(by_index[j], raw)
        else:
            from dsi_tpu.net.fetch import fetch_partition

            for n in dep_names:
                absorb(n, fetch_partition(deps[n]["addr"],
                                          deps[n]["name"],
                                          stats=net_io))

        t0 = time.perf_counter()
        with span("plan", stats=sc, key="plan_s", stage=stage.name,
                  kind=stage.kind) as sp:
            out = _run_stage(plan, i, stage, ctx, mesh, True, sc,
                             int(spec.get("stage_shards", 0)), sp)
        wall = round(time.perf_counter() - t0, 4)
        arrays, meta = _commit_payload(plan, stage, out, True)
        blob = pack_commit(arrays, meta)
        name = payload_name(i, stage.name)
        crc = srv.put(name, blob)
        ready = {"addr": srv.address, "name": name, "crc": crc,
                 "payload_bytes": len(blob), "stage_wall_s": wall,
                 "net": dict(net_io)}
        with atomic_write(spec["ready"], mode="w") as f:
            json.dump(ready, f, sort_keys=True)
        # Linger as a server: consumers (later stages, the collecting
        # parent) fetch on their own schedule; the parent terminates us.
        while True:
            time.sleep(3600)
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
