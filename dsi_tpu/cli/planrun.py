"""Multi-stage dataflow plan runner — chained jobs, no host round-trip.

Runs one of the canonical plans (``dsi_tpu/plan``) end to end: stages
execute as resumable step objects and the intermediate between them
stays DEVICE-RESIDENT (stage N+1's upload is stage N's output —
``device/relay.py``), against the ``--staged`` baseline that
materializes every intermediate through the host the way the 6.5840
contract does.  Stage boundaries are durable commit points
(``--checkpoint-dir``): a crash anywhere in the chain resumes from the
last COMPLETED stage (``--resume``), never from zero.

Chains:
  grep-wc   — grep → word count over exactly the matching lines;
              writes the word counts as mr-out-<r> files in --workdir.
  grep-grep — grep → grep: a narrowing filter cascade (lines with
              --pattern, of those, lines with --pattern2); writes
              plan-grep.json with the final match counts.
  wc-topk   — word count → top-k highest-count words (host reduction
              over the full table); writes plan-topk.json.
  indexer   — indexer → df-top-k (k-row snapshot off the resident df
              table) → per-term postings join; commits the whole
              inverted index as mr-out-<r> files in --workdir, one line
              a term, "<word> <n> <doc>,<doc>,..." (a document is an
              input file, named by its basename; names sorted and
              unique; the term in partition ihash(word) % nreduce:
              merged and sorted, what mrsequential with apps/indexer
              writes over the same names), and writes plan-join.json
              (the --topk terms of highest document frequency with
              their postings).  --pack-docs fills each wave with
              whole documents, --chunk-bytes a device, for a
              collection of many small documents (a page, a mail);
              a document longer than that still goes alone.  The
              committed bytes are the same.  The documents are read
              AHEAD of the walk: only their lengths are taken before
              the first stage, and a pool of reader threads
              (utils/ioread.ReadAheadDocs) fetches their bytes in the
              order the waves will ask, while the device works.  A
              file that cannot be read, or that grew or was cut after
              its length was taken, fails the job (exit 1, nothing
              committed).  --stats: read_s (the lengths), read_wait_s
              (the walk held by a document not yet read),
              read_ahead_hits / read_docs, read_threads.

  sort      — sample → range sort (OSDI'04 section 5.3, TeraSort's
              partitioner) over files of whole 100-byte records
              (gensort's: the key is bytes 0-9, compared as unsigned
              bytes; the record is opaque otherwise).  Stage sample
              reads --sort-sample keys (default 100,000; fewer where
              the input holds fewer) at evenly spaced record offsets,
              host side, sorts them and takes --nreduce - 1 split
              points at the equal-count positions.  Stage sort takes
              every record up in steps of --chunk-bytes (whole
              records) into a store that stays on the device, counts
              the records a partition against the split points there,
              and orders the store by key; ties keep input order (file
              order, then offset).  The ordered records are pulled and
              committed as mr-out-0 .. mr-out-<nreduce-1>, each the
              concatenation of its records in key order and every key
              of mr-out-<r> less than or equal to every key of
              mr-out-<r+1>: their concatenation in partition order is
              the sorted input.  --devices N (default 1) sorts across
              N devices: the same sample gives N - 1 device split
              points beside the partitions', every device owns one key
              range, a step is --chunk-bytes a device, and every
              record goes to the device that owns its key through the
              mesh's all_to_all, is ordered there and pulled from
              there, device 0's records first; the committed bytes are
              --devices 1's.  A device's store holds its share of the
              records by the sample and a sixteenth more: a key range
              the sample undercounts by more than that fails the job
              (exit 1, nothing committed, the device named).  A file
              whose length is not a multiple of 100 is refused before
              any stage; there is no host path: --staged fails the job
              (exit 1, nothing committed); --check, --hosts,
              --checkpoint-dir, --pipeline and --stage-shards are not
              this chain's.

  agg       — one stage: SELECT key, SUM(value) ... GROUP BY key over
              files of rows f0|f1|... ended by a newline (Pavlo et al.,
              SIGMOD'09, the Aggregation Task over UserVisits).  The key
              is field 0, 1-16 bytes of printable ASCII other than '|'; the value
              is field 3, [0-9]{1,3}(.[0-9]{1,6})?, summed exactly as a
              count of 10^-6 units; the other fields are not read (a
              file's last row may lack its newline).  --agg-prefix N
              groups by the first N bytes of the key
              (SUBSTR(sourceIP, 1, N)).  The rows are read and grouped
              on the device, by the word count's engine with this map
              in the tokenizer's place; commits mr-out-<r>, one line a
              key, "<key> <integer part>.<six digits>", the key in
              partition ihash(key) % nreduce, each file in key order.
              A row with fewer than four fields, a key of 0 or over 16
              bytes, or a value outside the grammar fails the job: exit
              1, nothing committed, the first such row's file and line
              in the message; there is no host path.  --staged,
              --check, --hosts, --checkpoint-dir, --pipeline,
              --stage-shards, --device-accumulate, --mesh-shards and
              --aot are not this chain's.

  join      — one stage: SELECT sourceIP, SUM(adRevenue), AVG(pageRank)
              FROM Rankings, UserVisits WHERE pageURL = destURL AND
              visitDate BETWEEN FROM AND TO GROUP BY sourceIP (Pavlo et
              al., SIGMOD'09, the Join Task), and the row of the largest
              sum.  --join-build FILE (repeatable) are the build side:
              rows pageURL|pageRank|..., the key 1-100 bytes of printable
              ASCII other than '|' and a primary key, the rank
              [0-9]{1,9}.  The input files are the probe side: rows
              sourceIP|destURL|visitDate|adRevenue|..., sourceIP 1-16
              bytes, destURL 1-100, the date YYYY-MM-DD, the value as
              --chain agg's.  --join-dates FROM:TO is the window, both
              ends inclusive, dates compared as their ten bytes.  The
              build side becomes a table that stays on the device,
              ordered by a hash of its keys; the probe side streams past
              it: the date compared, the rows inside the window looked
              up by their whole key (every byte decides: two keys of one
              hash re-order the table under another salt, and never
              match), and the matched rows' revenue, rank and count
              summed by sourceIP on the device, exactly, 64 bits wide.
              Commits mr-out-<r>, one line a sourceIP with a joined row,
              "<sourceIP> <sum>.<six digits> <mean>.<six digits>" (the
              mean is rank sum * 10^6 // rows: truncated, no float), the
              key in partition ihash(sourceIP) % nreduce, each file in
              key order, and plan-top.json: {"top": {"sourceIP",
              "totalRevenue", "avgPageRank"}}, the line of the largest
              sum (ties: the least sourceIP), or {"top": null} where no
              row joined.  A row of either table that cannot be read,
              and a second row with one pageURL, fail the job: exit 1,
              nothing committed, the rows' files and lines in the
              message; there is no host path.  --devices other than 1,
              --staged, --check, --hosts, --checkpoint-dir, --pipeline,
              --stage-shards, --device-accumulate, --mesh-shards and
              --aot are not this chain's.

Elastic execution (ISSUE 16): ``--pipeline`` overlaps a grep→wordcount
pair (the wordcount consumes relay buffers as they SEAL while the grep
is still producing; strict/staged stays the bit-parity oracle);
``--stage-shards K`` runs a file-backed source stage as K concurrent
newline-aligned shard attempts merged through the deterministic shard
codecs.

Usage:
    python -m dsi_tpu.cli.planrun --chain grep-wc --pattern PAT
        [--pattern2 PAT] [--pipeline] [--stage-shards K] [--pack-docs]
        [--staged] [--chunk-bytes B] [--devices D] [--pipeline-depth K]
        [--device-accumulate] [--sync-every K] [--mesh-shards N]
        [--nreduce N] [--u-cap U] [--topk K] [--sort-sample N]
        [--agg-prefix N] [--join-build FILE]... [--join-dates FROM:TO]
        [--aot]
        [--checkpoint-dir DIR] [--resume] [--workdir DIR] [--check]
        [--stats] [--stats-json FILE] [--trace-dir DIR] inputfiles...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def _plan_spec(args) -> dict:
    """The plan-rebuild spec (``plan.stagehost.build_plan`` input) this
    argv describes — the single source both the in-process paths and
    every ``--hosts`` stage host rebuild the plan from."""
    return {"chain": args.chain, "pattern": args.pattern,
            "pattern2": args.pattern2, "files": list(args.files),
            "chunk_bytes": args.chunk_bytes, "depth": args.pipeline_depth,
            "device_accumulate": args.device_accumulate,
            "sync_every": args.sync_every,
            "mesh_shards": args.mesh_shards, "aot": args.aot,
            "n_reduce": args.nreduce, "u_cap": args.u_cap,
            "topk": args.topk, "devices": args.devices,
            "pack_docs": args.pack_docs, "sample": args.sort_sample,
            "agg_prefix": args.agg_prefix,
            "join_build": list(args.join_build),
            "join_dates": list(args.join_dates or ())}


def _run_hosts(args, spec: dict, plan, mesh):
    """``--hosts``: every stage in its OWN process with a PRIVATE
    working directory; inter-stage bytes move ONLY over TCP (net-served
    plan relays, ISSUE 18).  Spawns one ``plan.stagehost`` per stage in
    topo order (each handed its deps' ``{addr, name, crc}`` from their
    ready files), then collects every stage's sealed payload over the
    stream transport to assemble the PlanResult.  Returns
    ``(PlanResult, stats_dict)``; raises RuntimeError on a stage
    failure or timeout."""
    import shutil
    import subprocess

    from dsi_tpu.obs import metrics_scope
    from dsi_tpu.plan.driver import PlanResult, _load_commit, plan_index
    from dsi_tpu.plan.stagehost import fetch_stage_payload
    from dsi_tpu.utils.atomicio import atomic_write

    order = plan.ordered()
    sc = metrics_scope("plan")
    sc.update({"plan_stages": len(order), "plan_intermediate_bytes": 0,
               "plan_commit_bytes": 0, "plan_resumed_stages": 0,
               "plan_handoff": "net", "plan_pipelined": 0,
               "plan_stage_shards": max(0, args.stage_shards),
               "plan_overlap_s": 0.0, "plan_s": 0.0,
               "plan_stage_walls": {}})
    net_io = metrics_scope("net")
    os.makedirs(args.workdir, exist_ok=True)
    procs: list = []
    stage_dirs: list = []
    readies: dict = {}
    deadline = time.monotonic() + args.timeout
    try:
        for i, stage in enumerate(order):
            sdir = os.path.join(args.workdir, f"stage-{i}")
            os.makedirs(os.path.join(sdir, "spool"), exist_ok=True)
            stage_dirs.append(sdir)
            host_spec = {
                "plan": spec, "stage_index": i,
                "stage_shards": max(0, args.stage_shards),
                "spool": os.path.join(sdir, "spool"),
                "ready": os.path.join(sdir, "ready.json"),
                "deps": {d: {"addr": readies[d]["addr"],
                             "name": readies[d]["name"],
                             "crc": readies[d]["crc"]}
                         for d in stage.deps},
            }
            spec_path = os.path.join(sdir, "spec.json")
            with atomic_write(spec_path, mode="w") as f:
                json.dump(host_spec, f, sort_keys=True)
            # dsicheck: allow[raw-write] child console capture, not durable state
            logf = open(os.path.join(sdir, "stage.log"), "wb")
            proc = subprocess.Popen(
                [sys.executable, "-m", "dsi_tpu.plan.stagehost",
                 "--spec", spec_path],
                stdout=logf, stderr=subprocess.STDOUT,
                env=dict(os.environ))
            procs.append((proc, logf))
            ready_path = host_spec["ready"]
            while not os.path.exists(ready_path):
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"stage host {i} ({stage.name}) exited "
                        f"rc={proc.returncode} before ready — see "
                        f"{sdir}/stage.log")
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"stage host {i} ({stage.name}) not ready "
                        f"within --timeout {args.timeout}s")
                time.sleep(0.05)
            with open(ready_path, "r", encoding="utf-8") as f:
                readies[stage.name] = json.load(f)
            r = readies[stage.name]
            sc["plan_stage_walls"][stage.name] = r.get("stage_wall_s", 0)
            sc["plan_s"] = round(sc["plan_s"]
                                 + float(r.get("stage_wall_s", 0)), 4)
            # The bytes a stage pulled from its predecessors ARE the
            # inter-stage intermediates — and they crossed only TCP.
            child_net = r.get("net") or {}
            sc["plan_intermediate_bytes"] += \
                int(child_net.get("net_bytes_raw", 0))
            for k, v in child_net.items():
                if k in ("net_ratio",):
                    continue
                if isinstance(v, (int, float)):
                    if k == "net_prefetch_window":
                        net_io[k] = max(int(net_io.get(k, 0) or 0),
                                        int(v))
                    else:
                        net_io[k] = type(v)(net_io.get(k, 0) or 0) + v
        # Share-nothing audit BEFORE any report artifact lands: sealed
        # stage payloads must exist ONLY in the private stage spools —
        # a payload-named file in the SHARED workdir means a stage
        # leaked its relay past the TCP boundary.
        leaked = [n for n in os.listdir(args.workdir)
                  if os.path.isfile(os.path.join(args.workdir, n))
                  and n.startswith("plan-") and n[5:6].isdigit()]
        if leaked:
            raise RuntimeError(
                f"share-nothing audit failed: stage payload(s) "
                f"{leaked} in shared workdir {args.workdir}")
        # Collect: every stage's sealed payload, over TCP, decoded by
        # the stage-commit codec — the same reconstruction the
        # checkpoint/resume path uses, so parity holds by construction.
        ctx = {}
        for i, stage in enumerate(order):
            r = readies[stage.name]
            arrays, meta = fetch_stage_payload(
                r["addr"], r["name"], int(r.get("crc", 0)),
                stats=net_io, timeout=args.timeout)
            ctx[stage.name] = _load_commit(plan, stage, meta, arrays,
                                           mesh, True, sc)
        for k in ("net_fetch_wait_s", "net_overlap_s"):
            if k in net_io:
                net_io[k] = round(float(net_io[k]), 6)
        sc.update(net_io)
        results = {name: out.result for name, out in ctx.items()}
        res = PlanResult(results, ctx[order[-1].name].result, sc,
                         index=plan_index(plan, ctx, sc))
    finally:
        for proc, logf in procs:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            logf.close()
    for sdir in stage_dirs:
        shutil.rmtree(sdir, ignore_errors=True)
    return res, dict(sc)


def _top_row(table) -> "dict | None":
    """The join's second statement over its committed table: the row of
    the largest sum, the least key among equals (the table is in key
    order, and ``argmax`` takes the first); None of an empty table."""
    import numpy as np

    from dsi_tpu.ops.wordcount import decode_packed

    if not len(table):
        return None
    at = np.array([np.argmax(table.cnts[:, 0])])
    unit = 10 ** table.decimals
    return {"sourceIP": decode_packed(table.skeys[at], table.lens[at], 1)[0],
            **{name: f"{int(v) // unit}.{int(v) % unit:0{table.decimals}d}"
               for name, v in (("totalRevenue", table.cnts[at[0], 0]),
                               ("avgPageRank", table.means(at)[0]))}}


def _job(args, pstats: dict, opened: list):
    """The job between its parsed arguments and its ``--stats`` line:
    ``(exit code, what --stats-json and --check read after it)``.
    ``pstats`` fills as ``--stats`` prints it."""
    from dsi_tpu.obs import span

    with span("start", lane="host", stats=pstats):
        if args.chain == "sort":
            # Not a truncated sort: refused before any stage (and before
            # JAX is imported: a record is ops/sortk.RECORD_BYTES long).
            for path in args.files:
                if os.path.getsize(path) % 100:
                    print(f"planrun: {path}: {os.path.getsize(path)} bytes "
                          "is not a whole number of 100-byte records",
                          file=sys.stderr)
                    return 1, None

        from dsi_tpu.utils.platformpin import require_device

        require_device("planrun")

        from dsi_tpu.ckpt import CheckpointMismatch
        from dsi_tpu.ops.fieldsum import BadRow
        from dsi_tpu.parallel.joinstream import DuplicateKey, HashCollision
        from dsi_tpu.parallel.shuffle import default_mesh
        from dsi_tpu.parallel.sortstream import StoreOverfull
        from dsi_tpu.plan import PlanHostPath, run_plan
        from dsi_tpu.plan.stagehost import build_plan

        mesh = default_mesh(args.devices)
        spec = _plan_spec(args)

        def build():
            plan = build_plan(spec)
            if args.chain == "indexer":
                opened.append(plan.param(plan["indexer"], "docs"))
            return plan

        read_ahead = args.chain == "indexer" and not args.hosts
        plan = None if read_ahead else build()

    stats: dict = {}
    try:
        if read_ahead:
            # What the chain's job pays for its documents before its
            # first stage: their lengths.  Their bytes are read ahead of
            # the walk (``read_wait_s`` is what the walk waits); the
            # other chains' stages read theirs.
            with span("read", lane="host", stats=pstats, key="read_s",
                      files=len(args.files)):
                plan = build()
        if args.hosts:
            res, stats = _run_hosts(args, spec, plan, mesh)
        else:
            res = run_plan(plan, mesh=mesh, staged=args.staged,
                           checkpoint_dir=args.checkpoint_dir,
                           resume=args.resume, pipelined=args.pipeline,
                           stage_shards=args.stage_shards, stats=stats)
    except CheckpointMismatch as e:
        print(f"planrun: {e}", file=sys.stderr)
        return 1, None
    except OSError as e:
        if args.chain != "indexer" or args.hosts:
            raise
        # A document that cannot be read, or is not the bytes its
        # length was taken from: nothing is committed.
        print(f"planrun: {e}", file=sys.stderr)
        return 1, None
    except (BadRow, StoreOverfull, DuplicateKey, HashCollision) as e:
        # --chain agg and join: a row that cannot be read fails the job;
        # --chain sort: so does a device whose key range outgrew its
        # store; --chain join: and a build key held twice, or two of one
        # hash under every salt.
        print(f"planrun: {e}", file=sys.stderr)
        return 1, None
    except PlanHostPath as e:
        # The chain contract is device-resident intermediates; a
        # host-path input breaks it loudly — run the standalone engines
        # (wcstream/grepstream) for such inputs.
        print(f"planrun: {e}", file=sys.stderr)
        return 1, None
    except RuntimeError as e:
        # --hosts orchestration failures (stage host died, deadline,
        # share-nothing audit) — loud, nonzero, no partial artifacts.
        if not args.hosts:
            raise
        print(f"planrun: {e}", file=sys.stderr)
        return 1, None

    committed = None  # what goes out as mr-out-<r>: a merged table
    with span("report", lane="host", stats=pstats):
        if args.resume:
            print(f"planrun: resumed past "
                  f"{stats.get('plan_resumed_stages', 0)} committed "
                  f"stage(s)", file=sys.stderr)
        for name, wall in stats.get("plan_stage_walls", {}).items():
            print(f"planrun: stage {name}: {wall}s", file=sys.stderr)
        print(f"planrun: handoff={stats.get('plan_handoff')} "
              f"intermediate_bytes={stats.get('plan_intermediate_bytes')} "
              f"commit_bytes={stats.get('plan_commit_bytes')}",
              file=sys.stderr)

        os.makedirs(args.workdir, exist_ok=True)
        # What --stats prints as pipeline_stats: the engines' scopes per
        # stage, the plan scope, and the commit below.
        pstats.update(stages=stats.get("stage_stats", {}),
                      plan={k: v for k, v in stats.items()
                            if k != "stage_stats"})
        if "read_s" in pstats:
            ahead = opened[0].stats
            pstats["read_s"] = round(pstats["read_s"], 4)
            pstats.update(ahead,
                          read_wait_s=round(ahead["read_wait_s"], 4))
        if args.chain == "grep-wc":
            g = res.results["grep"]
            print(f"planrun: grep lines={g.lines} matched={g.matched} "
                  f"occurrences={g.occurrences}", file=sys.stderr)
            committed = res.final
        elif args.chain == "agg":
            committed = res.final
            print(f"planrun: {stats['stage_stats']['agg']['agg_rows']} rows "
                  f"in {len(committed)} groups -> {args.workdir}/mr-out-0.."
                  f"{args.nreduce - 1}", file=sys.stderr)
        elif args.chain == "join":
            committed = res.final
            join = stats["stage_stats"]["join"]
            print(f"planrun: {join['join_matched_rows']} of "
                  f"{join['join_probe_rows']} rows joined with "
                  f"{join['join_build_rows']} in {len(committed)} groups -> "
                  f"{args.workdir}/mr-out-0..{args.nreduce - 1}",
                  file=sys.stderr)
        elif args.chain == "indexer":
            # The table the join stage grouped, its documents named as
            # the host app names them (mrsequential in the files'
            # directory).
            committed = res.index.named(
                [os.path.basename(path) for path in args.files])
    if args.chain == "sort":
        from dsi_tpu.parallel.sortstream import write_sorted_output

        with span("write", lane="host", stats=pstats,
                  keys=res.final.records) as sp:
            paths = write_sorted_output(res.final, args.workdir,
                                        stats=pstats)
            sp.set(bytes=sum(os.path.getsize(path) for path in paths))
        pstats["write_s"] = round(pstats["write_s"], 4)
    if committed is not None:
        from dsi_tpu.parallel.shuffle import write_partitioned_output

        with span("write", lane="host", stats=pstats,
                  keys=len(committed)) as sp:
            paths = write_partitioned_output(committed, args.nreduce,
                                             args.workdir, stats=pstats)
            sp.set(bytes=sum(os.path.getsize(path) for path in paths))
        for key in ("write_s", "write_format_s", "write_commit_s"):
            pstats[key] = round(pstats[key], 4)
    with span("report", lane="host", stats=pstats):
        if args.chain == "sort":
            print(f"planrun: {res.final.records} records in key order -> "
                  f"{args.workdir}/mr-out-0..{args.nreduce - 1}",
                  file=sys.stderr)
        elif args.chain == "grep-grep":
            stages = {name: {"lines": r.lines, "matched": r.matched,
                             "occurrences": r.occurrences}
                      for name, r in res.results.items()}
            path = os.path.join(args.workdir, "plan-grep.json")
            # dsicheck: allow[raw-write] report artifact, not durable state
            with open(path, "w", encoding="utf-8") as f:
                json.dump(stages, f, sort_keys=True, indent=1)
            g2 = res.final
            print(f"planrun: cascade matched={g2.matched} "
                  f"occurrences={g2.occurrences} -> {path}",
                  file=sys.stderr)
        elif args.chain == "wc-topk":
            path = os.path.join(args.workdir, "plan-topk.json")
            # dsicheck: allow[raw-write] report artifact, not durable state
            with open(path, "w", encoding="utf-8") as f:
                json.dump({"topk": [[int(c), w] for c, w in res.final]},
                          f, sort_keys=True, indent=1)
            print(f"planrun: top-{len(res.final)} words -> {path}",
                  file=sys.stderr)
        elif args.chain == "join":
            path = os.path.join(args.workdir, "plan-top.json")
            top = _top_row(committed)
            # dsicheck: allow[raw-write] report artifact, not durable state
            with open(path, "w", encoding="utf-8") as f:
                json.dump({"top": top}, f, sort_keys=True, indent=1)
            print(f"planrun: top row {top} -> {path}", file=sys.stderr)
        elif args.chain == "indexer":
            out = {w: {"df": df, "part": part, "docs": list(docs)}
                   for w, (df, part, docs) in res.final.items()}
            path = os.path.join(args.workdir, "plan-join.json")
            # dsicheck: allow[raw-write] report artifact, not durable state
            with open(path, "w", encoding="utf-8") as f:
                json.dump({"topk": [[c, w] for c, w in
                                    res.results.get("dftopk", ())],
                           "join": out}, f, sort_keys=True, indent=1)
            print(f"planrun: index of {len(committed)} terms -> "
                  f"{args.workdir}/mr-out-*; join of {len(out)} terms -> "
                  f"{path}", file=sys.stderr)
    return 0, (stats, res, mesh, build)


def main(argv=None) -> int:
    # The indexer chain's documents are read by a pool of threads
    # (``ioread.ReadAheadDocs``); this process runs job after job, and
    # no reader thread outlives the call, however it ends.
    opened: list = []
    try:
        return _main(argv, opened)
    finally:
        for docs in opened:
            docs.close()


def _main(argv, opened: list) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("files", nargs="+")
    p.add_argument("--chain",
                   choices=("grep-wc", "grep-grep", "wc-topk",
                            "indexer", "sort", "agg", "join"),
                   default="grep-wc",
                   help="grep-wc commits the word counts of the matching "
                        "lines as mr-out-<r>; indexer commits the whole "
                        "inverted index as mr-out-<r> (a document is an "
                        "input file, read ahead of the wave walk by a "
                        "pool of reader threads) and writes "
                        "plan-join.json; grep-grep and wc-topk write "
                        "plan-grep.json / plan-topk.json; sort commits "
                        "the input's 100-byte records ordered by their "
                        "10-byte key as mr-out-<r>, range-partitioned "
                        "from a sample of the keys; agg commits field "
                        "3's decimal sum by field 0 of |-delimited rows "
                        "as mr-out-<r>; join commits, by field 0 of the "
                        "input's rows inside --join-dates whose field 1 "
                        "is a key of --join-build's rows, field 3's sum "
                        "and the mean of those rows' ranks as mr-out-<r>, "
                        "and the row of the largest sum as plan-top.json")
    p.add_argument("--pattern", default=None,
                   help="literal grep pattern (required for grep-wc "
                        "and grep-grep)")
    p.add_argument("--pattern2", default=None,
                   help="second-stage literal pattern (required for "
                        "grep-grep)")
    p.add_argument("--pipeline", action="store_true",
                   help="overlap a grep→wordcount pair: stage N+1 "
                        "consumes sealed relay buffers while stage N "
                        "still produces (chained mode only)")
    p.add_argument("--stage-shards", type=int, default=0,
                   help="run a file-backed source stage as K "
                        "concurrent shard attempts (0 = off)")
    p.add_argument("--staged", action="store_true",
                   help="run the HOST-materialization baseline: every "
                        "inter-stage intermediate is pulled to the host "
                        "and re-fed (the 6.5840 shape) — results are "
                        "bit-identical to the chained default")
    p.add_argument("--chunk-bytes", type=_positive_int, default=1 << 20)
    p.add_argument("--pack-docs", action="store_true",
                   help="--chain indexer: fill each wave with whole "
                        "documents, a chunk of --chunk-bytes a device, "
                        "grouped by (word, document) on the device "
                        "(default: one document a device a wave, padded "
                        "to the power of two of its own length); a "
                        "document longer than --chunk-bytes goes alone, "
                        "none is split; the committed index is the same")
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--pipeline-depth", type=_positive_int, default=None)
    p.add_argument("--device-accumulate", action="store_true")
    p.add_argument("--sync-every", type=_positive_int, default=None)
    p.add_argument("--mesh-shards", type=int, default=None)
    p.add_argument("--nreduce", type=_positive_int, default=10)
    p.add_argument("--u-cap", type=_positive_int, default=1 << 12)
    p.add_argument("--topk", type=_positive_int, default=16)
    p.add_argument("--sort-sample", type=_positive_int, default=100_000,
                   help="--chain sort: keys the sampling pre-pass reads "
                        "for the split points (TeraSort's "
                        "mapreduce.terasort.partitions.sample)")
    p.add_argument("--agg-prefix", type=int, default=0,
                   help="--chain agg: group by the first N bytes of the "
                        "key field (0, the default: the whole field)")
    p.add_argument("--join-build", action="append", default=[],
                   metavar="FILE",
                   help="--chain join: a file of the build side's rows "
                        "key|rank|... (repeatable, in input order); the "
                        "input files are the probe side")
    p.add_argument("--join-dates", default=None, metavar="FROM:TO",
                   help="--chain join: the window of the probe rows' "
                        "dates, YYYY-MM-DD each, both inclusive")
    p.add_argument("--aot", action="store_true")
    p.add_argument("--checkpoint-dir", default=None,
                   help="stage-manifest commits land here: each "
                        "completed stage writes a durable manifest "
                        "(ckpt/store.py discipline) — see --resume")
    p.add_argument("--resume", action="store_true",
                   help="skip every stage whose manifest verifies and "
                        "continue from the last completed stage's "
                        "commit point")
    p.add_argument("--workdir", default=".")
    p.add_argument("--hosts", action="store_true",
                   help="net-served plan relays: run every stage in its "
                        "OWN process with a PRIVATE working directory; "
                        "inter-stage bytes move only over TCP (the "
                        "share-nothing harness, audited)")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="--hosts per-run deadline: seconds to wait for "
                        "all stage hosts to come ready")
    p.add_argument("--check", action="store_true",
                   help="also run the OTHER handoff mode (staged vs "
                        "chained) in-process and verify the results "
                        "are identical")
    p.add_argument("--stats", action="store_true")
    p.add_argument("--stats-json", default=None,
                   help="write the plan stats scope (plan_* keys) as "
                        "JSON there — the bench row's parse surface")
    p.add_argument("--trace-dir", default=None)
    args = p.parse_args(argv)

    if args.resume and not args.checkpoint_dir:
        p.error("--resume requires --checkpoint-dir")
    if args.chain in ("grep-wc", "grep-grep") and not args.pattern:
        p.error(f"--chain {args.chain} requires --pattern")
    if args.chain == "grep-grep" and not args.pattern2:
        p.error("--chain grep-grep requires --pattern2")
    if args.pack_docs and args.chain != "indexer":
        p.error("--pack-docs packs the documents of --chain indexer")
    if args.chain == "sort":
        for flag in ("check", "hosts", "checkpoint_dir", "pipeline",
                     "stage_shards"):
            if getattr(args, flag):
                p.error(f"--{flag.replace('_', '-')} is not --chain "
                        "sort's: the chain has one handoff mode and "
                        "commits once, after its last stage")
        if args.devices is not None and args.devices < 1:
            p.error("--chain sort orders its records on --devices 1 or "
                    "more")
        args.devices = args.devices or 1
    if args.agg_prefix and args.chain != "agg":
        p.error("--agg-prefix cuts the key of --chain agg")
    if args.chain == "agg":
        if args.agg_prefix < 0:
            p.error("--agg-prefix is a number of bytes: 0 or more")
        for flag in ("staged", "check", "hosts", "checkpoint_dir",
                     "pipeline", "stage_shards", "device_accumulate",
                     "mesh_shards", "aot"):
            if getattr(args, flag):
                p.error(f"--{flag.replace('_', '-')} is not --chain "
                        "agg's: one stage, summed through the host "
                        "merge and committed once")
    if (args.join_build or args.join_dates) and args.chain != "join":
        p.error("--join-build and --join-dates are --chain join's")
    if args.chain == "join":
        if not args.join_build or not args.join_dates:
            p.error("--chain join requires --join-build and --join-dates")
        from dsi_tpu.plan.graph import parse_dates

        try:
            args.join_dates = list(parse_dates(args.join_dates))
        except ValueError as e:
            p.error(f"--join-dates: {e}")
        if args.devices not in (None, 1):
            p.error("--chain join holds its table on --devices 1: across "
                    "a mesh both sides first need the exchange by key")
        args.devices = 1
        for flag in ("staged", "check", "hosts", "checkpoint_dir",
                     "pipeline", "stage_shards", "device_accumulate",
                     "mesh_shards", "aot"):
            if getattr(args, flag):
                p.error(f"--{flag.replace('_', '-')} is not --chain "
                        "join's: one stage, its table on the device, "
                        "summed through the host merge and committed once")
    if args.pipeline and args.staged:
        p.error("--pipeline is chained-mode only (staged execution "
                "stays strictly sequential: it is the parity oracle)")
    if args.hosts and args.pipeline:
        p.error("--hosts runs stages in separate processes; the "
                "in-process relay overlap (--pipeline) cannot cross "
                "them")
    if args.hosts and (args.checkpoint_dir or args.resume):
        p.error("--hosts has its own commit surface (sealed stage "
                "payloads); --checkpoint-dir/--resume are the "
                "in-process stage-manifest path")
    if args.hosts and args.staged:
        p.error("--hosts is its own handoff mode (net); --staged is "
                "the in-process host-materialization baseline")

    if args.trace_dir:
        from dsi_tpu.obs import configure_tracing

        configure_tracing(trace_dir=args.trace_dir)

    from dsi_tpu.obs import span
    from dsi_tpu.obs.registry import plan_job_children_s

    # The root of the main thread's account, as in wcstream: its direct
    # children (the registry's PLAN_JOB_CHILDREN) cover it.
    pstats: dict = {}
    with span("job", lane="host", stats=pstats):
        rc, ran = _job(args, pstats, opened)
    if rc:
        return rc
    stats, res, mesh, build = ran
    for key in ("job_s", "start_s", "report_s"):
        pstats[key] = round(pstats[key], 4)
    pstats["job_children_s"] = round(plan_job_children_s(pstats), 4)
    # After the commit, so that the line holds the job's tail too
    # (write_s) and the trace its last span.
    if args.stats:
        print(f"planrun: plan_stats={pstats['plan']}", file=sys.stderr)
        print(f"planrun: pipeline_stats={pstats}", file=sys.stderr)
    if args.stats_json:
        # dsicheck: allow[raw-write] bench parse surface, not durable state
        with open(args.stats_json, "w", encoding="utf-8") as f:
            json.dump({k: v for k, v in stats.items()}, f, default=str)
    if args.trace_dir:
        from dsi_tpu.obs import flush_tracing_report

        flush_tracing_report(args.trace_dir, "planrun")

    if args.check:
        # The twin runs the OTHER handoff mode under the SAME shard
        # fan-out: stage-sharded grep merges zero the order-sensitive
        # topk sample, so parity only holds shard-geometry-to-like.
        # Against --hosts the twin is the in-process chained run — the
        # net-served relays must reproduce it bit-identically.
        from dsi_tpu.plan import run_plan

        twin_staged = False if args.hosts else not args.staged
        twin = run_plan(build(), mesh=mesh, staged=twin_staged,
                        stage_shards=args.stage_shards)
        modes = ("hosts vs chained" if args.hosts
                 else "chained vs staged")
        ok = twin.final == res.final
        if args.chain == "grep-wc":
            ok = ok and twin.results["grep"] == res.results["grep"]
        elif args.chain == "grep-grep":
            ok = ok and twin.results == res.results
        if not ok:
            print(f"planrun: PARITY FAILURE {modes}", file=sys.stderr)
            return 2
        print(f"planrun: parity OK ({modes})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
