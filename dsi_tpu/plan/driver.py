"""Plan driver: run a Stage DAG with device-resident handoffs.

The execution half of ``dsi_tpu/plan`` (graph model in
``plan/graph.py``): stages run in topological order, each as a
resumable step object (``parallel/stepobj.py``) driven one ``advance()``
at a time, and the edge between two stages is a relay
(``device/relay.py``) — stage N+1's upload IS stage N's device-resident
output.  ``staged=True`` swaps every relay for its host flavor (full
materialization between stages), which is both the A/B baseline the
bench row measures against and the bit-parity oracle the tests compare
with: the two modes produce identical results by construction.

## Stage commits (crash-resume at stage granularity)

With ``checkpoint_dir``, each completed stage writes a durable STAGE
MANIFEST through the existing checkpoint machinery
(``ckpt/store.py`` — CRC'd payload + manifest, newest-valid-wins): the
stage's result plus whatever its downstream edge needs (the relay
image, the indexer's service images).  A ``resume=True`` run walks the
stage stores in plan order and skips every stage whose manifest
verifies, reconstructing its outputs host-side — so a crash ANYWHERE in
the chain (including a real ``os._exit`` mid-stage, the CI smoke)
resumes from the last completed stage's commit point, not from zero.  A
torn stage manifest simply fails verification and that stage re-runs
from its upstream's commit — the fallback the ckpt store's
newest-valid-wins walk already owes us.

Fault points (``ckpt/fault.py`` discipline, arbitrary names accepted):
``plan-stage<i>-advance`` fires per ``advance()`` of stage *i* (so
"kill mid-stage-2" is deterministic regardless of how many steps stage
1 ran), and ``post-stage-commit`` right after a stage manifest lands.

Blind spots, stated: intra-stage engine checkpoints are disabled on
chained stages (a byte cursor has no meaning over a device relay), so a
crash mid-stage re-runs THAT stage from its upstream commit; a stream
that needs the host path (non-ASCII, non-literal pattern) fails the
chain loudly instead of silently degrading — run the engines standalone
for host-path inputs.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from dsi_tpu.ckpt import CheckpointStore, fault_point
from dsi_tpu.obs import metrics_scope, span as _span
from dsi_tpu.plan.graph import Plan, PlanError, Stage


class PlanHostPath(RuntimeError):
    """A stage's engine routed to the host path: the chain cannot keep
    the intermediate on device, and silently degrading would invalidate
    the zero-host-bytes contract — the caller decides what to do."""


class StageOut:
    """One stage's outputs in the driver context: ``result`` (the
    stage's value), ``relay`` (the outgoing byte relay, grep), and
    ``handoff`` (exported live services, indexer).  ``relay_spent``
    marks a relay consumed INSIDE the producing run (the pipelined
    handoff): its stage manifest carries no relay image, so a resume
    may trust it only while the consumer's manifest verifies too.
    ``index`` is the whole postings table a ``postings_join`` stage
    grouped to look its terms up (``merge.PackedPostings``)."""

    __slots__ = ("result", "relay", "handoff", "resumed", "relay_spent",
                 "index")

    def __init__(self, result=None, relay=None, handoff=None,
                 resumed: bool = False, relay_spent: bool = False,
                 index=None):
        self.result = result
        self.relay = relay
        self.handoff = handoff
        self.resumed = resumed
        self.relay_spent = relay_spent
        self.index = index


class PlanResult:
    """``results[name]`` per stage, ``final`` = last stage's result,
    ``stats`` = the run's plan scope (plan_* keys, obs/registry.py),
    with ``stage_stats``: per stage that ran an engine, that engine's
    own scope (``steps``, ``replays``, ``device_rows``, ``upload_s``,
    ``kernel_s``, ... as ``wcstream --stats`` / ``grepstream --stats``
    print them) plus ``bytes_in``; a list of them, in shard order,
    where ``stage_shards`` split the stage.  ``index`` is the whole
    postings table of a plan with a ``postings_join`` stage
    (:func:`plan_index`), None for every other plan."""

    def __init__(self, results: Dict, final, stats: Dict, index=None):
        self.results = results
        self.final = final
        self.stats = stats
        self.index = index


def _spill_bytes(plan: Plan) -> int:
    mb = plan.defaults.get("spill_mb")
    if mb is None:
        try:
            mb = float(os.environ.get("DSI_PLAN_SPILL_MB", "0"))
        except ValueError:
            mb = 0.0
    return int(float(mb) * 1e6)


class _Books:
    """What the driver keeps for one engine of a stage: the dict the
    engine copies its scope into when it closes, and the bytes it was
    given — counted off the block stream as the engine reads it, or
    set by the driver where the input is a relay."""

    def __init__(self, blocks=None):
        self.stats: dict = {}
        self.bytes_in = 0
        self._blocks = blocks

    def __iter__(self):
        for block in self._blocks:
            self.bytes_in += len(block)
            yield block

    def record(self) -> dict:
        return dict(self.stats, bytes_in=self.bytes_in)


def _note_stage(sc: dict, sp, stage: Stage, books: List[_Books],
                sharded: bool = False) -> None:
    """File the stage's engine scopes under ``stage_stats`` and put the
    stage's ``steps`` and ``bytes_in`` on its ``plan`` span, so that a
    ``--trace-dir`` file reads without the stats line."""
    recs = [b.record() for b in books]
    sc["stage_stats"][stage.name] = recs if sharded else recs[0]
    sp.set(steps=sum(int(r.get("steps", 0)) for r in recs),
           bytes_in=sum(r["bytes_in"] for r in recs))


def _drive(step, i: int):
    """Advance stage *i* to completion (rung restarts included) with
    the per-advance fault point, then close."""
    while True:
        fault_point(f"plan-stage{i}-advance")
        if not step.advance():
            break
    return step.close()


def _drive_many(steps, i: int):
    """Round-robin the K shard attempts of stage *i* to completion —
    one ``advance()`` per live step per pass, so the shards' device
    work interleaves instead of running serially, with the same
    per-advance fault point as the single-step path."""
    live = list(steps)
    while live:
        nxt = []
        for st in live:
            fault_point(f"plan-stage{i}-advance")
            if st.advance():
                nxt.append(st)
        live = nxt
    return [st.close() for st in steps]


def _merge_grep_results(results):
    """Sum-merge K shard-grep results: lines/matched/occurrences/hist
    add exactly (shards partition the line stream at newline cuts);
    per-shard top-k ranks by SHARD-LOCAL line numbers and is not
    globally mergeable, so the merged result omits it — the
    ``mr/shards.merge_grep`` precedent."""
    from dsi_tpu.parallel.grepstream import GrepStreamResult

    hist = None
    lines = matched = occurrences = 0
    for r in results:
        lines += r.lines
        matched += r.matched
        occurrences += r.occurrences
        hist = (list(r.hist) if hist is None
                else [a + b for a, b in zip(hist, r.hist)])
    return GrepStreamResult(lines, matched, occurrences,
                            tuple(hist or ()), ())


def _merge_counts(results):
    """Sum-merge K shard-wordcount results ``{word: (count, part)}``:
    counts add (token-safe cuts), the partition is a pure function of
    the word so any shard's value is THE value."""
    total: Dict = {}
    for res in results:
        for w, (c, part) in res.items():
            prev = total.get(w)
            total[w] = (c + prev[0] if prev else c, part)
    return total


def _shard_specs(plan: Plan, stage: Stage, stage_shards: int):
    """The stage's shard plan, or None when sharding doesn't apply: K<2,
    a non-source stage (its input is an upstream relay, not a byte
    range), or a ``data`` source (``plan_shards`` geometry is
    file-backed).  Uses the SAME newline-aligned splitter as the shard
    scheduler — one geometry, one safety argument."""
    if stage_shards <= 1 or stage.deps:
        return None
    paths = plan.param(stage, "paths")
    if not paths:
        return None
    from dsi_tpu.mr.shards import plan_shards

    specs = plan_shards(list(paths), stage_shards)
    return specs if len(specs) > 1 else None


def _spec_blocks(plan: Plan, stage: Stage, spec):
    from dsi_tpu.mr.shards import read_stream_range

    return read_stream_range(list(plan.param(stage, "paths")),
                             spec.start, spec.end)


def _stage_store(checkpoint_dir: str, i: int, stage: Stage,
                 plan_sig: Dict, staged: bool) -> CheckpointStore:
    """One ckpt store per stage, keyed by the plan signature + handoff
    mode: resuming a chained run from a staged run's manifests (or
    either from a different plan) refuses instead of misreading."""
    d = os.path.join(checkpoint_dir, f"stage{i:02d}-{stage.name}")
    return CheckpointStore(d, f"plan-{stage.kind}",
                           {"plan": plan_sig, "stage": stage.name,
                            "staged": bool(staged)})


# ── result codecs (stage-commit payloads) ─────────────────────────────


def _encode_counts(d: Dict) -> Dict[str, np.ndarray]:
    words = sorted(d)
    joined = "\n".join(words).encode("ascii")
    return {"wc_words": np.frombuffer(joined, np.uint8).copy(),
            "wc_cnt": np.array([d[w][0] for w in words], np.int64),
            "wc_part": np.array([d[w][1] for w in words], np.int64)}


def _decode_counts(arrays: Dict[str, np.ndarray]) -> Dict:
    raw = np.asarray(arrays.get("wc_words", np.zeros(0, np.uint8)),
                     np.uint8).tobytes().decode("ascii")
    words = raw.split("\n") if raw else []
    cnt = np.asarray(arrays.get("wc_cnt", np.zeros(0)), np.int64)
    part = np.asarray(arrays.get("wc_part", np.zeros(0)), np.int64)
    return {w: (int(c), int(p)) for w, c, p in zip(words, cnt, part)}


def _encode_words(words: List[str], prefix: str) -> Dict[str, np.ndarray]:
    joined = "\n".join(words).encode("ascii")
    return {f"{prefix}words": np.frombuffer(joined, np.uint8).copy()}


def _decode_words(arrays: Dict[str, np.ndarray], prefix: str) -> List[str]:
    raw = np.asarray(arrays.get(f"{prefix}words", np.zeros(0, np.uint8)),
                     np.uint8).tobytes().decode("ascii")
    return raw.split("\n") if raw else []


def _encode_join(join: Dict) -> Dict[str, np.ndarray]:
    words = sorted(join, key=lambda w: (-join[w][0], w))
    docs_flat: List[int] = []
    offs = [0]
    for w in words:
        docs_flat.extend(join[w][2])
        offs.append(len(docs_flat))
    out = _encode_words(words, "j_")
    out["j_df"] = np.array([join[w][0] for w in words], np.int64)
    out["j_part"] = np.array([join[w][1] for w in words], np.int64)
    out["j_docs"] = np.array(docs_flat, np.int64)
    out["j_offs"] = np.array(offs, np.int64)
    return out


def _decode_join(arrays: Dict[str, np.ndarray]) -> Dict:
    words = _decode_words(arrays, "j_")
    df = np.asarray(arrays.get("j_df", np.zeros(0)), np.int64)
    part = np.asarray(arrays.get("j_part", np.zeros(0)), np.int64)
    docs = np.asarray(arrays.get("j_docs", np.zeros(0)), np.int64)
    offs = np.asarray(arrays.get("j_offs", np.zeros(1)), np.int64)
    return {w: (int(df[i]), int(part[i]),
                tuple(int(x) for x in docs[offs[i]:offs[i + 1]]))
            for i, w in enumerate(words)}


# ── the driver ────────────────────────────────────────────────────────


def run_plan(plan: Plan, *, mesh=None, staged: bool = False,
             checkpoint_dir: Optional[str] = None, resume: bool = False,
             pipelined: bool = False, stage_shards: int = 0,
             stats: Optional[dict] = None) -> PlanResult:
    """Run ``plan`` end to end (module docstring).  ``staged=True`` is
    the host-materialization baseline; results are bit-identical to the
    chained mode by construction.  ``checkpoint_dir`` turns stage
    boundaries into durable commit points; ``resume=True`` skips every
    stage whose manifest verifies.

    ``pipelined=True`` overlaps a grep→wordcount pair: the wordcount
    consumes relay buffers as they SEAL, while the grep is still
    producing (``plan_overlap_s`` attributes the overlapped wall).
    Chained mode only — staged execution stays strictly sequential and
    remains the bit-parity oracle.  ``stage_shards=K`` runs a
    file-backed source stage as K concurrent newline-aligned shard
    attempts (``mr/shards.plan_shards`` geometry) merged through the
    deterministic shard codecs."""
    from dsi_tpu.parallel.shuffle import default_mesh

    if resume and not checkpoint_dir:
        raise PlanError("resume=True requires checkpoint_dir")
    if mesh is None:
        mesh = default_mesh()
    pipelined = bool(pipelined) and not staged
    stage_shards = max(0, int(stage_shards or 0))
    sc = metrics_scope("plan")
    sc.update({"plan_stages": len(plan), "plan_intermediate_bytes": 0,
               "plan_commit_bytes": 0, "plan_resumed_stages": 0,
               "plan_handoff": "host" if staged else "device",
               "plan_pipelined": int(pipelined),
               "plan_stage_shards": stage_shards,
               "plan_overlap_s": 0.0,
               "plan_s": 0.0, "stage_commit_s": 0.0,
               "plan_stage_walls": {}, "stage_stats": {}})
    order = plan.ordered()
    # The job identity of the stage manifests.  It CRCs every document of
    # an indexer stage, so a run that keeps no manifest does not ask.
    sig = plan.signature() if checkpoint_dir else None
    ctx: Dict[str, StageOut] = {}
    completed = 0
    if checkpoint_dir:
        if resume:
            for i, stage in enumerate(order):
                loaded = _stage_store(checkpoint_dir, i, stage, sig,
                                      staged).load_latest()
                if loaded is None:
                    break  # this stage (and everything after) re-runs
                meta, arrays = loaded
                ctx[stage.name] = _load_commit(plan, stage, meta, arrays,
                                               mesh, staged, sc)
                completed += 1
            # A spent-relay manifest (pipelined producer) holds no relay
            # image: it is only trustworthy while its consumer's
            # manifest verifies too.  A consumer always sits LATER in
            # topo order, so a spent producer as the LAST loaded stage
            # means its consumer is missing — the producer must re-run
            # as well (resuming it would hand the consumer an empty
            # relay and silently produce empty counts).
            while completed > 0 \
                    and ctx[order[completed - 1].name].relay_spent:
                del ctx[order[completed - 1].name]
                completed -= 1
            sc["plan_resumed_stages"] = completed
        else:
            for i, stage in enumerate(order):
                _stage_store(checkpoint_dir, i, stage, sig,
                             staged).reset()

    def commit(i: int, stage: Stage, out: StageOut) -> None:
        with _span("stage_commit", lane="plan", stats=sc,
                   key="stage_commit_s", stage=stage.name):
            arrays, meta = _commit_payload(plan, stage, out, staged)
            store = _stage_store(checkpoint_dir, i, stage, sig, staged)
            store.save(arrays, meta)
            sc["plan_commit_bytes"] += store.last_payload_bytes
        fault_point("post-stage-commit")

    i = completed
    while i < len(order):
        stage = order[i]
        nxt = order[i + 1] if i + 1 < len(order) else None
        if (pipelined and stage.kind == "grep" and not stage.deps
                and nxt is not None and nxt.kind == "wordcount"
                and list(nxt.deps) == [stage.name]):
            # The fused pair: both stages run interleaved; commits land
            # afterwards, in plan order, with the grep manifest marked
            # relay-spent (its buffers were consumed in flight).
            t0 = time.perf_counter()
            g_out, w_out, g_wall = _run_pipelined_pair(
                plan, i, stage, nxt, mesh, sc, stage_shards)
            ctx[stage.name] = g_out
            ctx[nxt.name] = w_out
            sc["plan_stage_walls"][stage.name] = round(g_wall, 4)
            sc["plan_stage_walls"][nxt.name] = round(
                time.perf_counter() - t0, 4)
            if checkpoint_dir:
                commit(i, stage, g_out)
                commit(i + 1, nxt, w_out)
            i += 2
            continue
        t0 = time.perf_counter()
        with _span("plan", stats=sc, key="plan_s", stage=stage.name,
                   kind=stage.kind) as sp:
            out = _run_stage(plan, i, stage, ctx, mesh, staged, sc,
                             stage_shards, sp)
        ctx[stage.name] = out
        sc["plan_stage_walls"][stage.name] = round(
            time.perf_counter() - t0, 4)
        if checkpoint_dir:
            commit(i, stage, out)
        i += 1
    sc["plan_s"] = round(sc["plan_s"], 4)
    sc["stage_commit_s"] = round(sc["stage_commit_s"], 4)
    sc["plan_overlap_s"] = round(sc["plan_overlap_s"], 4)
    for k in ("relay_append_s", "relay_spill_s"):
        if k in sc:
            sc[k] = round(sc[k], 4)
    if stats is not None:
        stats.update(sc)
    results = {name: out.result for name, out in ctx.items()}
    return PlanResult(results, ctx[order[-1].name].result, sc,
                      index=plan_index(plan, ctx, sc))


def _engine_kw(plan: Plan, stage: Stage) -> Dict:
    return {
        "chunk_bytes": int(plan.param(stage, "chunk_bytes", 1 << 20)),
        "depth": plan.param(stage, "depth"),
        "aot": bool(plan.param(stage, "aot", False)),
        "device_accumulate": bool(
            plan.param(stage, "device_accumulate", False)),
        "sync_every": plan.param(stage, "sync_every"),
        "mesh_shards": plan.param(stage, "mesh_shards"),
    }


def _source_blocks(plan: Plan, stage: Stage):
    paths = plan.param(stage, "paths")
    data = plan.param(stage, "data")
    if paths:
        from dsi_tpu.parallel.streaming import stream_files

        return stream_files(list(paths))
    if data is not None:
        return [bytes(data)]
    raise PlanError(f"stage {stage.name!r} has neither paths nor data")


class _RelayFeed:
    """Queue-backed ``device_batches`` iterable for the pipelined
    handoff: the driver ``put``s each buffer the moment the producing
    relay seals it, and the consuming wordcount's batch feed blocks on
    the queue instead of on a materialized list.  The driver only
    advances the consumer while fed-but-unconsumed buffers remain
    (one pump dispatches exactly one item — ``pipeline.StepPipeline``
    invariant), so the feed never deadlocks."""

    _DONE = object()

    def __init__(self):
        import queue

        self._q = queue.Queue()

    def put(self, buf) -> None:
        self._q.put(buf)

    def close(self) -> None:
        self._q.put(self._DONE)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                return
            yield item


def _grep_steps(plan: Plan, stage: Stage, relay, mesh, kw,
                stage_shards: int, ctx: Optional[Dict] = None):
    """The stage's grep step(s), each with its books: K shard steps
    over newline-aligned byte ranges when sharding applies, else one
    step over the whole source (or the upstream relay's line stream —
    the cascade).  Returns ``(steps, books, sharded)``."""
    from dsi_tpu.parallel.grepstream import GrepStep

    pattern = plan.param(stage, "pattern")
    topk = int(plan.param(stage, "topk", 16))
    specs = None
    if stage.deps:
        up = ctx[stage.deps[0]]
        sources = [up.relay.blocks() if hasattr(up.relay, "blocks")
                   else up.relay.host_blocks()]
    else:
        specs = _shard_specs(plan, stage, stage_shards)
        sources = ([_source_blocks(plan, stage)] if specs is None
                   else [_spec_blocks(plan, stage, spec) for spec in specs])
    books = [_Books(src) for src in sources]
    steps = [GrepStep(b, pattern, mesh=mesh, topk=topk, line_sink=relay,
                      pipeline_stats=b.stats, **kw) for b in books]
    return steps, books, specs is not None


def _run_pipelined_pair(plan: Plan, i: int, g_stage: Stage,
                        wc_stage: Stage, mesh, sc: dict,
                        stage_shards: int):
    """The fused grep→wordcount pair: the wordcount consumes relay
    buffers as they SEAL while the grep(s) keep producing.  The
    consumer is only advanced while fed-but-unconsumed buffers exist,
    so the interleave can never block on an empty feed; wall spent in
    consumer advances BEFORE the producer finishes is the overlap the
    pipelining bought (``plan_overlap_s``, ``stage_overlap`` spans)."""
    from dsi_tpu.device.relay import DeviceRelay
    from dsi_tpu.parallel.streaming import WordcountStep

    kw = _engine_kw(plan, g_stage)
    relay = DeviceRelay(mesh, cap=kw["chunk_bytes"], aot=kw["aot"],
                        stats=sc, spill_bytes=_spill_bytes(plan))
    gsteps, g_books, sharded = _grep_steps(plan, g_stage, relay, mesh, kw,
                                           stage_shards)
    wkw = _engine_kw(plan, wc_stage)
    feed = _RelayFeed()
    w_books = _Books()
    wc = WordcountStep([], mesh=mesh,
                       n_reduce=int(plan.param(wc_stage, "n_reduce", 10)),
                       u_cap=int(plan.param(wc_stage, "u_cap", 1 << 12)),
                       device_batches=feed, pipeline_stats=w_books.stats,
                       **wkw)
    fed = consumed = 0
    wc_live = True
    t0 = time.perf_counter()
    with _span("plan", stats=sc, key="plan_s", stage=g_stage.name,
               kind="grep") as sp:
        live = list(gsteps)
        while live:
            nxt = []
            for st in live:
                fault_point(f"plan-stage{i}-advance")
                if st.advance():
                    nxt.append(st)
            live = nxt
            for buf in relay.take_sealed():
                feed.put(buf)
                fed += 1
            if wc_live and consumed < fed:
                with _span("stage_overlap", lane="plan", stats=sc,
                           key="plan_overlap_s", stage=wc_stage.name):
                    while wc_live and consumed < fed:
                        fault_point(f"plan-stage{i + 1}-advance")
                        wc_live = wc.advance()
                        consumed += 1
        g_results = [st.close() for st in gsteps]
        _note_stage(sc, sp, g_stage, g_books, sharded)
    g_wall = time.perf_counter() - t0
    if any(r is None for r in g_results):
        feed.close()
        wc.abort()
        raise PlanHostPath(f"stage {g_stage.name!r}: grep needs the "
                           f"host path (non-literal pattern or "
                           f"over-wide line)")
    g_res = (_merge_grep_results(g_results) if sharded
             else g_results[0])
    relay.finish()
    for buf in relay.take_sealed():
        feed.put(buf)
        fed += 1
    feed.close()
    with _span("plan", stats=sc, key="plan_s", stage=wc_stage.name,
               kind="wordcount") as sp:
        while wc_live:
            fault_point(f"plan-stage{i + 1}-advance")
            wc_live = wc.advance()
        w_res = wc.close()
        w_books.bytes_in = relay.total_bytes
        _note_stage(sc, sp, wc_stage, [w_books])
    if w_res is None:
        raise PlanHostPath(f"stage {wc_stage.name!r}: wordcount needs "
                           f"the host path (non-ASCII or >64-byte "
                           f"word)")
    return (StageOut(result=g_res, relay=relay, relay_spent=True),
            StageOut(result=w_res), g_wall)


def _run_stage(plan: Plan, i: int, stage: Stage, ctx: Dict, mesh,
               staged: bool, sc: dict, stage_shards: int, sp) -> StageOut:
    """Run one stage to its end.  ``sp`` is the stage's open ``plan``
    span: a stage that drives an engine notes its books on it."""
    kw = _engine_kw(plan, stage)
    if stage.kind == "grep":
        from dsi_tpu.device.relay import DeviceRelay, HostRelay

        relay = (HostRelay(stats=sc) if staged
                 else DeviceRelay(mesh, cap=kw["chunk_bytes"],
                                  aot=kw["aot"], stats=sc,
                                  spill_bytes=_spill_bytes(plan)))
        steps, books, sharded = _grep_steps(plan, stage, relay, mesh, kw,
                                            stage_shards, ctx)
        results = _drive_many(steps, i) if sharded \
            else [_drive(steps[0], i)]
        _note_stage(sc, sp, stage, books, sharded)
        if any(r is None for r in results):
            raise PlanHostPath(f"stage {stage.name!r}: grep needs the "
                               f"host path (non-literal pattern or "
                               f"over-wide line)")
        if sharded:
            res = _merge_grep_results(results)
        else:
            res = results[0]
            if stage.deps:
                # A cascade stage's line numbers follow the relay's
                # buffer order, which legitimately differs between the
                # two handoff modes — drop the (line_no, occ) ranks so
                # staged and chained results stay bit-comparable, the
                # merge_grep precedent.
                res = res._replace(topk=())
        return StageOut(result=res, relay=relay)

    if stage.kind == "wordcount":
        from dsi_tpu.parallel.streaming import WordcountStep

        wc_kw = dict(kw, n_reduce=int(plan.param(stage, "n_reduce", 10)),
                     u_cap=int(plan.param(stage, "u_cap", 1 << 12)))
        if stage.deps:
            up = ctx[stage.deps[0]]
            if hasattr(up.relay, "blocks"):  # staged / restored host
                books = _Books(up.relay.blocks())
                step = WordcountStep(books, mesh=mesh,
                                     pipeline_stats=books.stats, **wc_kw)
            else:
                books = _Books()
                books.bytes_in = up.relay.total_bytes
                step = WordcountStep([], mesh=mesh,
                                     device_batches=up.relay.batches(),
                                     pipeline_stats=books.stats, **wc_kw)
            res = _drive(step, i)
            _note_stage(sc, sp, stage, [books])
            if res is None:
                raise PlanHostPath(f"stage {stage.name!r}: wordcount "
                                   f"needs the host path (non-ASCII or "
                                   f">64-byte word)")
            return StageOut(result=res)
        # A source wordcount (no upstream): plain stream, K shard
        # attempts when sharding applies.
        specs = _shard_specs(plan, stage, stage_shards)
        books = ([_Books(_source_blocks(plan, stage))] if specs is None
                 else [_Books(_spec_blocks(plan, stage, spec))
                       for spec in specs])
        steps = [WordcountStep(b, mesh=mesh, pipeline_stats=b.stats,
                               **wc_kw) for b in books]
        results = _drive_many(steps, i) if len(steps) > 1 \
            else [_drive(steps[0], i)]
        _note_stage(sc, sp, stage, books, specs is not None)
        if any(r is None for r in results):
            raise PlanHostPath(f"stage {stage.name!r}: wordcount needs "
                               f"the host path (non-ASCII or >64-byte "
                               f"word)")
        return StageOut(result=results[0] if len(results) == 1
                        else _merge_counts(results))

    if stage.kind == "top_k":
        fault_point(f"plan-stage{i}-advance")
        k = int(plan.param(stage, "topk", 16))
        counts = ctx[stage.deps[0]].result
        return StageOut(result=tuple(sorted(
            ((int(c), w) for w, (c, _p) in counts.items()),
            key=lambda r: (-r[0], r[1]))[:k]))

    if stage.kind == "indexer":
        from dsi_tpu.parallel.grepstream import IndexerStep

        docs = plan.param(stage, "docs")
        books = _Books()
        books.bytes_in = sum(getattr(docs, "lengths", None)
                             or map(len, docs))
        step = IndexerStep(docs, mesh=mesh, stats=books.stats,
                           n_reduce=int(plan.param(stage, "n_reduce", 10)),
                           u_cap=int(plan.param(stage, "u_cap", 1 << 15)),
                           topk=int(plan.param(stage, "topk", 16)),
                           keep_services=not staged,
                           depth=kw["depth"],
                           device_accumulate=kw["device_accumulate"],
                           sync_every=kw["sync_every"],
                           mesh_shards=kw["mesh_shards"],
                           pack_docs=bool(plan.param(stage, "pack_docs",
                                                     False)),
                           chunk_bytes=kw["chunk_bytes"])
        res = _drive(step, i)
        _note_stage(sc, sp, stage, [books])
        if res is None:
            raise PlanHostPath(f"stage {stage.name!r}: indexer needs "
                               f"the host path (non-ASCII or >64-byte "
                               f"word)")
        if staged:
            return StageOut(result=res)
        return StageOut(result=None, handoff=step.exported)

    if stage.kind == "df_topk":
        fault_point(f"plan-stage{i}-advance")
        k = int(plan.param(stage, "topk", 16))
        up = ctx[stage.deps[0]]
        if up.handoff is None:  # staged (or restored) indexer result
            _, top = up.result
            return StageOut(result=tuple(top[:k]))
        return StageOut(result=_df_topk_from_handoff(
            up.handoff, k, sc["stage_stats"].get(stage.deps[0])))

    if stage.kind == "postings_join":
        fault_point(f"plan-stage{i}-advance")
        up_idx = ctx[stage.deps[0]]
        top = ctx[stage.deps[1]].result
        packed = _postings_index(
            up_idx, sc["stage_stats"].get(stage.deps[0]))
        found = packed.lookup_many([w for _, w in top])
        join = {w: (df, found[w][0], tuple(d for d, _ in found[w][1]))
                for df, w in top if w in found}
        return StageOut(result=join, index=packed)

    if stage.kind == "sample":
        from dsi_tpu.parallel.sortstream import sample_splits

        fault_point(f"plan-stage{i}-advance")
        books = _Books()
        points = sample_splits(
            list(plan.param(stage, "paths")),
            int(plan.param(stage, "n_reduce", 10)),
            int(plan.param(stage, "sample", 100_000)), stats=books.stats,
            n_dev=int(mesh.devices.size))
        _note_stage(sc, sp, stage, [books])
        return StageOut(result=points)

    if stage.kind == "range_sort":
        from dsi_tpu.parallel.sortstream import range_sort

        fault_point(f"plan-stage{i}-advance")
        if staged:
            # No host fallback commits a sort: the records' way through
            # the device, or across the mesh's, IS the chain.
            raise PlanHostPath(
                f"stage {stage.name!r}: the sort needs the host path (a "
                "staged run materializes on the host)")
        points = ctx[stage.deps[0]].result
        # What the stage ran with is part of what it is: the plan's
        # signature carries the partitions' split points as a CRC from
        # here on (the devices' are the layout's, not the answer's).
        stage.params["splits"] = points.partitions.tobytes()
        books = _Books()
        ordered = range_sort(list(plan.param(stage, "paths")), points,
                             mesh=mesh, chunk_bytes=kw["chunk_bytes"],
                             depth=kw["depth"], stats=books.stats)
        books.bytes_in = ordered.records * 100
        _note_stage(sc, sp, stage, [books])
        return StageOut(result=ordered)

    if stage.kind == "aggregate":
        from dsi_tpu.ops.fieldsum import BadRow, FieldSum
        from dsi_tpu.parallel.streaming import WordcountStep, stream_rows

        if staged:
            # One stage hands nothing over, so there is nothing to stage;
            # and no host fallback commits an aggregation.
            raise PlanHostPath(f"stage {stage.name!r}: the aggregation "
                               "needs the host path (a staged run "
                               "materializes on the host)")
        paths = list(plan.param(stage, "paths"))
        books = _Books(stream_rows(paths))
        try:
            step = WordcountStep(
                books, mesh=mesh, pipeline_stats=books.stats,
                n_reduce=int(plan.param(stage, "n_reduce", 10)),
                u_cap=int(plan.param(stage, "u_cap", 1 << 12)),
                map=FieldSum(prefix=int(plan.param(stage, "prefix", 0))),
                **kw)
            res = _drive(step, i)
        except BadRow as e:
            raise e.at(paths) from None
        _note_stage(sc, sp, stage, [books])
        if res is None:
            raise PlanHostPath(f"stage {stage.name!r}: the aggregation "
                               "needs the host path")
        return StageOut(result=res)

    if stage.kind == "join":
        from dsi_tpu.parallel.joinstream import table_join

        fault_point(f"plan-stage{i}-advance")
        if staged or int(mesh.devices.size) != 1:
            # No host fallback commits a join, and one device holds the
            # table: across a mesh the rows of both sides first have to
            # reach the device that owns their key.
            why = ("a staged run materializes on the host" if staged else
                   "its table is one device's: a mesh needs the exchange "
                   "by key")
            raise PlanHostPath(f"stage {stage.name!r}: the join needs the "
                               f"host path ({why})")
        books = _Books()
        try:
            res = table_join(
                list(plan.param(stage, "build_paths")),
                list(plan.param(stage, "paths")),
                tuple(d.encode("ascii") for d in plan.param(stage, "dates")),
                mesh=mesh, n_reduce=int(plan.param(stage, "n_reduce", 10)),
                chunk_bytes=kw["chunk_bytes"], depth=kw["depth"],
                stats=books.stats)
        finally:
            books.bytes_in = sum(
                os.path.getsize(path) for key in ("build_paths", "paths")
                for path in plan.param(stage, key))
            _note_stage(sc, sp, stage, [books])
        return StageOut(result=res)

    raise PlanError(f"unrunnable stage kind {stage.kind!r}")


def _postings_index(up: StageOut, stats: Optional[dict] = None):
    """The whole postings table behind an indexer stage's output, as
    ``merge.PackedPostings``, grouped once and kept: out of the handoff's
    host table (the device buffer's remainder flushed into it first),
    or out of the result a staged or restored indexer left as Python
    objects.  ``stats`` (the indexer's record under ``stage_stats``)
    takes the ``group`` span's seconds and the table's counts."""
    if up.handoff is None:
        from dsi_tpu.parallel.merge import PackedPostings

        postings, _ = up.result
        return PackedPostings.from_postings(postings)
    return _handoff_index(up.handoff, stats)


def _handoff_index(h: Dict, stats: Optional[dict] = None):
    """The handoff's host table grouped, once (kept under ``packed``)."""
    if h.get("packed") is None:
        if h.get("postings_svc") is not None:
            h["postings_svc"].close()  # flush the device buffer's
            h["postings_svc"] = None  # remainder into the table
        h["packed"] = h["table"].finalize_packed(stats=stats)
    return h["packed"]


def plan_index(plan: Plan, ctx: Dict, sc: dict):
    """The index a plan with a ``postings_join`` stage ends with: the
    table that stage grouped, or, where the stage was restored from its
    commit (a resume, a stage host's payload), the one its indexer
    stage's output gives.  None for a plan without such a stage."""
    for stage in plan.ordered():
        if stage.kind == "postings_join":
            out = ctx[stage.name]
            if out.index is None:
                out.index = _postings_index(
                    ctx[stage.deps[0]],
                    sc.get("stage_stats", {}).get(stage.deps[0]))
            return out.index
    return None


def _df_topk_from_handoff(h: Dict, k: int,
                          stats: Optional[dict] = None) -> Tuple:
    """The chained df-top-k: a k-row snapshot off the RESIDENT df table
    (no drain-to-host) when it holds the complete state; the exact
    drain fallback when a widen already spilled rows into the host
    accumulator (or there is no device table at all) — the fallback is
    counted pull volume, never a correctness trade."""
    from dsi_tpu.ops.wordcount import decode_packed

    tk = h.get("topk_svc")
    df_acc = h["df_acc"]
    residue = bool(df_acc.snapshot())
    if tk is not None and not residue:
        tk.sync()  # flushes the fold lag, pulls k rows per device
        out = []
        for c, keys, ln in tk.snapshot:
            w = decode_packed(np.array([keys], np.uint32),
                              np.array([int(ln)]), 1)[0]
            out.append((int(c), w))
        h["topk_svc"] = None  # the table is never drained: drop it
        return tuple(out[:k])
    if tk is not None:
        tk.close()  # exact drain into df_acc (the widen-residue path)
        h["topk_svc"] = None
    dfm = {w: c for w, (c, _p) in df_acc.finalize().items()}
    if not dfm:
        # Host-merge indexer (no dacc): document frequency is the
        # postings list length.  The table is in word order, so a
        # stable sort by df alone breaks ties by word, and only the k
        # leaders' spellings are decoded.
        packed = _handoff_index(h, stats)
        df = packed.ends - packed.starts
        lead = np.argsort(-df, kind="stable")[:k]
        words = decode_packed(packed.skeys[lead], packed.lens[lead],
                              len(lead))
        return tuple((int(df[j]), w) for j, w in zip(lead, words))
    return tuple(sorted(((c, w) for w, c in dfm.items()),
                        key=lambda r: (-r[0], r[1]))[:k])


# ── stage-commit payloads ─────────────────────────────────────────────


def _commit_payload(plan: Plan, stage: Stage, out: StageOut,
                    staged: bool) -> Tuple[Dict, Dict]:
    meta = {"stage": stage.name, "kind": stage.kind}
    if stage.kind == "grep":
        res = out.result
        if out.relay_spent:
            # The pipelined producer: its relay was consumed in-flight,
            # so the manifest carries the scalar result only.  The
            # paired resume-invalidation in run_plan drops this
            # manifest whenever its consumer's commit is missing.
            arrays = {}
            meta["relay_spent"] = True
        else:
            arrays = out.relay.capture()
            meta["relay_cap"] = int(plan.param(stage, "chunk_bytes",
                                               1 << 20))
        arrays["g_hist"] = np.array(res.hist, np.int64)
        arrays["g_tot"] = np.array(
            [res.lines, res.matched, res.occurrences], np.int64)
        arrays["g_topk"] = np.array(res.topk, np.int64).reshape(-1, 2)
        return arrays, meta
    if stage.kind == "wordcount":
        return _encode_counts(out.result), meta
    if stage.kind == "top_k":
        arrays = _encode_words([w for _, w in out.result], "t_")
        arrays["t_df"] = np.array([c for c, _ in out.result], np.int64)
        return arrays, meta
    if stage.kind == "indexer":
        if staged:
            postings, top = out.result
            join_like = {w: (len(ds), part, tuple(ds))
                         for w, (part, ds) in postings.items()}
            arrays = _encode_join(join_like)
            arrays.update(_encode_words([w for _, w in top], "t_"))
            arrays["t_df"] = np.array([c for c, _ in top], np.int64)
            return arrays, meta
        h = out.handoff
        arrays: Dict[str, np.ndarray] = {}
        tk = h.get("topk_svc")
        if tk is not None:
            for kk2, v in tk.checkpoint_state().items():
                arrays[f"tk_{kk2}"] = np.asarray(v)
            meta["table_kk"] = tk.kk
        pb = h.get("postings_svc")
        if pb is not None:
            img = pb.checkpoint_state()
            arrays["pb_buf"] = np.asarray(img["buf"])
            arrays["pb_nrows"] = np.asarray(img["nrows"])
        for kk2, v in h["df_acc"].snapshot().items():
            arrays[f"df_{kk2}"] = np.asarray(v)
        for kk2, v in h["table"].snapshot().items():
            arrays[f"pt_{kk2}"] = np.asarray(v)
        meta["kk"] = h["kk"]
        meta["n_real"] = h["n_real"]
        return arrays, meta
    if stage.kind == "df_topk":
        arrays = _encode_words([w for _, w in out.result], "t_")
        arrays["t_df"] = np.array([c for c, _ in out.result], np.int64)
        return arrays, meta
    if stage.kind == "postings_join":
        return _encode_join(out.result), meta
    raise PlanError(f"uncommittable stage kind {stage.kind!r}")


def _load_commit(plan: Plan, stage: Stage, meta: Dict, arrays: Dict,
                 mesh, staged: bool, sc: dict) -> StageOut:
    """Reconstruct a completed stage's outputs from its manifest —
    host-side (device state died with the crashed process; the drain
    path re-derives equivalent host state, the cross-degree-resume
    argument)."""
    if stage.kind == "grep":
        from dsi_tpu.device.relay import DeviceRelay, HostRelay
        from dsi_tpu.parallel.grepstream import GrepStreamResult

        tot = arrays["g_tot"]
        res = GrepStreamResult(
            int(tot[0]), int(tot[1]), int(tot[2]),
            tuple(int(x) for x in arrays["g_hist"]),
            tuple((int(a), int(b)) for a, b in arrays["g_topk"]))
        if meta.get("relay_spent"):
            return StageOut(result=res, relay=None, resumed=True,
                            relay_spent=True)
        if "hbytes" in arrays:
            relay = HostRelay.restore(arrays, stats=sc)
        else:
            relay = DeviceRelay.restore(
                mesh, arrays, cap=int(meta["relay_cap"]), stats=sc)
        return StageOut(result=res, relay=relay, resumed=True)
    if stage.kind == "wordcount":
        return StageOut(result=_decode_counts(arrays), resumed=True)
    if stage.kind == "top_k":
        top = tuple(zip((int(c) for c in arrays.get("t_df", ())),
                        _decode_words(arrays, "t_")))
        return StageOut(result=top, resumed=True)
    if stage.kind == "indexer":
        if staged:
            join_like = _decode_join(arrays)
            postings = {w: (part, list(ds))
                        for w, (_df, part, ds) in join_like.items()}
            top = tuple(zip(
                (int(c) for c in arrays.get("t_df", ())),
                _decode_words(arrays, "t_")))
            return StageOut(result=(postings, top), resumed=True)
        from dsi_tpu.device.postings import DevicePostings
        from dsi_tpu.device.table import DeviceTable
        from dsi_tpu.parallel.merge import PackedCounts, PostingsTable

        kk = int(meta["kk"])
        n_real = int(meta["n_real"])
        df_acc = PackedCounts()
        df_acc.restore({k[3:]: v for k, v in arrays.items()
                        if k.startswith("df_")})
        table = PostingsTable()
        table.restore({k[3:]: v for k, v in arrays.items()
                       if k.startswith("pt_")})
        tk_img = {k[3:]: v for k, v in arrays.items()
                  if k.startswith("tk_")}
        if tk_img:
            DeviceTable.drain_image(df_acc, tk_img)
        if "pb_buf" in arrays:
            def sink(r):
                r = r[r[:, kk + 2] < n_real]
                if len(r):
                    table.add(r, kk)

            DevicePostings.drain_image(
                sink, {"buf": arrays["pb_buf"],
                       "nrows": arrays["pb_nrows"]})
        handoff = {"kk": kk, "n_real": n_real, "topk_svc": None,
                   "postings_svc": None, "df_acc": df_acc,
                   "table": table, "device_accumulate": True}
        return StageOut(result=None, handoff=handoff, resumed=True)
    if stage.kind == "df_topk":
        top = tuple(zip((int(c) for c in arrays.get("t_df", ())),
                        _decode_words(arrays, "t_")))
        return StageOut(result=top, resumed=True)
    if stage.kind == "postings_join":
        return StageOut(result=_decode_join(arrays), resumed=True)
    raise PlanError(f"unloadable stage kind {stage.kind!r}")
