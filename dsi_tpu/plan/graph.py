"""Plan/Stage graph model: multi-stage dataflow without the host trip.

Dean & Ghemawat's flagship production use was a *sequence* of five to
ten MapReduces (the indexing pipeline, OSDI'04 §6.4); the 6.5840
contract this repo reproduces materializes every job's full output
before the next can start.  A :class:`Plan` is the declarative side of
the fix: a small DAG of :class:`Stage` nodes whose edges are
device-resident handoffs (``dsi_tpu/device/relay.py``,
``parallel/stepobj.py`` exports) instead of host materializations.  The
driver (``plan/driver.py``) runs it.

The ten stage kinds (what the driver knows how to run; ``sample`` and
``range_sort``, the sort chain's, are the two PR 45 added, ``aggregate``
the one PR 49 did, ``join`` the one PR 55 did):

* ``grep``          — streaming literal grep over a byte source,
  emitting the matching lines into the outgoing relay (the
  ``GrepStep(line_sink=...)`` emit path).
* ``wordcount``     — streaming word count consuming an upstream relay
  (``WordcountStep(device_batches=...)``) or a host block stream (the
  staged baseline / a source stage).
* ``indexer``       — wave-walk inverted index over a document list,
  completing with live device services exported
  (``IndexerStep(keep_services=True)``).
* ``df_topk``       — k-row document-frequency snapshot off an upstream
  indexer's resident :class:`DeviceTopK` (no drain-to-host).
* ``postings_join`` — per-term postings lookup for an upstream df_topk's
  terms (selective decode, not the full materialization).  To look them
  up it groups the indexer's whole postings table once
  (``merge.PackedPostings``); a plan that has such a stage ends with that
  table as ``PlanResult.index``, which ``planrun --chain indexer``
  commits as the inverted index (``mr-out-<r>``, one line a term,
  ``<word> <n> <doc>,<doc>,...``) beside ``plan-join.json``.
* ``top_k``         — k highest-count words of an upstream wordcount's
  result (count desc, word asc) — a host reduction over an
  already-host value, no engine.
* ``sample``        — TeraSort's sampling pre-pass over files of
  100-byte records (``parallel/sortstream.sample_splits``, host side):
  ``sample`` keys at evenly spaced record offsets, sorted, ``n_reduce`` - 1
  split points at the equal-count positions and, for a mesh, one fewer
  device split points than it has devices.  The split points are the
  stage's result.
* ``range_sort``    — the sort that consumes an upstream ``sample``'s
  split points (``parallel/sortstream.range_sort``): every record through
  a device step into a store that stays on the device (on a mesh: through
  the ``all_to_all`` into the store of the device that owns its key
  range), ordered there by key; the stage's result is the ordered
  store, which ``planrun --chain sort`` pulls and commits as
  ``mr-out-<r>``, totally ordered.  The split
  points it ran with enter the stage's identity, and so the plan's
  signature, as a CRC (``splits``).  OSDI'04 section 5.3's own shape.
* ``aggregate``     — ``SELECT key, SUM(value) ... GROUP BY key`` over
  files of newline-terminated, ``|``-delimited rows: the ``wordcount``
  stage's engine (``WordcountStep``) with ``ops/fieldsum.FieldSum`` as
  its map, so a row's key field is the word and its decimal value field
  what is summed, 64 bits wide.  A source stage; its result is the merged
  table, which ``planrun --chain agg`` commits as ``mr-out-<r>``
  (``<key> <sum with six decimals>``).  ``prefix`` groups by the key's
  first bytes.
* ``join``          — an inner equi-join of two tables of
  newline-terminated, ``|``-delimited rows, filtered by a date window and
  grouped (``parallel/joinstream.table_join``; Pavlo et al., SIGMOD'09,
  the Join Task): a source stage with TWO path inputs, ``build_paths``
  (rows ``key|rank|...``, the key 1-100 bytes and a primary key: the
  table that is built and stays on the device) and ``paths`` (rows
  ``group|key|date|value|...``: streamed past it), both in the stage's
  identity and so in the plan's signature, and ``dates``, the window's
  two ends.  Its result is the merged table of groups with three sums a
  key, which ``planrun --chain join`` commits as ``mr-out-<r>``
  (``<group> <sum of values> <mean of ranks>``) beside ``plan-top.json``.
* A ``grep`` stage MAY itself have a grep dep (the grep→grep cascade):
  it consumes the upstream relay's line stream instead of a byte
  source and re-greps it with its own pattern.

A plan is VALIDATED at build time (unique names, known deps, acyclic)
and serializes to a :meth:`Plan.signature` — the job identity its stage
manifests carry, so a resume against a different plan refuses instead of
misreading stage payloads.  Bulk inputs (corpus bytes, document lists)
enter the signature as CRCs, not content; the CRC reads every document,
so the driver asks for a signature only where a stage store reads it.
"""

from __future__ import annotations

import json
import re
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

#: The stage kinds plan/driver.py can run.
STAGE_KINDS = ("grep", "wordcount", "indexer", "df_topk", "postings_join",
               "top_k", "sample", "range_sort", "aggregate", "join")

#: Stage params carrying bulk payloads: identity-hashed, never inlined
#: into the signature.
_BULK_PARAMS = ("data", "docs", "paths", "build_paths", "splits")


class PlanError(ValueError):
    """A malformed plan: unknown kind, missing dep, duplicate name,
    cycle — raised at build/validate time, never mid-run."""


class Stage:
    """One node: ``name`` (unique), ``kind`` (STAGE_KINDS), ``deps``
    (upstream stage names this one consumes), ``params`` (kind-specific
    knobs; bulk inputs under ``data``/``docs``/``paths``)."""

    def __init__(self, name: str, kind: str,
                 deps: Sequence[str] = (), **params):
        if kind not in STAGE_KINDS:
            raise PlanError(f"unknown stage kind {kind!r} "
                            f"(have: {', '.join(STAGE_KINDS)})")
        self.name = str(name)
        self.kind = kind
        self.deps: Tuple[str, ...] = tuple(deps)
        self.params: Dict = dict(params)

    def identity(self) -> Dict:
        """JSON-ready identity: params with bulk payloads replaced by
        (length, crc32) pairs so the signature stays small and stable."""
        out = {"name": self.name, "kind": self.kind,
               "deps": list(self.deps)}
        for k in sorted(self.params):
            v = self.params[k]
            if k in _BULK_PARAMS and v is not None:
                if k == "docs":
                    crc = 0
                    total = 0
                    for d in v:
                        crc = zlib.crc32(bytes(d), crc)
                        total += len(d)
                    out[k] = {"n": len(v), "bytes": total, "crc32": crc}
                elif k in ("data", "splits"):
                    out[k] = {"bytes": len(v),
                              "crc32": zlib.crc32(bytes(v))}
                else:  # paths of either input: names are identity enough
                    out[k] = list(v)  # (files change under any cursor)
            else:
                out[k] = v
        return out


class Plan:
    """An ordered, validated stage DAG.  ``add`` returns the stage so
    chains read naturally::

        p = Plan("grep-wc", chunk_bytes=1 << 20)
        g = p.add(Stage("grep", "grep", pattern="the", paths=files))
        p.add(Stage("wc", "wordcount", deps=[g.name]))
    """

    def __init__(self, name: str, **defaults):
        self.name = str(name)
        #: Plan-wide engine knobs every stage inherits (chunk_bytes,
        #: depth, device_accumulate, sync_every, mesh_shards, aot, ...);
        #: a stage's own params override.
        self.defaults: Dict = dict(defaults)
        self._stages: List[Stage] = []
        self._by_name: Dict[str, Stage] = {}

    def add(self, stage: Stage) -> Stage:
        if stage.name in self._by_name:
            raise PlanError(f"duplicate stage name {stage.name!r}")
        for d in stage.deps:
            if d not in self._by_name:
                raise PlanError(f"stage {stage.name!r} depends on "
                                f"unknown stage {d!r} (deps must be "
                                f"added first — the DAG is built in "
                                f"topological order)")
        self._stages.append(stage)
        self._by_name[stage.name] = stage
        return stage

    def __len__(self) -> int:
        return len(self._stages)

    def __getitem__(self, name: str) -> Stage:
        return self._by_name[name]

    def ordered(self) -> Tuple[Stage, ...]:
        """The stages in execution order.  Insertion order IS a
        topological order (``add`` refuses forward deps), so this is
        deterministic and needs no tie-breaking."""
        return tuple(self._stages)

    def param(self, stage: Stage, key: str, default=None):
        """Stage-over-plan parameter resolution."""
        if key in stage.params:
            return stage.params[key]
        return self.defaults.get(key, default)

    def signature(self) -> Dict:
        """The plan's job identity (stage-manifest ``job`` field):
        JSON-normalised, bulk inputs as CRCs."""
        return json.loads(json.dumps({
            "plan": self.name,
            "defaults": {k: v for k, v in sorted(self.defaults.items())
                         if not callable(v)},
            "stages": [s.identity() for s in self._stages],
        }))


# ── the canonical chains ──────────────────────────────────────────


def grep_wordcount_plan(pattern: str, *, paths: Optional[Sequence[str]]
                        = None, data: Optional[bytes] = None,
                        **defaults) -> Plan:
    """grep → wordcount-over-matching-lines: stage 2 counts words over
    exactly the lines stage 1 matched, with the matching-line bytes
    staying device-resident between the stages."""
    p = Plan("grep-wc", **defaults)
    g = p.add(Stage("grep", "grep", pattern=pattern, paths=paths,
                    data=data))
    p.add(Stage("wc", "wordcount", deps=[g.name]))
    return p


def grep_cascade_plan(pattern1: str, pattern2: str, *,
                      paths: Optional[Sequence[str]] = None,
                      data: Optional[bytes] = None, **defaults) -> Plan:
    """grep → grep: stage 2 re-greps exactly the lines stage 1 matched
    (a narrowing filter chain — "lines with A, of those, lines with
    B"), the relay's line stream standing in for the byte source."""
    p = Plan("grep-grep", **defaults)
    g1 = p.add(Stage("grep1", "grep", pattern=pattern1, paths=paths,
                     data=data))
    p.add(Stage("grep2", "grep", deps=[g1.name], pattern=pattern2))
    return p


def wordcount_topk_plan(k: int = 16, *,
                        paths: Optional[Sequence[str]] = None,
                        data: Optional[bytes] = None, **defaults) -> Plan:
    """wordcount → top-k: stage 2 is a host reduction picking the k
    highest-count words of the full count table."""
    p = Plan("wc-topk", **defaults)
    w = p.add(Stage("wc", "wordcount", paths=paths, data=data))
    p.add(Stage("topk", "top_k", deps=[w.name], topk=k))
    return p


def indexer_join_plan(docs: Sequence[bytes], *, topk: int = 16,
                      pack_docs: bool = False, **defaults) -> Plan:
    """indexer → df-top-k → per-term postings join: stage 2 takes a
    k-row snapshot of the resident df table (no drain), stage 3 decodes
    postings for just those k terms, out of the whole table it groups
    once: the run's ``PlanResult.index``, the inverted index.
    ``pack_docs`` fills the indexer's waves with whole documents, a
    chunk of the plan's ``chunk_bytes`` a device
    (``IndexerStep(pack_docs=True)``); off, the stage and the plan's
    signature are what they were.  ``docs`` may be a lazy sequence with
    ``lengths`` (``ioread.ReadAheadDocs``): the stage keeps it as it is,
    and no document is asked for until the walk, or a signature, asks."""
    p = Plan("indexer-join", **defaults)
    packed = {"pack_docs": True} if pack_docs else {}
    if not hasattr(docs, "lengths"):
        docs = list(docs)
    i = p.add(Stage("indexer", "indexer", docs=docs, topk=topk,
                    **packed))
    t = p.add(Stage("dftopk", "df_topk", deps=[i.name], topk=topk))
    p.add(Stage("join", "postings_join", deps=[i.name, t.name]))
    return p


def sort_plan(paths: Sequence[str], *, sample: int = 100_000,
              **defaults) -> Plan:
    """sample → range_sort over files of 100-byte records (OSDI'04
    section 5.3, TeraSort's partitioner): stage 1 reads ``sample`` keys
    and gives the plan's ``n_reduce`` - 1 split points, stage 2 orders
    every record on the device and counts the records a partition
    against them."""
    p = Plan("sort", **defaults)
    s = p.add(Stage("sample", "sample", paths=list(paths), sample=sample))
    p.add(Stage("sort", "range_sort", deps=[s.name], paths=list(paths)))
    return p


def agg_plan(paths: Sequence[str], *, prefix: int = 0, **defaults) -> Plan:
    """One ``aggregate`` stage over files of ``|``-delimited rows: group
    by field 0 (its first ``prefix`` bytes where that is not 0), sum
    field 3, a decimal (Pavlo et al., SIGMOD'09, the Aggregation Task's
    two queries over ``UserVisits``)."""
    p = Plan("agg", **defaults)
    p.add(Stage("agg", "aggregate", paths=list(paths), prefix=int(prefix)))
    return p


def parse_dates(text: str) -> Tuple[str, str]:
    """``FROM:TO`` as the window's two dates (``YYYY-MM-DD`` each, both
    inclusive: a date is compared as its ten bytes); ``ValueError`` for
    anything else."""
    first, _, last = text.partition(":")
    if not all(re.fullmatch("[0-9]{4}-[0-9]{2}-[0-9]{2}", d, re.ASCII)
               for d in (first, last)):
        raise ValueError(f"{text!r} is not FROM:TO with both dates "
                         "YYYY-MM-DD")
    return first, last


def join_plan(build_paths: Sequence[str], paths: Sequence[str], *,
              dates: Tuple[str, str], **defaults) -> Plan:
    """One ``join`` stage: ``build_paths``' rows ``key|rank|...`` joined
    with ``paths``' rows ``group|key|date|value|...`` on the key, inside
    the window ``dates`` (both ends inclusive), the values' sum and the
    ranks' mean by group (Pavlo et al., SIGMOD'09, the Join Task:
    ``Rankings`` and ``UserVisits``)."""
    p = Plan("join", **defaults)
    p.add(Stage("join", "join", build_paths=list(build_paths),
                paths=list(paths), dates=[str(d) for d in dates]))
    return p
