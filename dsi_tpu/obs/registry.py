"""The one metrics registry behind every engine's phase dict.

Before this module each engine grew its own stats dict with its own
spellings — ``pipeline_stats``/``stream_phases`` (word count),
``wave_phases`` (TF-IDF), the grep variants — and bench.py and the CLIs
each re-learned every shape.  Now an
engine's stats dict IS a :class:`MetricsScope` registered here under the
engine's name, and every consumer reads one documented schema.

## The unified key schema

Phase wall-seconds (suffix ``_s``; a key is present when the engine has
that phase):

* ``materialize_s``      — building host-side step items (batch slicing,
  wave chunk assembly); in the producer thread at depth > 1
* ``materialize_wait_s`` — consumer starvation on the producer queue
* ``upload_s``           — H2D puts of step inputs
* ``kernel_s``           — seconds blocked on a step's deferred
  scalar/flag check.  NOT device time: it is what the window did not
  hide of the step program, as ``device_wait_s`` is of whatever the
  pulled tensor still waits for (``device_wait_share`` adds the two)
* ``pull_s``             — result pulls, host-blocked; the sum of
  ``device_wait_s`` (blocked in ``jax.block_until_ready`` until the
  device has produced what is pulled: device time, not transfer; a
  word-count step's table is packed when the step is dispatched, so
  this is what was left of the step's own device time, not a place in
  the queue behind the next step's kernel) and ``d2h_s`` (the
  device-to-host copy itself)
* ``merge_s``            — host-side accumulation of pulled results
* ``finalize_s``         — the final merge of the accumulator into the
  result: the last compaction; the merged table is the result
  (``merge.PackedWordCounts``)
* ``finalize_decode_s``  — the ``decode`` span: the spellings of the
  merged table decoded and the result dict built, on the first keyed
  access, iteration or comparison of the result (0.0 in a ``wcstream``
  job, whose writer reads the arrays); ``finalize_decoded_keys`` counts
  the spellings
* ``compact_s``          — the ``compact`` spans of the host accumulator
  (``parallel/merge.py``): the window's runs merged into one and that
  one into the merged table, which is never sorted again.  A window
  that filled is compacted on a merger thread, beside the steps that
  follow, and its span is in no phase of the main thread; the last,
  partial window on the caller's, inside ``finalize_s``, ``sync_s`` or
  ``ckpt_s``, whichever asked for the table
* ``compact_caller_s``   — what of ``compact_s`` the accumulator's
  caller was held for: the ``compact`` spans on its own thread and its
  ``merge_wait`` spans (a compaction still in flight when the next
  window was full, or when the table was asked for); 1 less its share
  of ``compact_s`` is the share of the compactions the steps hid
* ``group_s``            — the ``group`` span: the indexer's postings
  table grouped into the index (``merge.PostingsTable.finalize_packed``:
  the runs the waves' rows arrive in found and merged, no row through a
  sort), once a job, when the first stage that needs the whole table
  asks for it
* ``pack_s``             — the ``pack`` spans of a packed index walk
  (``planrun --chain indexer --pack-docs``): one wave's chunks joined
  from whole documents and its vector of ordinals built
  (``grepstream.pack_chunk``), on the producer thread, inside
  ``materialize_s``
* ``read_s``             — what a ``planrun --chain indexer`` job
  pays for its documents before its first stage (the ``read`` span
  around the plan's construction; at the top of its ``pipeline_stats``,
  beside ``write_s``): their lengths.  Their bytes are read AHEAD of the
  walk, by the reader threads of ``utils/ioread.ReadAheadDocs``, in the
  order the waves will ask
* ``read_wait_s``        — the ``read_wait`` spans beside it: seconds a
  caller of ``docs[i]`` was held by a document not yet read (the
  walk's producer thread, before a wave's ``pack`` span and outside
  it; a signature's CRC on the main thread under
  ``--checkpoint-dir``).  ``read_docs`` counts the documents asked
  for, each once, ``read_ahead_hits`` those that were there when first
  asked for (their ratio is the hit share), ``read_threads`` the pool
* ``write_s``            — writing the partitioned ``mr-out-*`` (the
  CLI's phase, not the engine's)
* ``write_format_s`` / ``write_commit_s`` — inside it
  (``shuffle.write_partitioned_output``): the ``format`` spans (a
  partition's bytes rendered from the merged table's arrays; for a dict,
  the bucketing, a partition's sort and line formatting) and the
  ``commit`` spans (its write, flush, fsync and rename);
  ``write_rows_packed`` / ``write_rows_dict`` count the rows written
  either way
* ``job_s``              — a stream command's root ``job`` span, from
  its parsed arguments to the ``--stats`` line; its direct children on
  the main thread are :data:`JOB_CHILDREN`, and ``job_children_s`` is
  the sum of their keys (``job_s`` less it is the root's self time)
* ``start_s``            — the ``start`` span: the device gate, the
  mesh, the engine's construction up to the pipeline armed (in
  ``planrun``: the plan's construction too, where no ``read`` span
  holds it)
* ``report_s``           — ``planrun``'s ``report`` spans: the stage and
  handoff lines, ``named()``, ``plan-join.json`` / ``plan-grep.json`` /
  ``plan-topk.json``.  ``planrun`` prints ``job_s``, ``start_s``,
  ``report_s`` and ``job_children_s`` at the top of its
  ``pipeline_stats``; its root's children are
  :data:`PLAN_JOB_CHILDREN`
* ``starved_s``          — the starvation account of a job's main thread
  (``obs/trace.py``, "The starvation account"): seconds of ``job_s``
  in pieces through which the chip had nothing queued, or ran out, or
  started with nothing (an upper bound of the chip's idle time as the
  host sees it); ``starved_dry_s`` is the part with nothing queued at
  both ends and nothing enqueued between (the lower bound), and
  ``starved_unseen_s`` the seconds in pieces at an end of which
  something was in flight and the account's budget allowed no look
  (neither fed nor starved: where it is a large part of ``job_s`` the
  account did not run, and ``results_ready`` over ``steps`` is the
  reading).
  ``starved_by`` maps a span name to the starved seconds charged to it
  as the innermost open span (``job`` for the root's own time), and
  ``starved_groups`` sums them over :data:`STARVED_GROUPS` (``input``,
  ``dispatch``, ``merge``, ``tail``: the four sum to ``starved_s``).  A
  span of :data:`DEVICE_BLOCKED` is never starved.  At the top of the
  ``pipeline_stats`` of ``wcstream``, ``grepstream`` and ``planrun``
* ``sync_wait_s``        — inside ``fold_s``, ``sync_s`` and ``widen_s``
  of a device table (``device/table.py``): the ``wait`` spans in which
  the host is blocked on a fold's flags or on the packed table's copy
* ``lowered_s`` / ``compiled_s`` — ``backends/aotcache.cached_compile``'s
  two halves: the ``lower`` span (tracing and lowering to StableHLO) and
  the ``compile`` span (the backend compile, or the persistent cache's
  load), summed over the programs this process compiled explicitly
  (``aotcache.stats``, which is the process's and no engine's scope)
* ``dispatch_s`` / ``retire_s`` — the pipeline core's ``dispatch`` and
  ``finish`` spans (``finish_s`` is the daemon's job-finish key), one
  each a step; ``upload_s`` and ``enqueue_s`` are inside the first,
  ``kernel_s``, ``pull_s``, ``merge_s``, ``replay_s``, ``fold_s`` and a
  step's ``sync_s`` and ``ckpt_s`` inside the second
* ``enqueue_s``          — the ``enqueue`` span: the call of the
  compiled step program and the starts of its async copies to the host
* ``replay_s``           — exactness-ladder replays of overflowed steps
* ``fold_s`` / ``append_s`` / ``hist_s`` — device-service folds
* ``sync_s`` / ``drain_s``               — device-service pulls/drains;
  in the stream engines ``drain_s`` is the end-of-stream drain (the
  table's close, the checkpoint writer's last commits)
* ``widen_s``            — drain→realloc→re-fold recoveries
* ``ckpt_s``             — checkpoint snapshot + durable write (with
  async commits, only the boundary-side work: capture + any barrier)
* ``ckpt_capture_s``     — the capture half of a save: flag flushes,
  snapshot-pull dispatches, host snapshot-by-reference (engine thread)
* ``ckpt_commit_s``      — the commit half: materialize the in-flight
  pulls, serialize, durable write (the background writer thread under
  ``--ckpt-async``, inline otherwise)
* ``ckpt_barrier_s``     — engine-thread stalls on the commit writer
  (the NEXT save or the end-of-stream drain found a commit in flight)

Counters / gauges: ``steps`` (or ``waves``), ``depth``, ``replays``,
``results_ready`` (steps whose programs the device had already run
when ``finish`` came to retire them: the pipeline core asks the array
the step's dispatch told the tracer, ``obs.enqueued``, without
blocking, for every engine on ``StepPipeline`` but the sort's ingest
loop, whose step is shorter than a hundred looks:
``count_ready=False``), ``step_pulls`` and its two kinds in the word-count stream
engine, ``pulls_early`` (served by the tensor packed when the step was
dispatched) and ``pulls_late`` (served by a pack enqueued at retirement:
a step whose table outgrew the predicted prefix, or a replay's payload;
``pulls_early + pulls_late == step_pulls``), ``sync_pulls``, ``widens``, ``folds``,
``fold_overflows``, ``appends``, ``append_overflows``,
``postings_widens``, ``topk_snapshots``, ``hist_folds``, ``hist_pulls``,
``table_cap``, ``sync_every``, ``max_inflight``,
``merge_rows_in`` (rows handed to the host accumulator's ``add``),
``merge_runs_in`` (batches handed to it), ``merge_runs_unsorted`` (those
whose rows did not strictly increase and were sorted on entry: 0 where
every batch is a device's step table), ``merge_rows_sorted`` (rows
handed to an ordering routine: an unsorted batch's on entry, a
window's at its compaction, the merged table's never) and
``merge_compacts`` (all five repeat exactly for one input, with the
native library or without; ``merge_compacts_async`` of them were handed
to a merger thread: the windows that filled), ``buffer_allocs``, ``ckpt_saves``, ``ckpt_every``, ``resume_gap_s``,
``resume_cursor``/``resume_wave``, ``device_accumulate``.  The grep
engine's row cut (``grepstream.batch_lines``) adds ``recopied_bytes``:
the bytes that waited behind a block's last cut as the head of the next
row and so were copied twice (under a row's worth a block; near the
input's bytes would say the rows are not cut in place).  The indexer's
wave walk adds ``docs`` (documents handed over), ``waves_by_size``
(padded chunk bytes → waves dispatched), ``wave_doc_bytes`` and
``wave_chunk_bytes`` (the documents' bytes and the padded bytes they
were uploaded as: their ratio is how full the waves were),
``postings_rows`` and ``index_terms`` (the table the index is grouped
from and the terms it holds), ``group_runs`` and ``group_rows_sorted``
(the runs the group merged, and the rows of the buffers that did not
arrive in runs and were sorted first: 0 where every wave's rows come as
the device leaves them), ``pack_docs`` (whether the walk packs
whole documents into its waves), ``wave_docs`` (documents dispatched in
waves: ``docs`` unless a rung restarted the walk) and
``docs_per_wave_max`` (the most one wave held: the devices' count
unless the walk packs).

The sort chain (``parallel/sortstream.py``, engine ``sort``; its two
stages' scopes under ``stage_stats``): ``sample_s`` and
``sort_sample_keys`` (the sampling pre-pass and the keys it read),
``sort_records`` (records of the job), ``sort_resident_bytes`` (the store
that stays on the device from the first step to the ordering: the
records and their key lanes), ``sort_partition_rows`` (records a
partition, a list of ``n_reduce``: the device's count against the
sampled split points), ``order_s`` (the ``order`` span: the host
blocked on the device's ordering) and ``sort_order_passes`` (its
single-key sort passes).  The commit's ``pull_s`` / ``d2h_s`` /
``pull_bytes`` / ``write_commit_s`` stand at the top of ``planrun``'s
``pipeline_stats`` beside ``write_s``.  On a mesh (``--devices`` 2 or
more) the scope also holds ``sort_devices``, ``sort_device_capacity``
(rows of a device's store: its share by the sample, a sixteenth more,
one step's landing block), ``sort_exchange_rows`` and
``sort_exchange_bytes`` (the records, and their 100-byte bytes, whose
owner is not the device that read them: counted on the device, a step
at a time, beside the partitions' counts); ``device_rows`` is then the
records a device holds when the ordering starts and
``sort_resident_bytes`` the stores' and lanes' bytes summed over the
devices.

The aggregation chain (``planrun --chain agg``: the stream engine with
``ops/fieldsum.FieldSum`` as its map; the scope under ``stage_stats`` is
the word count's own, ``steps``, ``pull_s``, ``merge_s``, ... and all):
``agg_rows`` (rows the confirmed steps read: the job's newlines),
``agg_groups`` (keys of the merged table: the committed lines) and
``agg_value_lanes`` (``uint32`` lanes a step's sum rides through the
shuffle, the pull and the merge: 2).

The join chain (``planrun --chain join``: ``parallel/joinstream.py``,
engine ``join``; the scope under ``stage_stats`` holds the stream
engines' ``steps`` (the probe's), ``upload_s``, ``kernel_s``, ``pull_s``,
``merge_s``, ``step_pulls``, ``pulls_early`` / ``pulls_late`` and the
accumulator's counters as a word count's): ``join_build_s`` and
``join_probe_s`` (the ``join_build`` span: the build side's rows counted,
every chunk through ``join_build_step``, the table ordered and its
neighbours checked; the ``join_probe`` span: every probe chunk through
``join_probe_step`` to the last table merged), ``read_s`` (the count of
the build side's rows, which sizes the table), ``order_s`` (the host
blocked on ``join_build_order``), ``join_build_rows`` and
``join_build_bytes`` (rows and bytes of the build side the retired steps
read: the files' newlines and bytes), ``join_build_steps``,
``join_table_bytes`` (what stays on the device for the probe: the
ordered rows and their hashes), ``join_probe_rows`` (every probe row
read), ``join_window_rows`` (those inside the date window),
``join_matched_rows`` (those whose key is the table's), ``join_groups``
(keys of the merged table: the committed lines) and ``join_value_lanes``
(``uint32`` lanes of a step's three sums: 6).  A phase that starts again
at its next rung counts from zero.

Async/incremental checkpoint keys (``dsi_tpu/ckpt`` writer/delta —
present when checkpointing is on): ``ckpt_async``/``ckpt_delta`` (the
mode flags), ``ckpt_deltas`` (incremental saves among ``ckpt_saves``),
``ckpt_full_bytes``/``ckpt_delta_bytes`` (serialized payload totals by
kind — the bench's delta-vs-full evidence).

Compressed wire + ingest keys (ISSUE 13): ``ingest_readers``/
``ingest_blocks``/``readahead_hit_pct`` and the ``ingest_wait_s``
phase come from the parallel reader pool (``utils/ioread.py``, folded
into the engine scope at release by ``parallel/pipeline.py
fold_source_stats``); ``wire_upload`` (the chunk-codec mode flag),
``wire_steps``/``wire_raw_steps`` (packed vs raw-fallback uploads),
``wire_packed_bytes``, ``wire_ratio`` (raw/packed upload bytes) and
the ``decode_s`` phase (host encode + decode-prologue dispatch) come
from the chunk-upload codec (``ops/wirecodec.py``);
``ckpt_compress``/``ckpt_delta_raw_bytes`` and the
``ckpt_compress_s`` phase are the compressed-checkpoint attribution
(``ckpt/store.py`` via the writer).

Plan-layer keys (``dsi_tpu/plan`` — the "plan" scope of a multi-stage
chain run): ``plan_stages`` (stage count), ``plan_handoff``
(``device``/``host`` — which relay flavor carried the intermediates),
``plan_intermediate_bytes`` (bytes that crossed the host on the
inter-stage handoff path: 0 on an unspilled device-relay chain, the
full materialization on the staged baseline), ``plan_handoff_bytes``
(total intermediate content the relays carried — the saved-bytes
denominator), ``plan_relay_buffers`` /
``plan_spilled_bytes`` / ``plan_restored_bytes`` (relay residency
accounting), ``plan_commit_bytes`` (durable stage-manifest payloads —
durability cost, deliberately NOT handoff bytes),
``plan_resumed_stages`` (stages skipped by a resume from stage
manifests), ``plan_stage_walls`` (per-stage wall seconds, keyed by
stage name), plus the ``plan_s`` / ``stage_commit_s`` phases.
``stage_stats`` maps each stage's name to the scope of the engine that
ran it (its ``pipeline_stats`` as ``wcstream`` / ``grepstream`` print
them, plus ``bytes_in``, the bytes the stage read; a list of them, one
per shard, where ``stage_shards`` split the stage).  The relay's own
cost is ``relay_appends`` (calls of ``append``), ``relay_seals``
(buffers sealed by this run; ``plan_relay_buffers`` also counts
restored ones) and the ``relay_append_s`` / ``relay_spill_s`` phases
(the ``relay_append`` and ``relay_spill`` spans).

Serving keys (``dsi_tpu/serve``): the packers' scopes ``serve`` (word
count) and ``serve_grep`` carry ``packed_steps`` / ``packed_rows`` /
``max_tenants_per_step`` / ``host_fallbacks`` and the phases of one
packed step, ``take_s`` (the ``take_row`` spans: a lane's next row cut
from its input on the scheduler thread), ``upload_s``, ``kernel_s``,
``pull_s``, ``merge_s``.  The grep packer keeps one step in flight
(``serve/pack.py``): ``upload_s`` is one ``device_put`` of a step's
operands, ``kernel_s`` the program's call and the starts of its
results' copies to the host (it returns before the device has run
anything), and ``pull_s`` the three reads, taken one call of ``step``
later, so it holds what of the device's time and of the copies the
host's work in between did not cover.  Its ``packed_steps`` and
``packed_rows`` count confirmed steps; ``results_ready`` counts those
whose program the device had finished when the host came to read them
(asked as the pipeline core asks it, ``Tracer.landed``), and
``settles`` those confirmed ahead of their turn, because a lane with a
row in them was snapshotted for an eviction or finalized.  The word-
count packer reads each step where it dispatched it.  The daemon's own
scope ``serve_daemon``
carries ``submits`` and ``submit_s`` (the ``Submit`` handler:
validation, the durable journal write, the reply), ``admit_s`` (runner
construction and chain load), ``evict_s`` and ``evictions`` (a park: the
forced snapshot, the journal write), ``resumes``, ``finish_s`` and
``jobs_done`` (finalize and the durable output), and what the lanes'
checkpoint writers add: ``ckpt_s`` (a lane's ``save_ckpt``, periodic or
forced by a park) with the ``ckpt_*`` counters above.  A job record's
``stats`` holds ``queue_wait_s`` (submission to the first row a packer
took from the job) and ``service_s`` (from there to its committed
output).  ``Status`` without a job id returns the three scopes as
``stats`` (``serve``, ``serve_grep``, ``daemon``: the last with the
admission counters ``shed``, ``rate_limited``, ``evict_p99``,
``evict_quota``), counted from the daemon's start.

``device_rows`` (the "stream" scope) is a length-``n_dev`` list: the
reduce-output rows each device of the mesh produced, summed over
confirmed steps — every entry is non-zero when every device held a shard
of the all-to-all shuffle.

Mesh-sharded service keys (``mesh_shards`` > 0, the shuffle-fold path
— ``device/table.py``): ``mesh_shards`` (the sharding degree),
``pull_bytes`` (total D2H drain payload, counted in BOTH modes — the
bench A/B's evidence), ``shard_widens`` (per-shard widen counts, a
length-``n_dev`` list whose sum tracks the per-shard drain→realloc→
re-fold recoveries), ``shard_imbalance`` (max/mean shard occupancy
after the last confirmed fold; ~1.0 under FNV routing), and
``resharded_resume`` (set when a resume crossed sharding degrees via
the drain path; its value is the checkpoint's OLD degree, which is
legitimately 0 resuming a host-merge image into a mesh run — key
presence, not truthiness, is the signal).  Fold spans land in the tracer's ``shuffle`` lane in
mesh mode; span totals still reconcile with ``fold_s`` — the span IS
the stats accumulator.

## Live telemetry keys (``obs/hist.py`` + ``obs/live.py``)

When the telemetry plane is active (tracing enabled, or a
``--statusz-port`` live sampler running), :meth:`MetricsRegistry.
snapshot` additionally carries ``histograms`` — one log-bucketed
latency distribution per hot stage (the pinned ``hist.HIST_STAGES``:
kernel/upload/pull/finish/fold/sync/ckpt_commit), each under the
pinned ``hist.HIST_SNAPSHOT_KEYS`` (``count``/``total_s``/``p50_ms``/
``p90_ms``/``p99_ms``/``max_ms``).  The stall watchdog
(``parallel/pipeline.py``) adds the ``stalls`` counter to an engine's
scope and publishes the ``pipeline_stall`` gauge (engine, step, age,
threshold of the most recent stall); the coordinator publishes
``mr_worker_heartbeat_age_s`` (current ages) and
``mr_worker_heartbeat_hist`` (per-worker contact-gap histogram
snapshots — the percentile-aware requeue signal) gauges.

Engines keep their historical spellings inside the scope (external
consumers — tests, soaks, BENCH artifacts — read those keys today);
:meth:`MetricsScope.unified` maps the legacy spellings onto the schema
above, which is the view new consumers (``scripts/tracecat.py``, the
trace-file registry snapshot, the schema contract test) use.  The
aliases below are the complete drift list — adding an engine key that
needs a NEW alias is a schema change and belongs in this table.

Since ISSUE 12 the prose above is backed by ONE machine-readable
tuple: :data:`SCHEMA_KEYS` (= :data:`PHASE_KEYS` +
:data:`COUNTER_KEYS`).  The ``metric-schema`` dsicheck rule gates
every stats-scope write against it and the bench contract test pins
every engine's unified view inside it, so adding an engine key is
exactly one edit here — and forgetting that edit fails both the static
gate and tier-1.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from dsi_tpu.obs.hist import active_histograms as _active_histograms

#: Legacy engine-specific spellings → unified schema names.  The
#: streaming word-count/grep engines predate the schema ("batch" for the
#: materialize phase, per-engine inflight names); everything else
#: already matches.
LEGACY_ALIASES = {
    "batch_s": "materialize_s",
    "batch_wait_s": "materialize_wait_s",
    "max_inflight_chunks": "max_inflight",
    "max_inflight_waves": "max_inflight",
    "batch_allocs": "buffer_allocs",
}

#: The canonical phase keys (module docstring) — what the schema
#: contract test pins.
PHASE_KEYS = (
    "materialize_s", "materialize_wait_s", "upload_s", "kernel_s",
    "pull_s", "device_wait_s", "d2h_s", "merge_s", "replay_s",
    "finalize_s", "write_s", "fold_s", "append_s", "hist_s",
    "sync_s", "drain_s", "widen_s", "ckpt_s", "ckpt_capture_s",
    "ckpt_commit_s", "ckpt_barrier_s",
    # compressed wire + ingest (ISSUE 13)
    "decode_s", "ingest_wait_s", "ckpt_compress_s",
    # plan layer (ISSUE 14): per-stage walls + stage-commit writes
    "plan_s", "stage_commit_s",
    # elastic dataflow (ISSUE 16): wall spent with two adjacent stages
    # advancing concurrently (seal-driven pipelining)
    "plan_overlap_s",
    # the plan layer's relay (device/relay.py): the host's cost of the
    # producer's appends, and of the pulls a spill budget forces
    "relay_append_s", "relay_spill_s",
    # overlapped shuffle (ISSUE 18): consumer time blocked on the
    # prefetch pool vs dialer wire time hidden behind the decode
    "net_fetch_wait_s", "net_overlap_s",
    # serving daemon (the "serve_daemon" scope, serve/daemon.py, and the
    # packers' row cut): the submit, admit, take_row, evict and finish
    # spans
    "submit_s", "admit_s", "take_s", "evict_s", "finish_s",
    # a stream job's main thread (ISSUE 36): the root span, the sum of
    # its direct children's keys, and what had no key: the start, the
    # pipeline core's dispatch and finish spans, the step program's call
    "job_s", "job_children_s", "start_s", "dispatch_s", "retire_s",
    "enqueue_s",
    # the host merge and the serial tail, split where the work happens
    "compact_s", "finalize_decode_s", "write_format_s", "write_commit_s",
    # what of compact_s the accumulator's caller was held for: the
    # compactions on its own thread and its waits for the merger's
    "compact_caller_s",
    # the postings table grouped into the index (``group`` span of
    # ``merge.PostingsTable.finalize_packed``: the runs found and
    # merged), in the indexer's scope
    "group_s",
    # a packed index walk's packer, a wave at a time on the producer
    # thread, and planrun --chain indexer reading its documents: what
    # the job pays before its first stage, and what the walk then waits
    # for a document its reader threads have not read yet
    "pack_s", "read_s", "read_wait_s",
    # the sort chain (parallel/sortstream.py): the sampling pre-pass, and
    # the host blocked on the device's ordering of the resident store
    "sample_s", "order_s",
    # the join chain's two phases (parallel/joinstream.py)
    "join_build_s", "join_probe_s",
    # planrun's root: what it says to stderr and writes beside mr-out-*
    "report_s",
    # the starvation account of a job's main thread (obs/trace.py)
    "starved_s", "starved_dry_s", "starved_unseen_s", "starved_by",
    "starved_groups",
    # a device table's folds and syncs, blocked on the device
    "sync_wait_s",
    # backends/aotcache.cached_compile's two halves
    "lowered_s", "compiled_s",
)

#: The direct children of a stream command's root ``job`` span on its
#: main thread, each with the key its seconds land in as
#: ``pipeline_stats`` spells it: ``job_s`` less the sum of these keys is
#: what no span covers.  ``tests/test_tracing.py`` holds the tuple to
#: what a ``--trace-dir`` run records.
JOB_CHILDREN = (
    ("start", "start_s"), ("wait", "batch_wait_s"),
    ("dispatch", "dispatch_s"), ("finish", "retire_s"),
    ("drain", "drain_s"), ("finalize", "finalize_s"),
    ("write", "write_s"),
)


def job_children_s(stats: dict) -> float:
    """Seconds in the direct children of a job's root span."""
    keys = [key for _, key in JOB_CHILDREN]
    if stats.get("depth") == 1:
        # No batcher thread: the ``materialize`` spans run inline on the
        # main thread and are children of ``job`` as well.
        keys.append("batch_s")
    return sum(stats.get(key, 0.0) for key in keys)

#: The direct children of ``planrun``'s root ``job`` span, as
#: :data:`JOB_CHILDREN` are a stream command's: ``read`` only in the
#: indexer chain, ``plan`` one a stage (its key in the plan scope),
#: ``stage_commit`` only under ``--checkpoint-dir`` (the plan scope's too).
PLAN_JOB_CHILDREN = (
    ("start", "start_s"), ("read", "read_s"), ("plan", "plan_s"),
    ("write", "write_s"), ("report", "report_s"),
    ("stage_commit", "stage_commit_s"),
)


def plan_job_children_s(pstats: dict) -> float:
    """Seconds in the direct children of a ``planrun`` job's root span:
    ``pstats`` is its ``pipeline_stats``, whose ``plan`` group holds the
    stages' and the stage commits' seconds."""
    plan = pstats.get("plan", {})
    return sum(pstats.get(key, plan.get(key, 0.0))
               for _, key in PLAN_JOB_CHILDREN)


#: The spans in which the host is blocked on the device, as ``(name,
#: lane)``: the starvation account (``obs/trace.py``) charges their time
#: to ``fed`` without asking.  ``wait`` in the ``materialize`` lane, the
#: step loop's wait for its producer, is not one of them.
DEVICE_BLOCKED = (
    ("kernel", "kernel"), ("wait", "pull"), ("d2h", "pull"),
    ("order", "kernel"), ("wait", "sync"),
)

#: ``starved_groups``: every span name in one of four groups, by what the
#: main thread was doing while the chip had nothing queued.  ``input``:
#: before a step can be cut (the start, the reads, the step loop's wait
#: for its producer, a stage's construction: ``plan``'s own time);
#: ``dispatch``: a step on its way to the device; ``merge``: a step's
#: retirement and the host's part of the device services; ``tail``:
#: after the last step, and the root's own time.  A name that is not
#: listed counts under ``tail``.
STARVED_GROUPS = (
    ("input", ("start", "read", "read_wait", "sample", "wait",
               "materialize", "pack", "plan", "probe", "backend_init",
               "launch", "lower", "compile", "join_build", "join_probe")),
    ("dispatch", ("dispatch", "upload", "enqueue", "relay_append",
                  "relay_spill")),
    ("merge", ("finish", "pull", "merge", "compact", "merge_wait", "replay",
               "fold", "sync", "widen", "group", "ckpt", "ckpt_capture",
               "ckpt_commit", "ckpt_save", "ckpt_restore", "shuffle",
               "append", "hist_fold", "hist_pull", "kernel", "d2h",
               "order")),
    ("tail", ("drain", "finalize", "decode", "write", "format", "commit",
              "report", "stage_commit", "stage_overlap", "resplit", "job")),
)

#: The canonical counter/gauge keys (module docstring) — previously
#: prose; now machine-readable because the ``metric-schema`` dsicheck
#: rule and the bench contract test both read THIS tuple, so the
#: docstring, the static gate, and the test cannot drift apart.
COUNTER_KEYS = (
    # pipeline / engine counters
    "steps", "waves", "depth", "replays", "results_ready", "step_pulls",
    "pulls_early", "pulls_late",
    "sync_pulls", "widens", "folds", "fold_overflows", "appends",
    "append_overflows",
    "postings_widens", "topk_snapshots", "hist_folds", "hist_pulls",
    "table_cap", "sync_every", "max_inflight",
    "buffer_allocs", "device_accumulate", "donate_chunks", "stalls",
    "device_rows",
    # the grep engine's row cut (grepstream.batch_lines): the bytes that
    # waited behind a block's last cut and so were copied twice
    "recopied_bytes",
    # the host accumulator (parallel/merge.py PackedCounts)
    "merge_rows_in", "merge_rows_sorted", "merge_compacts",
    "merge_runs_in", "merge_runs_unsorted", "merge_compacts_async",
    # its result and the partition writer: spellings turned into ``str``
    # (0 in a wcstream job), rows rendered from the merged table's
    # arrays, rows formatted from a dict (the host fallback's)
    "finalize_decoded_keys", "write_rows_packed", "write_rows_dict",
    # the indexer's wave walk (parallel/grepstream.py): documents handed
    # over, waves dispatched by padded chunk size, the documents' bytes
    # and the padded bytes they were uploaded as, and the table it ends
    # with: posting rows grouped, terms of the index, the runs the group
    # merged, the rows it had to sort first (their buffer was not in runs)
    "docs", "waves_by_size", "wave_doc_bytes", "wave_chunk_bytes",
    "postings_rows", "index_terms", "group_runs", "group_rows_sorted",
    # whether it packs whole documents into its waves, the documents it
    # dispatched in waves, the most one wave held
    "pack_docs", "wave_docs", "docs_per_wave_max",
    # its documents read ahead of it (utils/ioread.ReadAheadDocs; at the
    # top of planrun's pipeline_stats): documents asked for, those that
    # were there when first asked for, the pool's threads
    "read_docs", "read_ahead_hits", "read_threads",
    # the sort chain (parallel/sortstream.py): records of the job, keys
    # the pre-pass sampled, bytes of the store that stays on the device,
    # records a partition (a list of n_reduce), single-key sort passes
    # of the ordering
    "sort_records", "sort_sample_keys", "sort_resident_bytes",
    "sort_partition_rows", "sort_order_passes",
    # across a mesh: its devices, rows of a device's store, records
    # (and their bytes) that left the device that read them
    "sort_devices", "sort_device_capacity", "sort_exchange_rows",
    "sort_exchange_bytes",
    # the aggregation chain (the stream engine with a map,
    # ops/fieldsum.py): rows read, keys of the merged table, lanes a sum
    "agg_rows", "agg_groups", "agg_value_lanes",
    # the join chain (parallel/joinstream.py): rows, bytes and steps of
    # the build side, bytes of the table that stays on the device, probe
    # rows read, inside the window and matched, keys of the merged table,
    # lanes of a step's sums
    "join_build_rows", "join_build_bytes", "join_build_steps",
    "join_table_bytes", "join_probe_rows", "join_window_rows",
    "join_matched_rows", "join_groups", "join_value_lanes",
    # checkpoint/restore
    "ckpt_saves", "ckpt_every", "ckpt_async", "ckpt_delta",
    "ckpt_deltas", "ckpt_full_bytes", "ckpt_delta_bytes",
    "resume_gap_s", "resume_cursor", "resume_wave",
    # mesh-sharded services
    "mesh_shards", "pull_bytes", "shard_widens", "shard_imbalance",
    "resharded_resume",
    # compressed wire + parallel ingest (ISSUE 13): reader-pool fold
    # (utils/ioread.py ingest_stats → fold_source_stats) and the
    # chunk-upload codec's attribution (ops/wirecodec.py)
    "ingest_readers", "ingest_blocks", "readahead_hit_pct",
    "wire_upload", "wire_steps", "wire_raw_steps", "wire_packed_bytes",
    "wire_ratio", "ckpt_delta_raw_bytes", "ckpt_compress",
    # serving daemon (the "serve"/"serve_grep" scopes, serve/pack.py)
    "packed_steps", "packed_rows", "max_tenants_per_step",
    "host_fallbacks",
    # the grep packer's one step in flight: steps confirmed ahead of
    # their turn for a lane's eviction or finalize (``results_ready``,
    # above, counts those whose results the device had finished)
    "settles",
    # the daemon's own counts (the "serve_daemon" scope) and, per job,
    # the seconds from submission to its first row and from there to
    # its committed output (a job record's ``stats``)
    "submits", "evictions", "resumes", "jobs_done", "queue_wait_s",
    "service_s",
    # plan layer (the "plan" scope, dsi_tpu/plan + device/relay.py):
    # multi-stage chain accounting — handoff bytes vs commit bytes is
    # the zero-host-round-trip evidence
    "plan_stages", "plan_handoff", "plan_handoff_bytes",
    "plan_intermediate_bytes", "plan_commit_bytes",
    "plan_relay_buffers", "plan_spilled_bytes", "plan_restored_bytes",
    "plan_resumed_stages", "plan_stage_walls",
    # elastic dataflow (ISSUE 16): pipelined pair + stage-shard fan-out
    "plan_pipelined", "plan_stage_shards",
    # per-stage engine scopes, a stage's input bytes, relay calls
    "stage_stats", "bytes_in", "relay_appends", "relay_seals",
    # network data plane (ISSUE 17, the "net" scope, dsi_tpu/net):
    # worker-served shuffle attribution — raw vs wire bytes is the
    # codec's evidence, locality_hits the placement policy's, and
    # net_refetches the re-fetch-from-replacement machinery's
    "net_fetches", "net_local_reads", "net_bytes_raw", "net_bytes_wire",
    "net_ratio", "net_fetch_failures", "net_refetches", "locality_hits",
    # overlapped shuffle (ISSUE 18): the effective prefetch window
    # (gauge — 1 means the serial path ran)
    "net_prefetch_window",
    # replicated control plane (ISSUE 20, dsi_tpu/replica): the Raft
    # node's status surface — log-application progress and leadership
    # churn per replica (group_status / the failover harness read them)
    "applied_index", "failovers",
)

#: THE schema: every key an engine scope may carry, under its unified
#: spelling.  Legacy spellings (LEGACY_ALIASES keys) are additionally
#: accepted at write sites; ``unified()`` maps them here.
SCHEMA_KEYS = PHASE_KEYS + COUNTER_KEYS

#: The engine names the four streaming engines register under.
ENGINES = ("stream", "tfidf", "grep", "indexer")

#: Every ``dsi_serve_*`` series name the daemon may emit on
#: ``/metrics`` (``serve/daemon.py _metrics_section``).  Pinned the same
#: way SCHEMA_KEYS is: the ``metric-schema`` dsicheck rule requires any
#: ``dsi_serve_``-prefixed string literal in the tree to name (or be a
#: truncated f-string head of) a series listed here, and the bench
#: contract test asserts the daemon's emission stays inside this set —
#: so the serving surface cannot grow an unregistered series, and its
#: cardinality stays bounded by construction (per-tenant series are
#: emitted for the top ``DSI_SERVE_METRICS_TENANTS`` tenants only).
SERVE_SERIES = (
    "dsi_serve_jobs_total", "dsi_serve_queued", "dsi_serve_resident",
    "dsi_serve_tenants_total", "dsi_serve_queue_depth",
    "dsi_serve_shed_total", "dsi_serve_rate_limited_total",
    "dsi_serve_evictions_p99_total", "dsi_serve_evictions_quota_total",
    "dsi_serve_packed_steps", "dsi_serve_packed_rows",
    "dsi_serve_grep_packed_steps", "dsi_serve_grep_packed_rows",
    "dsi_serve_tenant_steps", "dsi_serve_tenant_rows",
    "dsi_serve_tenant_evictions", "dsi_serve_tenant_resumes",
    "dsi_serve_tenant_done",
    "dsi_serve_tenant_resume_gap_seconds",
    "dsi_serve_tenant_p99_ms",
)

#: Every ``dsi_replica_*`` gauge the replicated control plane
#: (``dsi_tpu/replica/node.py``) publishes — pinned alongside
#: SERVE_SERIES for the same reason: the failover evidence surface
#: (``scripts/tracecat.py`` replica lane, the tier-1 replication smoke)
#: keys on these names, so growing one is a schema change that starts
#: here.
REPLICA_SERIES = (
    "dsi_replica_term", "dsi_replica_elections",
    "dsi_replica_applied_index",
)


class MetricsScope(dict):
    """One engine's stats dict, registered in the registry at creation.
    Behaves exactly like the plain dict it replaces (engines mutate it
    with ``+=``/``setdefault``/``update``); :meth:`unified` is the
    schema-normalized read view."""

    def __init__(self, engine: str):
        super().__init__()
        self.engine = engine

    def unified(self) -> Dict:
        """The scope under the documented schema: legacy spellings
        renamed, everything else passed through."""
        return {LEGACY_ALIASES.get(k, k): v for k, v in self.items()}


class MetricsRegistry:
    """Process-global map of live engine scopes + named gauges.  An
    engine re-registers its scope per run (latest wins) — the registry
    answers "what did the most recent <engine> run report", which is
    what bench rows, the CLIs' ``--stats``, and the trace-file snapshot
    all want."""

    def __init__(self):
        self._lock = threading.Lock()
        self._scopes: Dict[str, MetricsScope] = {}
        self._gauges: Dict[str, object] = {}

    def scope(self, engine: str) -> MetricsScope:
        """A fresh scope for one engine run, registered as the engine's
        current phase dict."""
        sc = MetricsScope(engine)
        with self._lock:
            self._scopes[engine] = sc
        return sc

    def phases(self, engine: str) -> Optional[MetricsScope]:
        """The engine's current phase dict (None before its first run)."""
        with self._lock:
            return self._scopes.get(engine)

    def engines(self) -> tuple:
        with self._lock:
            return tuple(sorted(self._scopes))

    def set_gauge(self, name: str, value) -> None:
        """Publish a named gauge (e.g. the coordinator's per-worker
        heartbeat ages) — read back via :meth:`gauge`/:meth:`snapshot`;
        the speculative-execution hook consumes these."""
        with self._lock:
            self._gauges[name] = value

    def gauge(self, name: str, default=None):
        with self._lock:
            return self._gauges.get(name, default)

    def snapshot(self) -> Dict:
        """JSON-ready dump: every engine's unified view + the gauges —
        embedded in trace files by ``obs/trace.py`` at flush — plus the
        stage latency histograms whenever the live telemetry plane is
        active (``obs/hist.py``)."""
        with self._lock:
            scopes = dict(self._scopes)
            gauges = dict(self._gauges)
        out = {"engines": {e: sc.unified() for e, sc in scopes.items()},
               "gauges": gauges}
        hs = _active_histograms()
        if hs is not None:
            out["histograms"] = hs.snapshot()
        return out


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def metrics_scope(engine: str) -> MetricsScope:
    """Shorthand: a fresh registered scope on the global registry — the
    one-liner every engine calls where it used to build ``stats = {}``."""
    return _REGISTRY.scope(engine)
