"""Candidate forms of the word-count program's lane movement, timed on the
chip (PR 46; PERF.md section 6 holds the table this printed).  Not a test
and not a benchmark cell: run it through the chip tool,

    python scripts/pack_micro.py [--tiny] [--forms a,b,...] [--compile-only]

The movement takes the ``k`` key lanes, the token length and, for a packed
chunk, the document lane from the chunk's ``n`` positions to the ``t_cap``
rows of the token buffer (``ops/wordcount.tokenize_group_core``, scopes
``compact`` and ``pack``).  Every form returns ``(packed_cols, lengths,
doc_lane)`` and is checked, bit for bit and PAD rows included, against
``gathers``: the program's form until PR 46, two compaction sorts and
``k`` gathers ``b32[start_pos + 4j]``, kept here as the reference.  One
JSON line per (form, shape) on stdout and in
``chiprun_out/pack_micro.jsonl``: run ms, first-call (compile) s, the
compiled program's temporary bytes.  ``--tiny`` divides every shape by
1,024 (a rehearsal on the CPU, whose times mean nothing); ``--compile-only``
compiles each form for a described v5e with no chip attached and prints
compile seconds and bytes alone.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsi_tpu.ops.wordcount import (DOC_SEP, _PAD_KEY, _byte_mask,
                                   _move_left, _shift_left,
                                   compact_positions, is_ascii_letter,
                                   token_lanes)
from dsi_tpu.utils.jaxcompat import enable_x64

# (n, doc_sep): a stream step's chunk, a batch map's, a packed index wave's.
SHAPES = ((1 << 20, None), (1 << 24, None), (1 << 19, DOC_SEP))
K = 4  # max_word_len 16
PAD = jnp.uint32(_PAD_KEY)


def _edges(chunk):
    letter = is_ascii_letter(chunk)
    prev_letter = jnp.concatenate([jnp.zeros((1,), jnp.bool_), letter[:-1]])
    next_letter = jnp.concatenate([letter[1:], jnp.zeros((1,), jnp.bool_)])
    return letter & ~prev_letter, letter & ~next_letter


def _b32(chunk):
    c = chunk.astype(jnp.uint32)
    return ((c << 24) | (_shift_left(c, 1) << 16)
            | (_shift_left(c, 2) << 8) | _shift_left(c, 3))


def _finish(cols, lengths, docs, n_tokens, t_cap):
    """Cut to the token buffer, mask the lanes by the length, PAD the rows
    past the last token."""
    valid = jnp.arange(t_cap, dtype=jnp.int32) < n_tokens

    def cut(x):
        if x.shape[0] < t_cap:
            x = jnp.concatenate(
                [x, jnp.zeros((t_cap - x.shape[0],), x.dtype)])
        return x[:t_cap]

    lengths = jnp.where(valid, cut(lengths), 0)
    cols = tuple(
        jnp.where(valid, cut(c) & _byte_mask(jnp.clip(lengths - 4 * j, 0, 4)),
                  PAD) for j, c in enumerate(cols))
    doc = None if docs is None else jnp.where(
        valid, cut(docs).astype(jnp.uint32), PAD)
    return cols, lengths, doc


def gathers(chunk, t_cap, doc_sep):
    """The program until PR 46: two compaction sorts, ``k`` gathers (and
    one more for the document lane)."""
    n = chunk.shape[0]
    starts, ends = _edges(chunk)
    n_tokens = jnp.sum(starts, dtype=jnp.int32)
    start_pos = compact_positions(starts, t_cap, n - 1)
    end_pos = compact_positions(ends, t_cap, n - 1)
    valid = jnp.arange(t_cap, dtype=jnp.int32) < n_tokens
    lengths = jnp.where(valid, end_pos - start_pos + 1, 0)
    doc = None
    if doc_sep is not None:
        seps = jnp.cumsum(chunk == jnp.uint8(doc_sep), dtype=jnp.int32)
        doc = jnp.where(valid, seps[start_pos].astype(jnp.uint32), PAD)
    b32 = _b32(chunk)
    cols = tuple(
        jnp.where(valid, b32[start_pos + 4 * j]
                  & _byte_mask(jnp.clip(lengths - 4 * j, 0, 4)), PAD)
        for j in range(K))
    return cols, lengths, doc


def _per_position(chunk, doc_sep):
    """What every position would hand over if it were a token's start:
    the unmasked lanes, the distance to the token's end, the separators
    before it."""
    n = chunk.shape[0]
    starts, ends = _edges(chunk)
    pos = jnp.arange(n, dtype=jnp.int32)
    next_end = lax.cummin(jnp.where(ends, pos, jnp.int32(n)), reverse=True)
    b32 = _b32(chunk)
    lanes = [_shift_left(b32, 4 * j) for j in range(K)]
    seps = None if doc_sep is None else jnp.cumsum(
        chunk == jnp.uint8(doc_sep), dtype=jnp.int32)
    return starts, pos, lanes, next_end - pos + 1, seps


def sort_carried(chunk, t_cap, doc_sep):
    """A: the lanes ride the compaction's sort, one single-key sort over
    the chunk's positions with every lane an operand."""
    n = chunk.shape[0]
    starts, pos, lanes, length, seps = _per_position(chunk, doc_sep)
    n_tokens = jnp.sum(starts, dtype=jnp.int32)
    key = jnp.where(starts, pos, jnp.int32(n))
    extra = () if seps is None else (seps,)
    out = lax.sort((key, *lanes, length, *extra), num_keys=1)
    return _finish(out[1:1 + K], out[1 + K],
                   out[2 + K] if extra else None, n_tokens, t_cap)


def _row_gather(stack_axis):
    def form(chunk, t_cap, doc_sep):
        """B: one compaction sort for the starts, one gather of rows that
        hold every lane."""
        n = chunk.shape[0]
        starts, pos, lanes, length, seps = _per_position(chunk, doc_sep)
        n_tokens = jnp.sum(starts, dtype=jnp.int32)
        start_pos = compact_positions(starts, t_cap, n - 1)
        extra = [] if seps is None else [seps.astype(jnp.uint32)]
        table = jnp.stack(
            [*lanes, length.astype(jnp.uint32), *extra], axis=stack_axis)
        rows = jnp.take(table, start_pos, axis=1 - stack_axis)
        col = (lambda j: rows[:, j]) if stack_axis else (lambda j: rows[j])
        return _finish([col(j) for j in range(K)],
                       col(K).astype(jnp.int32),
                       col(K + 1) if extra else None, n_tokens, t_cap)
    return form


def shifts(chunk, t_cap, doc_sep):
    """C: no sort and no gather; the starts move to the front in log-step
    shifted selects (``ops/wordcount._move_left``) over the chunk's
    positions."""
    starts, pos, lanes, length, seps = _per_position(chunk, doc_sep)
    s32 = starts.astype(jnp.int32)
    n_tokens = jnp.sum(s32)
    d = jnp.where(starts, pos - (jnp.cumsum(s32) - s32), 0)
    extra = [] if seps is None else [seps]
    out = _move_left(d, [*lanes, length, *extra])
    return _finish(out[:K], out[K], out[K + 1] if extra else None,
                   n_tokens, t_cap)


def shifts_half(chunk, t_cap, doc_sep):
    """C2, adopted: the same movement over pairs of positions, a pair
    holding at most one start.  This is the program's own function."""
    assert t_cap == chunk.shape[0] // 4 + 1
    cols, lengths, _, doc, _ = token_lanes(
        chunk, max_word_len=4 * K, t_cap_frac=4, doc_sep=doc_sep)
    return cols, lengths, doc


FORMS = {"gathers": gathers, "sort_carried": sort_carried,
         "rows_nk": _row_gather(1), "rows_kn": _row_gather(0),
         "shifts": shifts, "shifts_half": shifts_half}


def timed(fn, args, reps):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return out, first_s, (time.perf_counter() - t0) / reps * 1e3


def text(n_bytes, doc_sep, seed):
    """The benchmark's text (``benchmarks/corpus.py`` at its defaults: a
    20,000-word vocabulary), zero-padded to ``n_bytes``; for a packed
    chunk a separator every 4 KiB, two of them adjacent (an empty
    document) and one the last byte."""
    import corpus

    blob = np.frombuffer(corpus.generate_bytes(
        n_bytes - 64, seed, corpus.effective({}, {})), np.uint8)
    buf = np.zeros(n_bytes, np.uint8)
    buf[:len(blob)] = blob
    if doc_sep is not None:
        every = min(4096, n_bytes // 8)
        cuts = np.arange(every, n_bytes, every)
        buf[cuts] = doc_sep
        buf[cuts[0] + 1] = doc_sep
        buf[-1] = doc_sep
    return buf


def _same(got, want):
    (cols, lengths, doc), (rcols, rlengths, rdoc) = got, want
    pairs = [*zip(cols, rcols), (lengths, rlengths)]
    if rdoc is not None:
        pairs.append((doc, rdoc))
    return all(bool((np.asarray(a) == np.asarray(b)).all())
               for a, b in pairs)


def main(argv):
    dev = jax.devices()[0]
    rows = []
    names = list(FORMS)
    if "--forms" in argv:
        names = argv[argv.index("--forms") + 1].split(",")
        if "gathers" not in names:
            names.insert(0, "gathers")
    cut = 10 if "--tiny" in argv else 0
    one_chip = None
    if "--compile-only" in argv:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one_chip = SingleDeviceSharding(topo.devices[0])

    def emit(**row):
        row["device"] = ("described v5e" if one_chip is not None
                         else f"{dev.platform}:{dev.device_kind}")
        rows.append(row)
        print(json.dumps(row), flush=True)

    for n, doc_sep in SHAPES:
        n >>= cut
        t_cap = n // 4 + 1
        ref = None
        chunk = None if one_chip is not None else jnp.asarray(
            text(n, doc_sep, 46))
        for name in names:
            fn = jax.jit(FORMS[name], static_argnums=(1, 2))
            with enable_x64(True):
                spec = jax.ShapeDtypeStruct((n,), jnp.uint8,
                                            sharding=one_chip)
                t0 = time.perf_counter()
                try:
                    compiled = fn.lower(spec, t_cap, doc_sep).compile()
                except Exception as e:  # a form the compiler refuses
                    emit(form=name, n=n, t_cap=t_cap, error=repr(e)[:300])
                    continue
                compile_s = time.perf_counter() - t0
                mem = compiled.memory_analysis()
                temp = getattr(mem, "temp_size_in_bytes", None)
                if one_chip is not None:
                    emit(form=name, n=n, t_cap=t_cap, docs=doc_sep is not None,
                         compile_s=compile_s, temp_bytes=temp)
                    continue
                out, first_s, ms = timed(
                    lambda c: fn(c, t_cap, doc_sep), (chunk,),
                    5 if n >= 1 << 22 else 30)
            ref = ref or out
            emit(form=name, n=n, t_cap=t_cap, docs=doc_sep is not None,
                 ms=ms, compile_s=compile_s, first_call_s=first_s,
                 temp_bytes=temp, ns_per_row=ms * 1e6 / t_cap,
                 equal=_same(out, ref))

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pack_micro.jsonl", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
