"""Candidate forms of ``ops/wordcount.group_sorted``'s per-run totals and
of ``parallel/shuffle.shuffle_rows``' block starts, timed on the chip
(PR 39; PERF.md section 6 holds the table this printed).  Not a test and
not a benchmark cell: run it through the chip tool,

    python scripts/segsum_micro.py [--tiny] [--quick] [--from N]

A sum over sorted segment ids is a prefix sum read at the run boundaries.
Every form returns the totals of the parent's ``jax.ops.segment_sum`` and
is checked against ``np.add.reduceat`` (wrapping, in the counts' dtype)
before it is timed; every form of the starts against ``np.searchsorted``.
One JSON line per (form, shape) on stdout and in
``chiprun_out/segsum_micro.jsonl``.  ``--tiny`` divides every shape by
1,024 (a rehearsal of the script on the CPU, whose times mean nothing).
The whole script compiles for over a quarter of an hour on the chip, most
of it the two 64-bit forms that lost (``jnp.cumsum`` of ``uint64`` 50-96 s
a program, ``assoc_pairs`` 100 s): ``--quick`` leaves those two out, and
``--from N`` starts at the N-th shape (the output file is then appended
to).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsi_tpu.ops.wordcount import (compact_positions, group_sorted,
                                   running_sum)
from dsi_tpu.utils.jaxcompat import enable_x64

# (rows t, out_cap, counts dtype): a batch map's group, a stream step's
# at its two table rungs, a one-chip fold's and a mesh fold's.
SHAPES = (((1 << 22) + 1, 1 << 17, "int32"),
          ((1 << 18) + 1, 1 << 16, "int32"),
          ((1 << 18) + 1, 1 << 14, "int32"),
          (327_680, 1 << 18, "uint64"),
          (1_310_720, 1 << 18, "uint64"))

# (rows, n_dev): a one-chip step's shuffle, a mesh step's, a mesh fold's.
SHUFFLES = ((1 << 16, 1), (1 << 16, 4), (1 << 18, 4))


# ── the prefix sum, 64-bit ──────────────────────────────────────────────

def cumsum_own(c):
    """``jnp.cumsum`` in the counts' dtype: for 64 bits, whatever XLA
    makes of a 64-bit ``reduce-window`` on a chip that emulates them."""
    return jnp.cumsum(c, dtype=c.dtype)


def _halves(c):
    return c.astype(jnp.uint32), (c >> 32).astype(jnp.uint32)


def _join(lo, hi):
    return (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)


def split32_halves(c):
    """Three 32-bit scans: the low halves summed modulo 2^32, the wraps of
    that sum counted (a step wraps at most once, and exactly when the sum
    falls), the high halves summed and the wraps added."""
    lo, hi = _halves(c)
    slo = jnp.cumsum(lo, dtype=jnp.uint32)
    prev = jnp.concatenate([jnp.zeros((1,), jnp.uint32), slo[:-1]])
    wraps = jnp.cumsum((slo < prev).astype(jnp.uint32), dtype=jnp.uint32)
    return slo, jnp.cumsum(hi, dtype=jnp.uint32) + wraps


def split32(c):
    return _join(*split32_halves(c))


def assoc_pairs(c):
    """``lax.associative_scan`` over (lo, hi) ``uint32`` pairs with the
    carry in the combiner."""
    def add(a, b):
        lo = a[0] + b[0]
        return lo, a[1] + b[1] + (lo < a[0]).astype(jnp.uint32)
    return _join(*lax.associative_scan(add, _halves(c)))


def blocked(c, block=1024):
    """Two levels: a 64-bit cumsum inside blocks of 1,024 rows, one over
    the blocks' totals, and the offsets added."""
    (t,) = c.shape
    nb = -(-t // block)
    x = jnp.concatenate([c, jnp.zeros((nb * block - t,), c.dtype)])
    inner = jnp.cumsum(x.reshape(nb, block), axis=1, dtype=c.dtype)
    tops = jnp.cumsum(inner[:, -1], dtype=c.dtype)
    offs = jnp.concatenate([jnp.zeros((1,), c.dtype), tops[:-1]])
    return (inner + offs[:, None]).reshape(-1)[:t]


PREFIX64 = {"cumsum": cumsum_own, "split32": split32,
            "assoc_pairs": assoc_pairs, "blocked": blocked}
PREFIX32 = {"cumsum": cumsum_own}


# ── the totals ─────────────────────────────────────────────────────────

def segment_sum_totals(c, is_new, valid, bounds, out_cap):
    """The parent's form: ids from a cumsum of the run starts, one
    ``scatter-add`` over sorted ids."""
    uid = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    return jax.ops.segment_sum(
        jnp.where(valid, c, 0), jnp.where(valid, uid, out_cap),
        num_segments=out_cap + 1, indices_are_sorted=True)[:out_cap]


def scan_totals(prefix):
    """A prefix sum read at the run boundaries (``bounds``: the starts of
    the first ``out_cap + 1`` runs, then ``t``) and differenced."""
    def totals(c, is_new, valid, bounds, out_cap):
        zero = jnp.zeros((), c.dtype)
        csum = prefix(jnp.where(valid, c, zero))
        before = jnp.where(bounds > 0, csum[jnp.maximum(bounds - 1, 0)],
                           zero)
        return before[1:] - before[:-1]
    return totals


def split32_gather_halves(c, is_new, valid, bounds, out_cap):
    """``split32`` with the two halves gathered apart and joined after
    the gather, so that no 64-bit array of ``t`` rows is formed."""
    slo, shi = split32_halves(jnp.where(valid, c, jnp.zeros((), c.dtype)))
    at = jnp.maximum(bounds - 1, 0)
    before = jnp.where(bounds > 0, _join(slo[at], shi[at]),
                       jnp.zeros((), c.dtype))
    return before[1:] - before[:-1]


def parent_group_sorted(skeys_cols, counts, out_cap):
    """``group_sorted`` as the parent commit has it."""
    t = skeys_cols[0].shape[0]
    k = len(skeys_cols)
    dtype = skeys_cols[0].dtype
    pad = jnp.array(jnp.iinfo(dtype).max, dtype)
    keys = jnp.stack(skeys_cols, axis=1)
    valid = skeys_cols[0] != pad
    prev = jnp.concatenate([jnp.full((1, k), pad, dtype), keys[:-1]], axis=0)
    is_new = jnp.any(keys != prev, axis=1) & valid
    n_unique = jnp.sum(is_new, dtype=jnp.int32)
    uid = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    totals = jax.ops.segment_sum(
        jnp.where(valid, counts, 0), jnp.where(valid, uid, out_cap),
        num_segments=out_cap + 1, indices_are_sorted=True)[:out_cap]
    upos = compact_positions(is_new, out_cap, t - 1)
    ovalid = jnp.arange(out_cap, dtype=jnp.int32) < n_unique
    return keys, totals, upos, ovalid, n_unique


# ── the shuffle's block starts ─────────────────────────────────────────

def bincount_starts(dest, n_dev):
    """The parent's form: a 64-bit ``scatter-add`` into ``n_dev + 1``
    bins under the x64 scope, then the exclusive cumsum."""
    sdest = dest[jnp.argsort(dest, stable=True)]
    counts = jnp.bincount(sdest, length=n_dev + 1).astype(jnp.int32)
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])


def sort_only(dest, n_dev):
    """What both forms above share, to subtract."""
    return dest[jnp.argsort(dest, stable=True)][:n_dev + 1]


def compare_sum_starts(dest, n_dev):
    return jnp.sum(
        dest[None, :] < jnp.arange(n_dev + 1, dtype=dest.dtype)[:, None],
        axis=1, dtype=jnp.int32)


def searchsorted_starts(dest, n_dev):
    sdest = dest[jnp.argsort(dest, stable=True)]
    return jnp.searchsorted(
        sdest, jnp.arange(n_dev + 1, dtype=dest.dtype)).astype(jnp.int32)


STARTS = {"bincount": bincount_starts, "sort_only": sort_only,
          "compare_sum": compare_sum_starts,
          "searchsorted": searchsorted_starts}


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


def runs(t, out_cap, dtype, rng):
    """Sorted rows as a fold or a map hands them over: 19 in 20 valid,
    nine tenths of ``out_cap`` runs, pad rows last.  64-bit counts near
    2^63, so the running sum wraps many times; 32-bit ones like a
    step's (small, a few large)."""
    n_valid = t - t // 20
    n_unique = min(n_valid, out_cap - out_cap // 10)
    is_new = np.zeros(t, bool)
    is_new[rng.choice(np.arange(1, n_valid), n_unique - 1,
                      replace=False)] = True
    is_new[0] = True
    valid = np.arange(t) < n_valid
    if dtype == "uint64":
        c = rng.integers(0, 1 << 40, t, dtype=np.uint64)
        c[rng.random(t) < 0.01] = np.uint64((1 << 63) - 12345)
    else:
        c = rng.integers(1, 50, t).astype(dtype)
        c[rng.random(t) < 0.001] = 1 << 20
    c[~valid] = 0
    starts = np.flatnonzero(is_new)
    want = np.zeros(out_cap, c.dtype)
    want[:n_unique] = np.add.reduceat(c, starts)
    key = (np.cumsum(is_new) - 1).astype(np.uint32)
    key[~valid] = 0xFFFFFFFF
    return c, is_new, valid, n_unique, want, key


def main(argv):
    rng = np.random.default_rng(39)
    dev = jax.devices()[0]
    rows = []

    def emit(**row):
        row["device"] = f"{dev.platform}:{dev.device_kind}"
        rows.append(row)
        print(json.dumps(row), flush=True)

    cut = 10 if "--tiny" in argv else 0
    first = int(argv[argv.index("--from") + 1]) if "--from" in argv else 0
    slow = ("cumsum", "assoc_pairs") if "--quick" in argv else ()
    for t, out_cap, dtype in SHAPES[first:]:
        t, out_cap = ((t - 1) >> cut) + 1, ((out_cap - 1) >> cut) + 1
        c_np, is_new_np, valid_np, n_unique, want, key_np = runs(
            t, out_cap, dtype, rng)
        with enable_x64(True):
            c, is_new, valid = map(jnp.asarray, (c_np, is_new_np, valid_np))
            key = jnp.asarray(key_np)
            starts = compact_positions(is_new, out_cap + 1, t - 1)
            bounds = jnp.where(
                jnp.arange(out_cap + 1, dtype=jnp.int32) < n_unique,
                starts, jnp.int32(t))
            prefixes = PREFIX32
            if dtype == "uint64":
                prefixes = {name: p for name, p in PREFIX64.items()
                            if name not in slow}
            want_csum = np.cumsum(c_np, dtype=c_np.dtype)
            for name, prefix in prefixes.items():
                out, first_s, ms = timed(jax.jit(prefix), (c,), 20)
                emit(what="prefix", form=name, t=t, dtype=str(out.dtype),
                     ms=ms, first_call_s=first_s,
                     equal=bool((np.asarray(out) == want_csum).all()))
            forms = {"segment_sum": segment_sum_totals}
            forms.update({name: scan_totals(p)
                          for name, p in prefixes.items()})
            if dtype == "uint64":
                forms["split32_gather_halves"] = split32_gather_halves
            for name, form in forms.items():
                fn = jax.jit(form, static_argnums=4)
                out, first_s, ms = timed(
                    fn, (c, is_new, valid, bounds, out_cap), 20)
                got = np.asarray(out)[:n_unique]
                emit(what="totals", form=name, t=t, out_cap=out_cap,
                     dtype=str(out.dtype), ms=ms, first_call_s=first_s,
                     equal=bool((got == want[:n_unique]).all()))
            for name, form in (("parent", parent_group_sorted),
                               ("adopted", group_sorted)):
                fn = jax.jit(lambda k, x, form=form: form((k,), x, out_cap))
                out, first_s, ms = timed(fn, (key, c), 20)
                emit(what="group_sorted", form=name, t=t, out_cap=out_cap,
                     dtype=str(out[1].dtype), ms=ms, first_call_s=first_s,
                     equal=bool((np.asarray(out[1]) == want).all()
                                and int(out[4]) == n_unique))
            if dtype == "uint64":
                out, first_s, ms = timed(jax.jit(running_sum), (c,), 20)
                emit(what="prefix", form="adopted", t=t,
                     dtype=str(out.dtype), ms=ms, first_call_s=first_s,
                     equal=bool((np.asarray(out) == want_csum).all()))

    for m, n_dev in SHUFFLES:
        m = ((m - 1) >> cut) + 1
        dest_np = rng.integers(0, n_dev + 1, m).astype(np.int32)
        want = np.searchsorted(np.sort(dest_np), np.arange(n_dev + 1))
        with enable_x64(True):
            dest = jnp.asarray(dest_np)
            for name, form in STARTS.items():
                fn = jax.jit(form, static_argnums=1)
                out, first_s, ms = timed(fn, (dest, n_dev), 20)
                emit(what="starts", form=name, m=m, n_dev=n_dev, ms=ms,
                     first_call_s=first_s, dtype=str(out.dtype),
                     equal=bool(name == "sort_only"
                                or (np.asarray(out) == want).all()))

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/segsum_micro.jsonl", "a" if first else "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0 if all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
