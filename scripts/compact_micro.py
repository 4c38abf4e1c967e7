"""Candidate forms of ``ops/wordcount.compact_positions`` timed on the chip
(PR 35; PERF.md section 6 holds the table this printed).  Not a test and
not a benchmark cell: run it through the chip tool,

    python scripts/compact_micro.py [--kernel] [--tiny]

Every form returns the values of ``jnp.nonzero(mask, size=, fill_value=)``
and is checked against ``np.flatnonzero`` before it is timed.  One JSON
line per (form, shape) on stdout and in ``chiprun_out/compact_micro.jsonl``.
``--kernel`` also times the whole word-count program at a batch map's and
a stream step's shape (the forms of the token lengths that PR 35 timed
here went with PR 46: the program compacts no end positions any more,
``scripts/pack_micro.py``); ``--tiny`` divides every shape by 1,024 (a rehearsal
of the script on the CPU, whose times mean nothing).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dsi_tpu.ops.wordcount import compact_positions, tokenize_group_core
from dsi_tpu.utils.jaxcompat import enable_x64

# (m, size): a batch map's two compactions and its group's, a stream
# step's two and its group's, a fold's group (the table's capacity).
SHAPES = ((1 << 24, (1 << 22) + 1), ((1 << 22) + 1, 1 << 17),
          (1 << 20, (1 << 18) + 1), ((1 << 18) + 1, 1 << 16),
          (1 << 18, 1 << 18))


def nonzero64(mask, size, fill_value):
    """The parent's call, under the x64 scope its programs run in."""
    return jnp.nonzero(mask, size=size, fill_value=fill_value)[0]


def scatter32(mask, size, fill_value):
    """PR 24's form as its title tells it: rank by an int32 cumsum, write
    each set position to its rank with one int32 scatter."""
    m = mask.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dst = jnp.where(mask, rank, size)
    out = jnp.full((size,), fill_value, jnp.int32)
    return out.at[dst].set(jnp.arange(m, dtype=jnp.int32), mode="drop",
                           unique_indices=True)


def segmin32(mask, size, fill_value):
    """The same rank as a scatter over sorted ids: the inclusive count is
    monotone, and the first position of each of its runs is the set one."""
    m = mask.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    first = jax.ops.segment_min(
        jnp.arange(m, dtype=jnp.int32), jnp.where(rank < 0, size, rank),
        num_segments=size + 1, indices_are_sorted=True)[:size]
    count = jnp.sum(mask, dtype=jnp.int32)
    return jnp.where(jnp.arange(size, dtype=jnp.int32) < count, first,
                     jnp.int32(fill_value))


FORMS = {"nonzero64": nonzero64, "sort": compact_positions,
         "scatter32": scatter32, "segmin32": segmin32}


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


def text(n_bytes, rng):
    """Zipf-weighted words of 2-12 letters over a 20,000-word vocabulary,
    zero-padded to the next power of two."""
    vocab = ["".join(rng.choice(list("abcdefghijklmnopqrstuvwxyz"),
                                size=int(k)))
             for k in rng.integers(2, 13, size=20_000)]
    w = 1.0 / (np.arange(len(vocab)) + 2.7)
    words = rng.choice(vocab, size=n_bytes // 5, p=w / w.sum())
    blob = " ".join(words).encode()[:n_bytes - 64]
    buf = np.zeros(n_bytes, np.uint8)
    buf[:len(blob)] = np.frombuffer(blob, np.uint8)
    return buf


def main(argv):
    rng = np.random.default_rng(35)
    dev = jax.devices()[0]
    rows = []

    def emit(**row):
        row["device"] = f"{dev.platform}:{dev.device_kind}"
        rows.append(row)
        print(json.dumps(row), flush=True)

    cut = 10 if "--tiny" in argv else 0
    for m, size in SHAPES:
        m, size = ((m - 1) >> cut) + 1, ((size - 1) >> cut) + 1
        # a sixth of the positions set where a chunk is compacted to its
        # tokens; as many as fit where sorted rows are compacted to groups
        share = 1 / 6 if m >= 4 * size - 4 else 0.9 * size / m
        mask_np = rng.random(m) < share
        want = np.full(size, m - 1, np.int64)
        hits = np.flatnonzero(mask_np)[:size]
        want[:len(hits)] = hits
        mask = jnp.asarray(mask_np)
        for name, form in FORMS.items():
            fn = jax.jit(form, static_argnums=(1, 2))
            with enable_x64(True):
                out, first_s, ms = timed(fn, (mask, size, m - 1),
                                         3 if m >= 1 << 22 else 20)
            emit(what="compact", form=name, m=m, size=size, ms=ms,
                 first_call_s=first_s, dtype=str(out.dtype),
                 equal=bool((np.asarray(out) == want).all()))

    if "--kernel" in argv:
        chunk = text(1 << (24 - cut), rng)
        for n, u_cap in ((1 << 24, 1 << 17), (1 << 20, 1 << 16)):
            n, u_cap = n >> cut, max(u_cap >> cut, 4096)
            fn = jax.jit(tokenize_group_core,
                         static_argnames=("max_word_len", "u_cap",
                                          "t_cap_frac"))
            with enable_x64(True):
                out, first_s, ms = timed(
                    lambda c: fn(c, max_word_len=16, u_cap=u_cap),
                    (jnp.asarray(chunk[:n]),), 3)
            emit(what="tokenize_group_core", n=n, u_cap=u_cap, ms=ms,
                 first_call_s=first_s, n_unique=int(out[4]),
                 token_overflow=bool(out[7]), ms_per_MiB=ms * (1 << 20) / n)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/compact_micro.jsonl", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
