"""Forms of the word-count stream step's host-merge pull, timed on the chip
(PR 50; PERF.md section 6 holds the table this printed).  Not a test and
not a benchmark cell: run it through the chip tool,

    python scripts/pull_micro.py [--tiny] [--forms a,b,...] [--shapes a,...]
                                 [--steps N]

A window of depth 2, as ``parallel/pipeline.StepPipeline.pump`` drives it:
dispatch step N+1 (upload a 1 MiB chunk a device, call the step program),
then retire step N (read its flags, pull its packed table, merge).  The
step program is a stand-in of the cells' device time a step (an
elementwise loop over the chunk, its trip count fitted on the spot) that
leaves the four result tables at a cell's shape; the merge is a spin of
the cells' host time a step.  The pack and the copy are the program's own
(``shuffle._slice_pack``, ``np.asarray``).  What differs is where the pack
is enqueued and what it slices:

``late``             at retirement, the step's own occupied prefix: the
                     program until PR 50; on an in-order device queue the
                     pack stands behind step N+1's program
``early_capacity``   at dispatch, every row of the table (as ``aot``)
``early_prefix``     at dispatch, the prefix the steps before it reached
``early_prefix_async``  the same, and ``copy_to_host_async`` on the
                     packed tensor at dispatch

One JSON line per (shape, form) on stdout and in
``chiprun_out/pull_micro.jsonl``: ms a step, bytes pulled a step, and the
retiring host's ms a step blocked on the flags (``kernel``), on the packed
tensor (``wait``) and in the copy (``d2h``).  The shapes are the cells':
16,384 rows (``stream-wc-20k``), 65,536 rows holding 40,000 and, so that
the prefix lies under the capacity, 20,000 words (``stream-wc-heaps``),
and, where the machine has four chips, 4 x 262,144 rows holding 50,000
(``stream-wc-mesh4``).  ``--tiny`` divides the rows by 64 and the times by
8 (a rehearsal on the CPU, whose times mean nothing).
"""
import argparse
import collections
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from dsi_tpu.parallel.shuffle import (AXIS, _slice_pack, default_mesh,
                                      occupied_prefix)

KK = 4                 # max_word_len 16
CHUNK = 1 << 20        # bytes a device a step
FORMS = ("late", "early_capacity", "early_prefix", "early_prefix_async")
# (name, devices, rows a device, occupied rows, device ms, host ms a step):
# the device times are the cells' step programs (ledger, PR 49), the host
# times their step_ms less that, less the upload and the copy made here.
SHAPES = (("wc-20k", 1, 1 << 14, 16_000, 5.4, 2.5),
          ("wc-heaps", 1, 1 << 16, 40_000, 10.0, 6.0),
          ("wc-heaps-half", 1, 1 << 16, 20_000, 10.0, 6.0),
          ("wc-mesh4", 4, 1 << 18, 50_000, 21.0, 20.0))


@functools.partial(jax.jit, static_argnames=("rows",))
def _stand_in(chunk, iters, *, rows):
    """The step program's place on the device's queue: ``iters`` passes
    over the chunk, then the four tables of ``rows`` rows a device and
    the flags cut from it."""
    x = chunk.astype(jnp.uint32)
    x = lax.fori_loop(
        0, iters, lambda i, x: (x * jnp.uint32(1664525)
                                + jnp.uint32(1013904223)) ^ (x >> 7), x)
    reps = -(-rows * KK // x.shape[1])
    keys = jnp.tile(x, (1, reps))[:, :rows * KK].reshape(-1, rows, KK)
    lens = (keys[:, :, 0] & 15).astype(jnp.int32)
    cnts = (keys[:, :, 1] & 1023).astype(jnp.int32)
    parts = keys[:, :, 2] % 10
    scal = jnp.sum(keys[:, :5, 0], axis=1, keepdims=True) * jnp.arange(
        5, dtype=jnp.uint32)[None, :]
    return keys, lens, cnts, parts, scal


def _spin(ms):
    end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < end:
        pass


def _fit_iters(chunk, rows, target_ms):
    """The trip count at which the stand-in runs ``target_ms``."""
    def ms(iters, reps=5):
        jax.block_until_ready(_stand_in(chunk, iters, rows=rows))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = _stand_in(chunk, iters, rows=rows)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e3
    lo, hi = ms(16), ms(272)
    per = max((hi - lo) / 256, 1e-6)
    iters = max(1, int(16 + (target_ms - lo) / per))
    return iters, ms(iters)


def run_form(form, sharding, rows, m, iters, host_ms, steps):
    chunk_np = np.random.default_rng(50).integers(
        32, 127, (sharding.mesh.devices.size, CHUNK), dtype=np.uint8)
    prefix = occupied_prefix(m, rows)
    took = collections.Counter()

    def dispatch():
        chunk = jax.device_put(chunk_np, sharding)
        *tables, scal = _stand_in(chunk, iters, rows=rows)
        packed = None
        if form != "late":
            packed = _slice_pack(
                *tables, mp=rows if form == "early_capacity" else prefix)
            if form == "early_prefix_async":
                packed.copy_to_host_async()
        return scal, packed, tables

    def finish(rec):
        scal, packed, tables = rec
        t0 = time.perf_counter()
        np.asarray(scal)
        t1 = time.perf_counter()
        if packed is None:
            packed = _slice_pack(*tables, mp=prefix)
        jax.block_until_ready(packed)
        t2 = time.perf_counter()
        host = np.asarray(packed)
        t3 = time.perf_counter()
        _spin(host_ms)
        took.update(kernel=t1 - t0, wait=t2 - t1, d2h=t3 - t2,
                    bytes=host.nbytes)

    def window(n):
        pending = collections.deque()
        for _ in range(n):
            pending.append(dispatch())
            if len(pending) >= 2:
                finish(pending.popleft())
        while pending:
            finish(pending.popleft())

    window(6)  # every shape compiled, the queue warm
    took.clear()
    t0 = time.perf_counter()
    window(steps)
    wall = time.perf_counter() - t0
    return {"step_ms": round(wall / steps * 1e3, 3),
            "pulled_bytes_a_step": took["bytes"] // steps,
            "kernel_wait_ms": round(took["kernel"] / steps * 1e3, 3),
            "pack_wait_ms": round(took["wait"] / steps * 1e3, 3),
            "d2h_ms": round(took["d2h"] / steps * 1e3, 3),
            "prefix_rows": prefix}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--forms", default=",".join(FORMS))
    p.add_argument("--shapes", default=",".join(s[0] for s in SHAPES))
    p.add_argument("--steps", type=int, default=96)
    args = p.parse_args(argv)
    dev = jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pull_micro.jsonl", "a") as out:
        for name, n_dev, rows, m, dev_ms, host_ms in SHAPES:
            if n_dev > len(dev) or name not in args.shapes.split(","):
                continue
            if args.tiny:
                rows, m, dev_ms, host_ms = (rows // 64, m // 64, dev_ms / 8,
                                            host_ms / 8)
            sharding = NamedSharding(default_mesh(n_dev),
                                     PartitionSpec(AXIS, None))
            iters, fitted_ms = _fit_iters(
                jax.device_put(np.full((n_dev, CHUNK), 97, np.uint8),
                               sharding), rows, dev_ms)
            for form in args.forms.split(","):
                line = {"shape": name, "devices": n_dev, "rows": rows,
                        "occupied": m, "form": form,
                        "device": dev[0].device_kind,
                        "stand_in_ms": round(fitted_ms, 3),
                        "host_spin_ms": host_ms,
                        **run_form(form, sharding, rows, m, iters,
                                   host_ms, args.steps)}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
