"""The aggregation map (``ops/fieldsum.py``) and its parts, timed on the
chip (PR 49; PERF.md section 6 holds the table this printed).  Not a test
and not a benchmark cell: run it through the chip tool,

    python scripts/agg_micro.py [--tiny]

One chunk of 1 MiB of ``benchmarks/uservisits.py`` rows (about 8,100 rows
of 129 B), resident on the device; every program runs 20 times and prints
one JSON line on stdout and in ``chiprun_out/agg_micro.jsonl``: the whole
step program at the table rung a job settles on, the map alone, and the
map's parts (the row starts compacted by ``_move_left``, as the map does,
or by ``compact_positions``' sort of the positions; the terminator scan;
the gathers).  ``--tiny`` takes a
chunk of 16 KiB (a rehearsal of the script on the CPU, whose times mean
nothing).
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import uservisits
    from dsi_tpu.ops import fieldsum, wordcount
    from dsi_tpu.parallel import shuffle
    from dsi_tpu.utils.jaxcompat import enable_x64

    n = 1 << 14 if "--tiny" in sys.argv else 1 << 20
    reps = 20
    data = uservisits.rows(n // 100, np.random.default_rng(49))[:n]
    cut = int(np.flatnonzero(data == 10)[-1]) + 1
    host = np.zeros(n, np.uint8)
    host[:cut] = data[:cut]
    chunk = jax.device_put(host)
    rows = int(np.count_nonzero(host == 10))
    spec = fieldsum.FieldSum()
    mesh = shuffle.default_mesh(1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "agg_micro.jsonl"), "a")

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        with enable_x64(True):
            jitted = jax.jit(fn)
            jax.block_until_ready(jitted(*args))
        first = time.perf_counter() - t0
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            with enable_x64(True):
                jax.block_until_ready(jitted(*args))
            times.append(time.perf_counter() - t0)
        line = json.dumps({
            "form": name, "n": n, "rows": rows,
            "device": jax.devices()[0].device_kind,
            "first_s": round(first, 2),
            "min_ms": round(1e3 * min(times), 3),
            "median_ms": round(1e3 * statistics.median(times), 3)})
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def by_sort(is_start, size):
        return wordcount.compact_positions(is_start, size, 0)

    def step(cap, frac):
        def fn(c):
            return shuffle._mapreduce_step_impl(
                c[None], n_dev=1, n_reduce=10, max_word_len=16, u_cap=cap,
                mesh=mesh, t_cap_frac=frac, map=spec)
        return fn

    def rows_only(c):
        return fieldsum.field_rows(c, spec=spec, max_word_len=16,
                                   t_cap_frac=64)

    def starts_only(c):
        end = c == 10
        return fieldsum._row_starts(jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), end[:-1]]) & (c != 0), n // 64 + 1)

    def scan_only(c):
        pos = jnp.arange(n, dtype=jnp.int32)
        return lax.cummin(jnp.where((c == 10) | (c == 124), pos, n),
                          reverse=True)

    def gathers_only(c, idx):
        w = fieldsum._words(c)
        return [w[jnp.minimum(idx + 4 * j, n)] for j in range(15)]

    idx = jax.device_put(np.sort(np.random.default_rng(1).integers(
        0, n, n // 64 + 1)).astype(np.int32))
    # a form is traced through fresh functions: jit keys its cache by the
    # function it wraps
    for name, form in (("sort", by_sort),
                       ("move_left", fieldsum._row_starts)):
        fieldsum._row_starts = form
        timed(f"starts[{name}]", lambda c: starts_only(c), chunk)
        timed(f"field_rows[{name}]", lambda c: rows_only(c), chunk)
        timed(f"step u16384 f64 [{name}]", step(1 << 14, 64), chunk)
    timed("terminator scan", scan_only, chunk)
    timed("15 gathers of t_cap rows", gathers_only, chunk, idx)


if __name__ == "__main__":
    main()
