"""Candidate forms of the sort chain's ordering (``ops/sortk.py``), of the
way down and of the durable commit, timed on the chip (PR 45; PERF.md
section 6 holds the table this printed).  Not a test and not a benchmark
cell: run it through the chip tool,

    python scripts/sort_micro.py [--tiny] [form ...]

The job is 5,368,704 records of 25 uint32 words.  Every form runs in a
child process of its own with a time limit (the parent never touches JAX,
so a compile that does not end costs its limit and nothing else), and
prints one JSON line on stdout and in ``chiprun_out/sort_micro.jsonl``.
``--tiny`` divides the rows by 1,024 (a rehearsal of the script on the
CPU, whose times mean nothing).
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N = 5_368_704
W = 25
FORMS = ("perm", "rows25", "rows32", "cols", "d2h", "h2d", "commit",
         "ingest")
LIMIT_S = 420


def _timed(fn, *args, reps=3):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return out, first, best


def _perm(l0, l1, l2):
    import jax.numpy as jnp

    from dsi_tpu.ops.wordcount import lex_sort

    idx = jnp.arange(l0.shape[0], dtype=jnp.int32)
    return lex_sort((l0, l1, l2), (idx,))[3]


def _ingest_forms(n: int) -> dict:
    """The host's cost of the ingest loop, 513 steps of 1 MiB at depth 2,
    by how a step's two scalars reach the program and what surrounds the
    call: seconds a job's worth of steps, each form twice."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dsi_tpu.device.table import _quiet_unusable_donation
    from dsi_tpu.ops import sortk

    per = max(1, (1 << 20) // 100 * n // N)
    steps = -(-n // per)
    cap = steps * per
    words = per * 25
    rng = np.random.default_rng(1)
    bufs = [rng.integers(0, 1 << 32, words + 2, dtype=np.uint32)
            for _ in range(4)]
    splits = jnp.asarray(np.sort(rng.integers(
        0, 1 << 32, (9, 3), dtype=np.uint32), axis=0))
    def scalar_step(store, lanes, chunk, offset, n_valid, sp):
        return sortk._ingest(store, lanes, chunk, offset, n_valid, sp,
                             chunk_records=per)

    plain = jax.jit(scalar_step, donate_argnums=(0, 1))

    def meta_step(store, lanes, chunk, meta, sp):
        return sortk._ingest(store, lanes, chunk, meta[0], meta[1], sp,
                             chunk_records=per)

    meta_fn = jax.jit(meta_step, donate_argnums=(0, 1))
    tail_fn = sortk.ingest_fn(per)   # the tree's: the header in the chunk

    def loop(form):
        store = jnp.zeros((cap, 25), jnp.uint32)
        lanes = jnp.zeros((3, cap), jnp.uint32)
        jax.block_until_ready((store, lanes))
        last = None
        t0 = time.perf_counter()
        for i in range(steps):
            buf = bufs[i % 4]
            if form == "tail":
                buf[words:] = (i * per, per)
                store, lanes, hist = tail_fn(store, lanes,
                                             jax.device_put(buf), splits)
            elif form == "meta":
                chunk, meta = jax.device_put(
                    (buf, np.array([i * per, per], np.int32)))
                store, lanes, hist = meta_fn(store, lanes, chunk, meta,
                                             splits)
            elif form == "quiet":
                with _quiet_unusable_donation():
                    store, lanes, hist = plain(
                        store, lanes, jax.device_put(buf),
                        np.int32(i * per), np.int32(per), splits)
            else:
                store, lanes, hist = plain(
                    store, lanes, jax.device_put(buf), np.int32(i * per),
                    np.int32(per), splits)
            if form != "nocopy":
                hist.copy_to_host_async()
            if last is not None:
                np.asarray(last)
            last = hist
        np.asarray(last)
        return time.perf_counter() - t0

    out = {"steps": steps}
    for form in ("quiet", "scalars", "nocopy", "meta", "tail"):
        loop(form)
        out[form + "_s"] = [round(loop(form), 4) for _ in range(2)]
    return out


def child(form: str, n: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.default_rng(7)
    out = {"form": form, "rows": n,
           "device": jax.devices()[0].device_kind}
    if form == "commit":
        # ten durable commits of a tenth of the job each: one after the
        # other, or a partition's flush, fsync and rename on a pool of
        # threads while the next partition is written
        import contextlib
        from concurrent.futures import ThreadPoolExecutor

        from dsi_tpu.utils.atomicio import atomic_write

        data = rng.integers(0, 255, n * W * 4 // 10, dtype=np.uint8)
        wd = os.path.join(ROOT, ".bench_cache", "sort_micro")
        os.makedirs(wd, exist_ok=True)

        def commit_all(threads: int) -> float:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=max(threads, 1)) as pool:
                for r in range(10):
                    stack = contextlib.ExitStack()
                    f = stack.enter_context(atomic_write(
                        os.path.join(wd, f"part-{r}"), "wb"))
                    for at in range(0, len(data), 13 << 20):
                        f.write(data[at:at + (13 << 20)])
                    if threads:
                        pool.submit(stack.close)
                    else:
                        stack.close()
            return round(time.perf_counter() - t0, 4)

        out["bytes"] = int(data.nbytes) * 10
        for threads in (0, 1, 2, 4, 10, 0, 4):
            out.setdefault(f"commit_t{threads}_s", []).append(
                commit_all(threads))
        return out
    if form == "ingest":
        return dict(out, **_ingest_forms(n))
    if form == "h2d":
        chunk = rng.integers(0, 1 << 32, 1 << 18, dtype=np.uint32)
        jax.block_until_ready(jax.device_put(chunk))
        t0 = time.perf_counter()
        for _ in range(128):
            d = jax.device_put(chunk)
        jax.block_until_ready(d)
        out["h2d_MBps"] = 128 * chunk.nbytes / 1e6 / (time.perf_counter()
                                                      - t0)
        return out
    keys = [jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
            for _ in range(3)]
    perm_fn = jax.jit(_perm)
    perm, first, best = _timed(perm_fn, *keys)
    out.update(perm_first_s=first, perm_s=best)
    if form == "perm":
        return out
    if form in ("rows25", "rows32"):
        w = W if form == "rows25" else 32
        store = jnp.zeros((n, w), jnp.uint32) + jnp.arange(
            n, dtype=jnp.uint32)[:, None]
        fn = jax.jit(lambda s, p: jnp.take(s, p, axis=0))
    elif form == "rowsflat":
        # flat in, flat out: what the engine would hold and pull
        store = jnp.arange(n * W, dtype=jnp.uint32)
        fn = jax.jit(lambda s, p: jnp.take(
            s.reshape(n, W), p, axis=0).reshape(n * W))
    elif form == "cols":
        store = jnp.zeros((W, n), jnp.uint32) + jnp.arange(
            n, dtype=jnp.uint32)[None, :]
        fn = jax.jit(lambda s, p: jnp.take(s, p, axis=1))
    elif form == "sortcarry":
        store = tuple(jnp.arange(n, dtype=jnp.uint32) + j
                      for j in range(W))

        def fn(s, p):
            # rank of every record, then one pass that carries the words
            rank = lax.sort((p, jnp.arange(n, dtype=jnp.int32)),
                            num_keys=1)[1]
            return lax.sort((rank, *s), num_keys=1)[1:]
        fn = jax.jit(fn)
    elif form == "d2h":
        flat = jnp.arange(n * W, dtype=jnp.uint32)
        jax.block_until_ready(flat)
        t0 = time.perf_counter()
        host = np.asarray(flat)
        out["d2h_whole_MBps"] = host.nbytes / 1e6 / (time.perf_counter()
                                                     - t0)
        block = min(1 << 22, n)
        cut = jax.jit(lambda f, s: lax.dynamic_slice(f, (s,), (block,)))
        jax.block_until_ready(cut(flat, 0))
        t0 = time.perf_counter()
        blocks = [cut(flat, s) for s in range(0, n * W - block, block)]
        for b in blocks:
            b.copy_to_host_async()
        got = sum(np.asarray(b).nbytes for b in blocks)
        out["d2h_blocks_MBps"] = got / 1e6 / (time.perf_counter() - t0)
        rows = jnp.zeros((n, W), jnp.uint32)
        jax.block_until_ready(rows)
        t0 = time.perf_counter()
        host = np.asarray(rows)
        out["d2h_rows_MBps"] = host.nbytes / 1e6 / (time.perf_counter()
                                                    - t0)
        return out
    jax.block_until_ready(store)
    got, first, best = _timed(fn, store, perm)
    out.update(apply_first_s=first, apply_s=best)
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    return out


def main(argv) -> int:
    tiny = "--tiny" in argv
    n = N // 1024 if tiny else N
    forms = [a for a in argv if not a.startswith("--")] or list(FORMS)
    if "--child" in argv:
        print(json.dumps(child(forms[0], n)), flush=True)
        return 0
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    # the children share the permutation's program through the cache
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jaxcache"))
    with open(os.path.join(ROOT, "chiprun_out", "sort_micro.jsonl"),
              "a") as log:
        for form in forms:
            t0 = time.time()
            try:
                done = subprocess.run(
                    [sys.executable, __file__, "--child", form]
                    + (["--tiny"] if tiny else []),
                    capture_output=True, text=True, timeout=LIMIT_S)
                lines = [ln for ln in done.stdout.splitlines()
                         if ln.startswith("{")]
                line = lines[-1] if lines else json.dumps(
                    {"form": form, "rc": done.returncode,
                     "err": done.stderr[-600:]})
            except subprocess.TimeoutExpired:
                line = json.dumps({"form": form, "timeout_s": LIMIT_S})
            rec = dict(json.loads(line), wall_s=round(time.time() - t0, 1))
            print(json.dumps(rec), flush=True)
            log.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
