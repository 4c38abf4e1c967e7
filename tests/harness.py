"""Shared helpers for the differential end-to-end tests.

This is the Python form of ``main/test-mr.sh``'s core loop: fresh sandbox,
oracle run, 1 coordinator + N workers, merge ``sort mr-out* | grep .`` and
byte-compare with the oracle output (test-mr.sh:13-53).
"""

from __future__ import annotations

import contextlib
import glob
import os
import threading
import time
import types
from typing import List

from dsi_tpu.config import JobConfig
from dsi_tpu.mr.coordinator import make_coordinator
from dsi_tpu.mr.plugin import load_plugin
from dsi_tpu.mr.sequential import run_sequential
from dsi_tpu.mr.worker import worker_loop


def merged_output(workdir: str) -> List[str]:
    """sort mr-out* | grep .  (test-mr.sh:52 — empty lines dropped so
    per-partition boundaries don't matter)."""
    lines: List[str] = []
    for p in sorted(glob.glob(os.path.join(workdir, "mr-out-*"))):
        with open(p, encoding="utf-8") as f:
            lines.extend(l for l in f if l.strip())
    return sorted(lines)


def oracle_output(app: str, files, workdir: str) -> List[str]:
    mapf, reducef = load_plugin(app)
    out = os.path.join(workdir, "mr-correct.txt")
    run_sequential(mapf, reducef, files, out)
    with open(out, encoding="utf-8") as f:
        return sorted(l for l in f if l.strip())


def run_distributed_threads(app: str, files, workdir: str, n_workers: int = 3,
                            n_reduce: int = 10, timeout_s: float = 60.0,
                            task_timeout_s: float = 10.0) -> None:
    """In-process distributed run: coordinator + worker threads sharing cfg."""
    cfg = JobConfig(n_reduce=n_reduce, workdir=workdir,
                    task_timeout_s=task_timeout_s,
                    socket_path=os.path.join(workdir, "mr.sock"),
                    wait_sleep_s=0.05)
    mapf, reducef = load_plugin(app)
    c = make_coordinator(files, n_reduce, cfg)
    try:
        workers = [threading.Thread(target=worker_loop, args=(mapf, reducef, cfg),
                                    daemon=True)
                   for _ in range(n_workers)]
        for w in workers:
            w.start()
        deadline = time.time() + timeout_s
        while not c.done():
            if time.time() > deadline:
                raise TimeoutError("job did not finish in time")
            time.sleep(0.05)
        for w in workers:
            w.join(timeout=10.0)
    finally:
        c.close()


@contextlib.contextmanager
def net_job_split_roles(app: str, files, workdir: str, n_reduce: int,
                        bind: str = ""):
    """A net-data-plane job in which every shuffle fetch is REMOTE by
    construction: one partition server's spool holds every map's output
    (the map branch of ``worker_loop``, driven here), and one
    reduce-only ``worker_loop`` (``take_maps=False``) behind a second
    server runs every reduce, so no fetch can take the local-read short
    cut.  A CLI fleet cannot promise that: the worker that wins all the
    maps holds all the data, and locality placement then hands it the
    reduces too.

    Yields, with both servers still up, a namespace of ``stats`` (the
    coordinator's ``net_stats()``), ``lines`` (the outputs, fetched as
    the driver fetches them and merged like :func:`merged_output`),
    ``producer`` and ``consumer`` (the servers)."""
    from dsi_tpu.mr.coordinator import Coordinator
    from dsi_tpu.mr.types import TaskStatus
    from dsi_tpu.mr.worker import intermediate_name, run_map_task
    from dsi_tpu.net import PartitionServer
    from dsi_tpu.net.fetch import fetch_partition

    mapf, reducef = load_plugin(app)
    coord = Coordinator(list(files), n_reduce,
                        JobConfig(n_reduce=n_reduce, workdir=workdir,
                                  socket_path="tcp:127.0.0.1:0",
                                  net_shuffle=True))
    coord.serve()
    spools = [os.path.join(workdir, f"worker-{i}") for i in range(2)]
    producer = PartitionServer(spools[0], bind=bind)
    consumer = PartitionServer(spools[1], bind=bind)
    producer.start()
    consumer.start()
    try:
        for _ in files:  # one map per file; a further request would
            # be handed a reduce
            r = coord.request_task({"WorkerId": "producer",
                                    "Addr": producer.address})
            assert r["TaskStatus"] == int(TaskStatus.MAP), r
            run_map_task(mapf, r["Filename"], r["CMap"], n_reduce,
                         spools[0])
            coord.map_complete({
                "TaskNumber": r["CMap"], "Addr": producer.address,
                "PartSizes": [os.path.getsize(intermediate_name(
                    r["CMap"], p, spools[0])) for p in range(n_reduce)]})
        cfg = JobConfig(n_reduce=n_reduce, workdir=spools[1],
                        socket_path=coord.address(), take_maps=False,
                        net_shuffle=True, wait_sleep_s=0.05)
        reducer = threading.Thread(
            target=worker_loop, args=(mapf, reducef, cfg),
            kwargs={"partsrv": consumer}, daemon=True)
        reducer.start()
        deadline = time.time() + 60.0
        while not coord.done():
            if time.time() > deadline:
                raise TimeoutError("net job did not finish in time")
            time.sleep(0.05)
        lines: List[str] = []
        for _r, (addr, name, _crc) in sorted(
                coord.output_locations().items()):
            raw = fetch_partition(addr, name, timeout=10.0)
            lines.extend(l for l in raw.decode("utf-8").splitlines(True)
                         if l.strip())
        yield types.SimpleNamespace(stats=coord.net_stats(),
                                    lines=sorted(lines),
                                    producer=producer, consumer=consumer)
    finally:
        coord.close()
        producer.close()
        consumer.close()


def word_count_program(program: str, n_dev: int = 1, *, size: int,
                       u_cap: int, **more):
    """``(name, fn, args, static)`` of one of the programs the word-count
    map is in, at 16-byte words and ``t_cap_frac`` 4: ``fn`` over the
    shape structs ``args`` and the static keywords ``static``, and the
    name its compiled executable is kept under (``None``: the bare map
    and ``corpus_kernel`` are named by their callers).  ``program``:
    ``wc``, ``corpus``, ``stream``, ``tfidf``, ``idx``
    (``pack_docs=True`` for the packed wave)."""
    import jax
    import numpy as np

    from dsi_tpu.ops.corpus_wc import corpus_kernel
    from dsi_tpu.ops.wordcount import tokenize_group_core
    from dsi_tpu.parallel.grepstream import _idx_program, pack_docs_cap
    from dsi_tpu.parallel.shuffle import default_mesh
    from dsi_tpu.parallel.streaming import _step_program
    from dsi_tpu.parallel.tfidf import _wave_program

    sds = jax.ShapeDtypeStruct
    if program in ("wc", "corpus"):
        core = tokenize_group_core if program == "wc" else corpus_kernel
        static = dict(max_word_len=16, u_cap=u_cap, t_cap_frac=4, **more)
        return None, core, (sds((size,), np.uint8),), static
    shape = dict(n_dev=n_dev, n_reduce=10, max_word_len=16, u_cap=u_cap,
                 mesh=default_mesh(n_dev), t_cap_frac=4)
    chunks = sds((n_dev, size), np.uint8)
    if program == "stream":
        return (*_step_program(**shape), (chunks,), {})
    if program == "tfidf":
        return (*_wave_program(size=size, **shape),
                (chunks, sds((n_dev,), np.int32)), {})
    ids = (n_dev, pack_docs_cap(size)) if more.get("pack_docs") else (n_dev,)
    return (*_idx_program(size=size, **shape, **more),
            (chunks, sds(ids, np.int32)), {})
