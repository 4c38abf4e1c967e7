"""How fast this host reads a page job's documents: ~12,000 files of
1-16 KiB (the sizes of ``plan-index-pages``), written where the benchmark
writes its own, then ``os.stat`` over them (by path, by name from the
open directory, and that by threads), a serial ``open().read()`` loop,
the same by three ``os`` calls a file (by path, and by name), by
``native.read_files`` and ``native.file_lengths`` (the same calls
outside the interpreter), and
``ioread.ReadAheadDocs`` walked in document order at several pool sizes.
The numbers behind ``ioread.DOC_READ_THREADS`` and the way
``ReadAheadDocs`` names its files; no chip is used, but the host that
matters is the chip's.

    python scripts/docread_micro.py [--docs 12000] [--repeat 3]
"""

import argparse
import json
import math
import os
import random
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from dsi_tpu import native  # noqa: E402
from dsi_tpu.utils import ioread  # noqa: E402


def timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return round(time.perf_counter() - t0, 4)


def serial_read(paths):
    for path in paths:
        with open(path, "rb") as f:
            f.read()


def os_read(names, dir_fd=None):
    for name in names:
        fd = os.open(name, os.O_RDONLY, dir_fd=dir_fd)
        try:
            os.read(fd, 1 << 15)
        finally:
            os.close(fd)


def by_threads(fn, items, threads: int) -> None:
    """``fn`` over ``items``, a contiguous run a thread."""
    run = -(-len(items) // threads)
    pool = [threading.Thread(target=fn, args=(items[i:i + run],))
            for i in range(0, len(items), run)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()


def walk(paths, threads: int) -> dict:
    ioread.DOC_READ_THREADS = threads
    t0 = time.perf_counter()
    docs = ioread.ReadAheadDocs(paths)
    lengths_s = time.perf_counter() - t0
    try:
        docs.read_ahead()
        n = sum(len(d) for d in docs)
    finally:
        docs.close()
    return {"threads": threads, "lengths_s": round(lengths_s, 4),
            "walk_s": round(time.perf_counter() - t0 - lengths_s, 4),
            "bytes": n, **docs.stats}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--docs", type=int, default=12000)
    p.add_argument("--repeat", type=int, default=3)
    args = p.parse_args()
    directory = os.path.join(ROOT, ".bench_cache", "docread")
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    rng = random.Random(42)
    paths = []
    for i in range(args.docs):
        size = int(math.exp(rng.uniform(math.log(1024), math.log(16384))))
        paths.append(os.path.join(directory, f"d{i:05d}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(rng.randbytes(size))
    names = [os.path.basename(q) for q in paths]
    sizes = dict(zip(names, map(os.path.getsize, paths)))
    dfd = os.open(directory, os.O_RDONLY)

    def stat_names(part):
        for name in part:
            os.stat(name, dir_fd=dfd)

    def native_read(part):
        for lo in range(0, len(part), 64):
            run = part[lo:lo + 64]
            _, bad, _ = native.read_files(
                [os.fsencode(name) for name in run], [dfd] * len(run),
                [sizes[name] for name in run])
            assert bad == -1

    def native_stat(part):
        native.file_lengths([os.fsencode(name) for name in part],
                            [dfd] * len(part))

    try:
        for _ in range(args.repeat):
            row = {"docs": len(paths), "cores": os.cpu_count(),
                   "stat_s": timed(lambda: [os.stat(q) for q in paths]),
                   "stat_name_s": timed(lambda: stat_names(names)),
                   "serial_read_s": timed(lambda: serial_read(paths)),
                   "os_read_s": timed(lambda: os_read(paths)),
                   "os_read_name_s": timed(lambda: os_read(names, dfd))}
            for threads in (1, 2, 4, 8) if native.available() else ():
                row[f"native_read_name_{threads}_s"] = timed(
                    lambda: by_threads(native_read, names, threads))
                row[f"native_stat_name_{threads}_s"] = timed(
                    lambda: by_threads(native_stat, names, threads))
            for threads in (2, 4, 8):
                row[f"stat_name_{threads}_s"] = timed(
                    lambda: by_threads(stat_names, names, threads))
                row[f"os_read_name_{threads}_s"] = timed(
                    lambda: by_threads(lambda part: os_read(part, dfd),
                                       names, threads))
            print(json.dumps(row), flush=True)
            for threads in (1, 2, 4, 8, 16, 32):
                print(json.dumps(walk(paths, threads)), flush=True)
    finally:
        os.close(dfd)
        shutil.rmtree(directory, ignore_errors=True)


if __name__ == "__main__":
    main()
