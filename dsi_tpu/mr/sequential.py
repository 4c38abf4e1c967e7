"""Sequential oracle: the semantic definition of correctness.

Reference: ``main/mrsequential.go:25-87`` — read every input file, run the app
Map over each, concatenate, ONE global sort by key (no partitioning,
mrsequential.go:53-59), group runs of equal keys, run Reduce, write every line
to a single ``mr-out-0`` in ``"%v %v\n"`` format (mrsequential.go:61-86).

The distributed system's merged, sorted output must byte-compare equal to this
(test-mr.sh:30-31,52-53) — that differential check is this repo's primary
correctness test.
"""

from __future__ import annotations

import os
from typing import List, Sequence

from dsi_tpu.mr.types import KeyValue
from dsi_tpu.mr.worker import MapFn, ReduceFn, group_and_reduce
from dsi_tpu.utils.atomicio import atomic_write


def run_sequential(mapf: MapFn, reducef: ReduceFn, files: Sequence[str],
                   out_path: str = "mr-out-0") -> str:
    intermediate: List[KeyValue] = []
    for filename in files:  # mrsequential.go:39-51
        with open(filename, "rb") as f:
            contents = f.read().decode("utf-8", errors="replace")
        intermediate.extend(mapf(filename, contents))
    with atomic_write(out_path) as out:  # one global sort + group (:59-86)
        group_and_reduce(intermediate, reducef, out)
    return os.path.abspath(out_path)
