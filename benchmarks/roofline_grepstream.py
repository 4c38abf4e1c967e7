"""Least bytes of the grep stream's step program, from its shapes.

The step (literal match, per-line occurrence counts, histogram, top-k
candidates) is an integer program: compares, a prefix sum, two segment sums
and a sort of line slots.  No formulation needs floating-point work, so the
bound that applies is memory: whatever the implementation, it has to read
every input byte once and write its small result once.  Everything else it
moves (match flags, line ids, the sorted line slots) is the implementation's
surplus, which the share exposes.
"""

from __future__ import annotations


def linestats_bytes(shapes: dict) -> float:
    """Least bytes for one run of the line-statistics program on one
    device: ``input_bytes`` of text in, ``result_bytes`` of histogram row,
    candidate rows and scalars out."""
    return float(shapes["input_bytes"] + shapes["result_bytes"])
