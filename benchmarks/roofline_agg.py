"""Least bytes of the aggregation's step program.

An integer program that reads rows and writes sums: no formulation needs
floating-point work, so the bound that applies is memory, as for the other
kernels here (``roofline.py``).  The count is of the work, not of what
implements it: every step has to read its chunk once, and every row of
every step's table (a key the step saw, with its sum) has to be written
once: ``row_bytes`` = 16 of key, 4 of length, 8 of sum.  Scans over the
chunk's positions, the sorts that compact and group the rows and the
shuffle's copies move more; that surplus is what the share exposes.
"""

from __future__ import annotations


def step_bytes(shapes: dict) -> float:
    """Least bytes of ``steps`` runs over chunks of ``input_bytes`` that
    hand over ``table_rows`` rows in all."""
    return float(shapes["steps"] * shapes["input_bytes"]
                 + shapes["table_rows"] * shapes["row_bytes"])
