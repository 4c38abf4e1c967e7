"""Least bytes of the sort chain's two device programs.

Both are integer programs that move records: no formulation needs
floating-point work, so the bound that applies is memory, as for the
other kernels here (``roofline.py``).  The counts are of the work, not of
what implements it:

* An ingest step has to read its uploaded chunk once and write it once
  into the store that stays on the device, plus ``lane_bytes`` a record:
  the key as three 32-bit lanes and the record's partition (16 B).
* The ordering has to read every resident record and its ``lane_bytes``
  once and write every record once, in its place.  A sort that moves the
  lanes in several passes and a gather that reads padded rows move more;
  that surplus is what the share exposes.
"""

from __future__ import annotations


def ingest_bytes(shapes: dict) -> float:
    """Least bytes of one step over a chunk of ``input_bytes`` that holds
    ``chunk_records`` records."""
    return float(2 * shapes["input_bytes"]
                 + shapes["chunk_records"] * shapes["lane_bytes"])


def order_bytes(shapes: dict) -> float:
    """Least bytes of ordering ``records`` records of ``record_bytes``."""
    return float(shapes["records"] * (2 * shapes["record_bytes"]
                                      + shapes["lane_bytes"]))
