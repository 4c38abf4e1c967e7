"""Least bytes of the join chain's device programs.

Integer programs that read rows and write rows: no formulation needs
floating-point work, so the bound that applies is memory, as for the other
kernels here (``roofline.py``).  The counts are of the work, not of what
implements it:

* The probe steps have to read every chunk once and to write, for every
  row inside the date window, its join key's bytes (``key_bytes``, the
  width the key is packed to) and ``group_bytes``: the group key, its
  length and the row's three values (16 + 4 + 12 B).  The 99.8 % of a
  narrow window's rows that do not pass have to be read and nothing more.
* The build has to read the build side's bytes once and to write every
  row's ``row_bytes`` (the key's 25 lanes, its length, its rank: 108 B)
  once into the table.  The ordering's sort passes, its gather of the
  rows, the rows' padding and the hashes move more; that surplus is what
  the share exposes.
"""

from __future__ import annotations


def probe_bytes(shapes: dict) -> float:
    """Least bytes of ``steps`` probe steps over chunks of ``input_bytes``
    of which ``window_rows`` rows in all lie inside the window."""
    return float(shapes["steps"] * shapes["input_bytes"]
                 + shapes["window_rows"] * (shapes["key_bytes"]
                                            + shapes["group_bytes"]))


def build_bytes(shapes: dict) -> float:
    """Least bytes of building a table of ``build_rows`` rows out of
    ``build_bytes`` of input."""
    return float(shapes["build_bytes"]
                 + shapes["build_rows"] * shapes["row_bytes"])
