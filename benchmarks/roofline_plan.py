"""Least bytes of the two programs only a plan runs, from their shapes.

Both are integer programs over bytes (compares, scans, a sort or a gather
of positions): no formulation needs floating-point work, so the bound that
applies is memory, as for the other kernels here (``roofline.py``).
"""

from __future__ import annotations


def emit_bytes(shapes: dict) -> float:
    """Least bytes for one run of the grep step with the ``emit`` outputs
    on one device: ``input_bytes`` of text in; out, the compacted row of
    matching lines (``emitted_bytes``: a full row, because the program's
    contract is a fixed-shape row that is zero past the kept bytes), the
    ``result_bytes`` of histogram row, candidate rows and scalars, and the
    ``kept_bytes`` of the kept count.  The match flags, scans and sort
    keys it moves besides are the implementation's surplus."""
    return float(shapes["input_bytes"] + shapes["emitted_bytes"]
                 + shapes["result_bytes"] + shapes["kept_bytes"])


def pack_bytes(shapes: dict) -> float:
    """Least bytes for one run of the relay's pack program on one device,
    as the program states its job: out of the accumulation row and the
    appended row (``rows_read`` rows of ``row_bytes``) and the fill
    offset, one fresh row (``rows_written``).  An append in place would
    move only the kept bytes; the program's contract is a whole row, and
    the yardstick holds it to that."""
    return float((shapes["rows_read"] + shapes["rows_written"])
                 * shapes["row_bytes"] + shapes["offset_bytes"])
