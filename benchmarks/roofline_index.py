"""Least bytes of the indexer's wave program, summed over a job's waves.

The wave program is an integer program (tokenize, group equal words, route
the rows, sort them): no formulation needs floating-point work, so the
bound that applies is memory, as for the other kernels here
(``roofline.py``).  Whatever implements a wave has to read the wave's
padded chunk once and write its two result tables once: the posting rows
and the document-frequency rows, ``table_rows`` rows each, because the
program's contract is fixed-shape tables at the capacity rung the job
settles on.

A job's waves come in several chunk sizes (a wave is padded to the power
of two of its own longest document), so the bytes are summed over the
sizes the program's ``waves_by_size`` counter reports and not taken from
one ``input_bytes``: the same work whatever implements the wave.  Replays
and the first waves at a narrower rung add device time and no bytes here,
which can only lower the share.
"""

from __future__ import annotations


def wave_bytes(shapes: dict, waves_by_size: dict) -> float:
    """Least bytes of the waves ``{chunk bytes: waves}`` on ``devices``
    devices: per wave and device the chunk read once and ``table_rows``
    rows of ``row_bytes`` (posting rows) and of ``df_row_bytes``
    (document-frequency rows) written once."""
    tables = shapes["table_rows"] * (shapes["row_bytes"]
                                     + shapes["df_row_bytes"])
    return float(shapes.get("devices", 1) * sum(
        int(n) * (int(size) + tables) for size, n in waves_by_size.items()))
