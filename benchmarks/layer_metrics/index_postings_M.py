"""Millions of posting rows the traced job grouped into its index
(``postings_rows``: one row a distinct word a document).  A count, read
from the traced job as ``index_wave_fill`` is."""

from layer_metrics._index import traced_walk


def read(obs):
    rows = (traced_walk(obs) or {}).get("postings_rows")
    return None if rows is None else rows / 1e6
