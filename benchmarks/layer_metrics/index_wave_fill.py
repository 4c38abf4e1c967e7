"""Percent of the uploaded wave bytes that were document bytes in the
traced job (``wave_doc_bytes`` / ``wave_chunk_bytes``): how full the
padded waves are.  A count, not a time; every job of a run walks the same
documents, and the traced one is the job whose waves the device trace
shows.  A run without a traced job (an untraced run, a rehearsal) has
nothing here to read."""

from layer_metrics._index import traced_walk


def read(obs):
    walk = traced_walk(obs) or {}
    if not walk.get("wave_chunk_bytes"):
        return None
    return 100.0 * walk["wave_doc_bytes"] / walk["wave_chunk_bytes"]
