"""Documents a wave of the traced job's walk held (``docs`` / ``waves``
of the walk's scope): 1.0 on one chip where the walk gives a document a
wave, the hundreds a chunk holds where it packs whole documents
(``planrun --pack-docs``).  A count, read from the traced job alone as
``index_postings_M`` is, so a run without one (an untraced run, a
rehearsal) has nothing here to read."""

from layer_metrics._index import traced_walk


def read(obs):
    walk = traced_walk(obs) or {}
    if not walk.get("waves") or walk.get("docs") is None:
        return None
    return walk["docs"] / walk["waves"]
