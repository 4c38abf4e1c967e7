"""H2D upload of a program's host arguments, with its wall time accounted.

``corpus_wc`` routes its piece upload through :func:`put_views`, and
``bench.py`` reports ``stats``' wall time as an ``upload_s`` phase instead
of letting it hide inside ``kernel_s``.
"""
from __future__ import annotations

import time
from typing import Any, List, Sequence

#: Upload telemetry: ``upload_s`` ACCUMULATES across calls (a single
#: logical operation may upload more than once, e.g. corpus_wc's
#: token-bound retry rung re-uploads the corpus) until the reader —
#: bench.py's per-rep phase capture — zeroes it.
stats = {"upload_s": 0.0}


def put_views(views: Sequence[Any], device=None) -> List[Any]:
    """Transfer ``views`` (host arrays) to ``device`` (default: JAX's
    default device) in one ``device_put`` and record the wall time in
    ``stats``.  Returns device arrays in input order.

    Blocking before return costs nothing real — a consuming program
    cannot start until all its arguments have landed — and gives callers
    an honest upload phase boundary.
    """
    import jax

    t0 = time.perf_counter()
    out = (jax.device_put(list(views), device) if device is not None
           else jax.device_put(list(views)))
    jax.block_until_ready(out)
    stats["upload_s"] += time.perf_counter() - t0
    return list(out)
