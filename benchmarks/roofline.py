"""What a kernel has to move, from its shapes: the numerator of a roofline
share.  The yardstick lives here, where a PR that changes a kernel cannot
change it.

The word-count programs (the batch map kernel and the stream step) are
integer programs: tokenize, group equal words, count.  No formulation needs
floating-point work, so the bound that applies is memory: whatever the
implementation, it has to read every input byte once and write its result
table once.  That least traffic over the chip's peak HBM bandwidth is the
least time the chip could take; over the measured device time it is the
roofline share.  A sort-based implementation moves many times more (every
pass of the sort reads and writes every row); that surplus is exactly what
the share exposes.
"""

from __future__ import annotations

import importlib


def wordcount_bytes(shapes: dict) -> float:
    """Least bytes for one run of a word-count program over
    ``input_bytes`` of text into a table of ``table_rows`` rows of
    ``row_bytes`` (packed key, length, count, partition), on each of
    ``devices`` devices."""
    return float(shapes["input_bytes"]
                 + shapes["table_rows"] * shapes["row_bytes"])


def lineflag_bytes(shapes: dict) -> float:
    """Least bytes for one run of a line-matching program: read the text,
    write ``flag_bytes`` of line flags (the kernel block says how many the
    program's result holds: one packed bit per byte position today)."""
    return float(shapes["input_bytes"] + shapes["flag_bytes"])


def share(kernel: dict, peaks: dict, seconds_per_run: float) -> float:
    """Roofline share in percent: least time over measured time.  Bound by
    bytes (see the module text).  The kernel's block names its least-bytes
    function as ``"bytes_fn": "<module>:<function>"``, so a new kind of
    arithmetic is a new module beside this one."""
    module, _, fn = kernel["bytes_fn"].partition(":")
    least_bytes = getattr(importlib.import_module(module), fn)
    least_s = least_bytes(kernel["shapes"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds_per_run
