"""tpu_grep: distributed grep with the line filter on device.

Same job and output as ``grep`` (the working realization of the reference's
``mrapps/dgrep.go`` intent — see apps/grep.py): Map emits ``{line, ""}`` per
matching line, Reduce counts occurrences.  Four device tiers: a plain
ASCII literal ``DSI_GREP_PATTERN`` runs as the shifted-compare kernel
(``ops/grepk.py``); fixed-length class patterns (``[Tt]he``, ``w.rd``,
``^\\d\\d`` …) run as the range-compare kernel (``ops/regexk.py``);
top-level alternations of those (``the|and``, ``[Cc]at|[Dd]og``) run one
kernel pass per branch with the matched line ends OR-ed (``ops/altk.py``);
variable-length patterns (``* + ?``, mixed alternation: ``ab*c``,
``[0-9]+``, ``colou?r|gr[ae]y$``) run as a log-depth NFA transition-
matrix scan (``ops/nfak.py``); anything wider (groups, backrefs,
nullable patterns) falls back to the host Map.  Every tier returns the
END positions of the matching lines as packed bits
(``grepk.line_flags_from_match``) and the host takes those lines by
offset (``grepk.lines_from_hits``).
"""

from __future__ import annotations

import os
from typing import List, Optional

from dsi_tpu.apps.grep import Map, Reduce  # noqa: F401  (host fallback)
from dsi_tpu.mr.types import KeyValue
from dsi_tpu.obs import span as _span

#: C++ task bodies (native/wcjob.cpp via backends/native.py, literal
#: patterns only — regex declines to the host re path).
native_kind = "grep_count"


def tpu_map(filename: str, raw: bytes) -> Optional[List[KeyValue]]:
    from dsi_tpu.ops.altk import altgrep_host_result
    from dsi_tpu.ops.grepk import grep_host_result
    from dsi_tpu.ops.nfak import nfagrep_host_result
    from dsi_tpu.ops.regexk import classgrep_host_result

    pattern = os.environ.get("DSI_GREP_PATTERN", r"(?!x)x")
    lines = grep_host_result(raw, pattern)
    if lines is None:
        lines = classgrep_host_result(raw, pattern)
    if lines is None:
        lines = altgrep_host_result(raw, pattern)
    if lines is None:
        lines = nfagrep_host_result(raw, pattern)
    if lines is None:
        return None
    with _span("decode", lane="host", records=len(lines)):
        return [KeyValue(line, "") for line in lines]
