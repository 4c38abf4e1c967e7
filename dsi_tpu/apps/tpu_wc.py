"""tpu_wc: word count with an on-device map-side combiner.

This is the plugin BASELINE.json's north star calls ``mrapps/tpuwc.go``: the
same job as ``wc`` (reference ``mrapps/wc.go:21-44``) but the map task's
tokenize/bucket hot loop (``mr/worker.go:69-78``) runs as the fused TPU
kernel in ``dsi_tpu/ops/wordcount.py`` via the ``--backend=tpu`` worker flag.

Map emits one record per *unique* word per split, valued with its in-split
count (a combiner), so Reduce sums counts instead of counting occurrences.
The merged ``mr-out-*`` output is byte-identical to ``wc``'s — only the
intermediate record multiplicity differs, which the differential harness
deliberately ignores (it compares final output, test-mr.sh:52-53).

The host ``Map`` below is the exact fallback the TPU runner uses for
non-ASCII splits, so correctness never depends on the kernel.
"""

from __future__ import annotations

from collections import Counter
from typing import List, Optional

from dsi_tpu.apps.wc import tokenize
from dsi_tpu.mr.types import KeyValue
from dsi_tpu.obs import span as _span

#: The C++ job kernels (native/wcjob.cpp via backends/native.py) implement
#: exactly this app's combiner semantics — Map emits per-unique counts,
#: Reduce sums them.
native_kind = "wc_combine"


def Map(filename: str, contents: str) -> List[KeyValue]:
    counts = Counter(tokenize(contents))
    return [KeyValue(w, str(c)) for w, c in sorted(counts.items())]


def Reduce(key: str, values: List[str]) -> str:
    return str(sum(int(v) for v in values))


def split_unicode_runs(raw: bytes):
    """Partition a split for block-level Unicode fallback (one stray
    non-ASCII byte used to forfeit the device for the WHOLE split).

    Returns ``None`` when the split is too non-ASCII to be worth
    splitting, else ``(clean_bytes, dirty_pieces)`` where ``clean_bytes``
    is the split with every dirty letter-run blanked to spaces (device
    counts it exactly) and ``dirty_pieces`` are the blanked runs' bytes
    (host tokenizes them; counts add).

    Exactness: a "run" is a maximal stretch of ASCII letters and/or
    bytes >= 0x80.  In UTF-8 every byte of a multi-byte code point is
    >= 0x80 and every ASCII byte is a standalone code point, so a
    Unicode-letter token can never cross an ASCII non-letter byte — runs
    are token-closed, and decoding a dirty run in isolation (same
    ``errors="replace"`` policy as the host fallback) yields exactly the
    tokens it yields in context.  Digits/underscores are non-letters in
    both views (``wc.go:23`` splits on them), so they bound runs too.
    """
    import numpy as np

    arr = np.frombuffer(raw, np.uint8)
    high = arr >= 128
    if not high.any():
        return raw, []
    letterish = (((arr >= 65) & (arr <= 90))
                 | ((arr >= 97) & (arr <= 122)) | high)
    m = letterish.astype(np.int8)
    starts = np.flatnonzero(np.diff(np.concatenate(
        (np.zeros(1, np.int8), m))) == 1)
    ends = np.flatnonzero(np.diff(np.concatenate(
        (m, np.zeros(1, np.int8)))) == -1) + 1
    ch = np.concatenate(([0], np.cumsum(high, dtype=np.int64)))
    dirty = np.flatnonzero(ch[ends] - ch[starts] > 0)
    dirty_bytes = int((ends[dirty] - starts[dirty]).sum())
    if dirty_bytes * 4 > len(raw):
        return None  # mostly non-ASCII: the whole-split host path wins
    clean = arr.copy()
    pieces = []
    for i in dirty.tolist():
        s, e = int(starts[i]), int(ends[i])
        pieces.append(raw[s:e])
        clean[s:e] = 32  # spaces: non-letter, creates no tokens
    return clean.tobytes(), pieces


def tpu_map(filename: str, raw: bytes) -> Optional[List[KeyValue]]:
    """Device map: fused tokenize/group/count; None -> host fallback.

    Non-ASCII inputs are split block-level: dirty letter-runs go to the
    host tokenizer, everything else stays on device — one stray
    smart-quote costs the affected runs, not the split."""
    from dsi_tpu.ops.wordcount import count_words_host_result

    with _span("decode", lane="host", bytes=len(raw)):  # the non-ASCII scan
        parts = split_unicode_runs(raw)
    if parts is None:
        return None
    clean, dirty_pieces = parts
    res = count_words_host_result(clean)
    if res is None:
        return None
    with _span("decode", lane="host", records=len(res)):
        counts = Counter()
        for w, (c, _) in res.items():
            counts[w] = c
        if dirty_pieces:
            counts.update(tokenize(
                b" ".join(dirty_pieces).decode("utf-8", errors="replace")))
        return [KeyValue(w, str(c)) for w, c in sorted(counts.items())]
