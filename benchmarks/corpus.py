"""Seeded synthetic text, vectorised: the benchmark's one traffic generator.

The distribution is the program's own ``utils/corpus.generate_file``
(Gutenberg-like ASCII: words of 2..12 random letters, every tenth one
Capitalised, rank weights ``1/(r + offset)``, separators 80 % space, 12 %
punctuation + space, 8 % newline), rebuilt here with array operations so
that 134 MB take seconds and not a Python loop per word.  The bytes are
NOT those of the program's generator for the same seed; only the
distribution is the same.  ASCII only, no word over 12 letters, so the
device kernels never need their host path on this text.

Everything a traffic mix may vary is a parameter read from its data file
(see ``benchmarks/README.md``): number of files, bytes per file,
vocabulary per file, the rank offset and exponent, the separator shares.

Two parameters pin counts that the program turns into array shapes, so that
a run with a new seed needs no program the checkout has not compiled:
``exact_vocabulary`` (every file holds exactly ``vocab_per_file`` distinct
words: the vocabulary has no repeats, and the file opens with one pass over
it) and ``newlines_per_file`` (every file holds exactly that many newlines
and ends with one).  Off unless a configuration or a mix sets them.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

_PUNCT = np.frombuffer(b".,;:!?", dtype=np.uint8)
_MAX_WORD = 12

#: Every key a ``corpus`` block may carry, with the value used when a
#: configuration and a traffic mix both leave it out.
DEFAULTS = {"files": 4, "file_bytes": (16 << 20) - 64,
            "vocab_per_file": 20_000, "rank_offset": 2.7,
            "rank_exponent": 1.0, "space_share": 0.80,
            "punct_share": 0.12, "capitalised_every": 10,
            "exact_vocabulary": 0, "newlines_per_file": 0}


def effective(config_corpus: dict, traffic_corpus: dict) -> dict:
    """The corpus parameters of one cell: defaults, then the
    configuration's ``corpus`` block, then the traffic mix's."""
    out = dict(DEFAULTS)
    for block in (config_corpus or {}), (traffic_corpus or {}):
        unknown = sorted(set(block) - set(DEFAULTS))
        if unknown:
            raise ValueError(f"unknown corpus parameter(s) {unknown}; "
                             f"known: {sorted(DEFAULTS)}")
        out.update(block)
    return out


def params_key(params: dict) -> str:
    """A short stable name for one set of corpus parameters."""
    blob = json.dumps(params, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


def _vocabulary(rng: np.random.Generator, size: int, cap_every: int,
                distinct: bool = False):
    """``(matrix, lengths)``: one row of ``_MAX_WORD + 2`` bytes per word
    (the two spare columns take the separator), zero padded.  With
    ``distinct`` no word repeats: words are drawn until ``size`` different
    ones have come, and kept in the order they came."""
    lengths = rng.integers(2, _MAX_WORD + 1, size=size).astype(np.int64)
    mat = rng.integers(ord("a"), ord("z") + 1,
                       size=(size, _MAX_WORD + 2), dtype=np.uint8)
    while distinct:
        cols = np.arange(_MAX_WORD + 2)[None, :]
        words = np.where(cols < lengths[:, None], mat, 0)
        _, first = np.unique(words, axis=0, return_index=True)
        first.sort()
        mat, lengths = mat[first], lengths[first]
        if len(first) >= size:
            mat, lengths = mat[:size].copy(), lengths[:size].copy()
            break
        more = size - len(first) + 64
        lengths = np.concatenate([lengths, rng.integers(
            2, _MAX_WORD + 1, size=more).astype(np.int64)])
        mat = np.concatenate([mat, rng.integers(
            ord("a"), ord("z") + 1, size=(more, _MAX_WORD + 2),
            dtype=np.uint8)])
    if cap_every:
        mat[::cap_every, 0] -= 32  # Capitalised
    return mat, lengths


def generate_bytes(size_bytes: int, seed: int, params: dict) -> bytes:
    """One file's text, exactly ``size_bytes`` long."""
    rng = np.random.default_rng(seed)
    v = int(params["vocab_per_file"])
    exact = bool(params.get("exact_vocabulary"))
    mat, lengths = _vocabulary(rng, v, int(params["capitalised_every"]),
                               distinct=exact)
    weights = 1.0 / (np.arange(v, dtype=np.float64)
                     + float(params["rank_offset"])
                     ) ** float(params["rank_exponent"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    mean_token = float((lengths * np.diff(cdf, prepend=0.0)).sum()) + 1.12
    n_words = int(size_bytes / mean_token * 1.02) + 64
    while True:
        idx = np.searchsorted(cdf, rng.random(n_words), side="right")
        np.minimum(idx, v - 1, out=idx)
        if exact:
            idx[:v] = rng.permutation(v)  # every word at least once
        wl = lengths[idx]
        kind = rng.random(n_words)
        space = float(params["space_share"])
        punct = space + float(params["punct_share"])
        rows = mat[idx]                                # (n_words, 14)
        ar = np.arange(n_words)
        first = np.full(n_words, ord(" "), dtype=np.uint8)
        is_punct = (kind >= space) & (kind < punct)
        first[is_punct] = _PUNCT[(kind[is_punct] * 1000).astype(np.int64)
                                 % len(_PUNCT)]
        first[kind >= punct] = ord("\n")
        rows[ar, wl] = first
        rows[ar[is_punct], wl[is_punct] + 1] = ord(" ")
        tok_len = wl + 1 + is_punct
        keep = np.arange(_MAX_WORD + 2)[None, :] < tok_len[:, None]
        blob = rows[keep]
        if blob.size >= size_bytes:
            blob = blob[:size_bytes].copy()
            newlines = int(params.get("newlines_per_file") or 0)
            if exact or newlines:
                _end_on_a_newline(blob)
            if newlines:
                _pin_newlines(blob, newlines, rng)
            return blob.tobytes()
        n_words = int(n_words * 1.1) + 64  # a rare short draw: more words


def _end_on_a_newline(blob: np.ndarray) -> None:
    """Blank the word the cut at the file's end left unfinished (it would
    be one more distinct word) and end the file with a newline."""
    tail = blob[-(_MAX_WORD + 2):]
    is_letter = ((tail | 32) >= ord("a")) & ((tail | 32) <= ord("z"))
    stops = np.flatnonzero(~is_letter)
    tail[(stops[-1] + 1 if len(stops) else 0):] = ord(" ")
    blob[-1] = ord("\n")


def _pin_newlines(blob: np.ndarray, want: int, rng) -> None:
    """Exactly ``want`` newlines: turn surplus ones into spaces, or spaces
    into the missing ones, at positions drawn from ``rng``.  The last byte
    stays a newline; no word changes."""
    have = np.flatnonzero(blob[:-1] == ord("\n"))
    extra = len(have) + 1 - want
    if extra > 0:
        if extra > len(have):
            raise ValueError(f"newlines_per_file {want} cannot be met")
        blob[rng.choice(have, size=extra, replace=False)] = ord(" ")
    elif extra < 0:
        spaces = np.flatnonzero(blob[:-1] == ord(" "))
        if -extra > len(spaces):
            raise ValueError(f"newlines_per_file {want} cannot be met")
        blob[rng.choice(spaces, size=-extra, replace=False)] = ord("\n")


def ensure(cache_root: str, params: dict, seed: int) -> Dict[str, object]:
    """The corpus of ``params`` and ``seed`` under ``cache_root``, made if
    it is not there.  Returns ``{"dir", "files", "generated"}``.  Other
    seeds' corpora are removed first: the cache holds one seed at a time,
    so a checkout never grows past one set of corpora."""
    os.makedirs(cache_root, exist_ok=True)
    tail = f"-s{seed}"
    for name in os.listdir(cache_root):
        if name.startswith("corpus-") and not name.endswith(tail):
            shutil.rmtree(os.path.join(cache_root, name), ignore_errors=True)
    directory = os.path.join(cache_root,
                             f"corpus-{params_key(params)}{tail}")
    n, size = int(params["files"]), int(params["file_bytes"])
    files: List[str] = [os.path.join(directory, f"pg-{i:02d}.txt")
                        for i in range(n)]
    done = os.path.join(directory, "DONE")
    if os.path.exists(done):
        return {"dir": directory, "files": files, "generated": False}
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    def make(i: int) -> None:
        with open(files[i], "wb") as f:
            f.write(generate_bytes(size, seed * 1000 + i, params))

    # Array operations release the interpreter lock: a few threads cut the
    # set-up of a run with a new seed to a third.
    with ThreadPoolExecutor(max_workers=min(8, n)) as pool:
        list(pool.map(make, range(n)))
    with open(done, "w") as f:
        json.dump({"params": params, "seed": seed}, f)
    return {"dir": directory, "files": files, "generated": True}
