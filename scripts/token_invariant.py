#!/usr/bin/env python
"""Total-token invariant for a GB-scale stream run.

One host pass over the corpus counts ASCII-letter tokens and compares
against the sum of counts in the run's ``mr-out-*`` files — a cheap gross
miscount detector at sizes where full per-word parity is impractical
(per-word parity is covered at test scale by ``wcstream --check`` and the
differential suite).

Usage: python scripts/token_invariant.py <corpus_dir> <workdir>
Prints ``token-count invariant: corpus=N mr-out=M match=True|False``;
exit 0 iff they match.
"""
import glob
import re
import sys


def main() -> int:
    corpus_dir, workdir = sys.argv[1], sys.argv[2]
    tot = 0
    for p in sorted(glob.glob(f"{corpus_dir}/pg-*.txt")):
        with open(p, "rb") as f:
            tot += len(re.findall(rb"[A-Za-z]+", f.read()))
    got = 0
    for p in glob.glob(f"{workdir}/mr-out-*"):
        with open(p) as f:
            for line in f:
                if line.strip():
                    got += int(line.rsplit(" ", 1)[1])
    print(f"token-count invariant: corpus={tot} mr-out={got} "
          f"match={tot == got}", flush=True)
    return 0 if tot == got else 1


if __name__ == "__main__":
    raise SystemExit(main())
