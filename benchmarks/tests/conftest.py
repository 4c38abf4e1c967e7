"""These tests belong to the benchmark and are not part of tier-1
(``pytest tests/``); run them with ``python -m pytest benchmarks/tests -q``."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:   # the benchmark's modules import each other flat
    sys.path.insert(0, BENCH)
