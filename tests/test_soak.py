"""Race-soak of the REAL host control plane.

The reference builds every binary with the Go race detector and runs the
full job repeatedly to amplify flakes (``main/test-mr.sh:10,19-22``,
``main/test-mr-many.sh:15-22``).  Python has no tsan, so the analogue is a
high-contention soak: many workers x tiny tasks x a task timeout on the
order of task duration, repeated, with output parity asserted every trial —
the duplicate-execution, requeue-vs-complete, and dial-under-load races all
fire here if they exist.
"""

from __future__ import annotations

import os
import textwrap

import pytest

from dsi_tpu.utils.corpus import ensure_corpus
from tests.harness import merged_output, oracle_output, run_distributed_threads

N_TRIALS = 20

SLOW_WC = textwrap.dedent(
    """
    '''wc with a deterministic per-task stall, sized to straddle the
    requeue timeout so some map tasks get reassigned mid-flight.'''
    import time
    import zlib

    from dsi_tpu.apps.wc import Map as _Map, Reduce

    def Map(filename, contents):
        # Deterministic stall in [0, 0.3) s keyed by the split name: some
        # tasks finish well inside the 0.2 s timeout, some blow through it.
        time.sleep((zlib.crc32(filename.encode()) % 300) / 1000.0)
        return _Map(filename, contents)
    """)


@pytest.mark.slow
def test_many_worker_tiny_task_race_soak(tmp_path):
    corpus_dir = tmp_path / "inputs"
    files = ensure_corpus(str(corpus_dir), n_files=12, file_size=2_000)
    plugin = tmp_path / "slow_wc.py"
    plugin.write_text(SLOW_WC)
    want = oracle_output("wc", files, str(tmp_path))

    for trial in range(N_TRIALS):
        wd = tmp_path / f"trial-{trial}"
        os.makedirs(wd)
        run_distributed_threads(str(plugin), files, str(wd), n_workers=8,
                                n_reduce=6, timeout_s=60.0,
                                task_timeout_s=0.2)
        assert merged_output(str(wd)) == want, f"parity broke in trial {trial}"
