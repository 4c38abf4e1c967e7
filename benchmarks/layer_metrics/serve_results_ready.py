"""Percent of a served wave's packed grep steps whose results the device
had finished when the scheduler came to read them: 100 · ``results_ready``
÷ ``packed_steps`` of the ``serve_grep`` scope, the median over the run's
whole waves.  The packed scheduler keeps one step in flight: a call
dispatches a step and starts its results' copies to the host, and the
next call reads them; ``results_ready`` counts the steps whose program
had run by then (asked without blocking, as the stream engines'
``results_ready`` is).  High, the device's time lies under the host's
work between two calls; low, the host still waits for the chip inside
``pull_s``.  A program that reads a step where it dispatched it, as those
before PR 54, counts no ``results_ready`` and has nothing here to read.
Read in the traced run, as ``serve_evictions`` is (a rehearsal's waves
carry the two counts in their ``job`` lines)."""

from layer_metrics._serve import wave_median


def read(obs):
    if not obs.get("traced_job"):
        return None
    return wave_median(obs, lambda w: 100.0
                       * w["stats"]["serve_grep"]["results_ready"]
                       / w["stats"]["serve_grep"]["packed_steps"])
