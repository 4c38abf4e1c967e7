"""Seconds per job the index walk was held by a document its reader
threads had not read yet (the ``read_wait`` spans on the walk's producer
thread, before a wave's ``pack`` span and outside it; ``read_wait_s`` at
the top of ``planrun``'s ``pipeline_stats``, beside ``read_s``), median
over jobs.  Near 0 where the read-ahead keeps ahead of the walk.  A
program that prints no ``read_wait_s`` (one that reads its documents whole
before the first stage) has nothing here to read."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([p["read_wait_s"] for p in pipeline_stats(obs)
                      if p.get("read_wait_s") is not None])
