"""Seconds per job ``planrun --chain indexer`` reads its documents whole
before the first stage (the ``read`` span around the plan's construction,
``read_s`` at the top of its ``pipeline_stats``; inside ``plan_tail_s``),
median over jobs.  A program that prints no ``read_s`` has nothing here
to read."""

from layer_metrics._common import median_of, pipeline_stats


def read(obs):
    return median_of([p["read_s"] for p in pipeline_stats(obs)
                      if p.get("read_s") is not None])
