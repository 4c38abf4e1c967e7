"""Milliseconds a wave the packer takes to build one packed wave: its
chunks joined from whole documents and its vector of ordinals (the
``pack`` spans on the walk's producer thread, ``pack_s`` of the walk's
scope, over ``waves``), median over jobs.  A walk that does not pack
reports no ``pack_s`` and has nothing here to read."""

from layer_metrics._index import STAGE, job_median


def read(obs):
    return job_median(obs, lambda p: 1e3 * p["stages"][STAGE]["pack_s"]
                      / p["stages"][STAGE]["waves"])
