"""Seconds per job decoding the merged table's spellings and building the
result dict (``decode`` span inside ``finalize``, ``finalize_decode_s``)."""

from layer_metrics._tasks import phase_s


def read(obs):
    return phase_s(obs, "finalize_decode_s")
