"""Least work of the sort chain's exchange step: what one device of a mesh
has to do with one chunk, whatever implements it.

The step is an integer program that moves records, as the one-device
ingest step is (``roofline_sort.py``), and two of the chip's peaks bound
it:

* **memory**: the chunk has to be read once, and what lands on the device
  (a chunk's worth, where the keys are spread evenly) written once into
  the store that stays there, plus ``lane_bytes`` a record: the key as
  three 32-bit lanes and the record's partition (16 B);
* **interconnect**: of a chunk read on one of ``devices`` devices,
  ``(devices - 1) / devices`` belongs to another and has to leave the
  chip.

The least time of a step is the larger of the two (at 1 MiB on four v5e
chips: 2.8 us of memory, 3.9 us of interconnect); a scatter into blocks
padded for every destination and an ``all_to_all`` of those blocks move
more, which is what the share exposes.  It can never read over 100 %.
"""

from __future__ import annotations


def exchange_hbm_bytes(shapes: dict) -> float:
    """Least bytes to and from memory of one step on one device, over a
    chunk of ``input_bytes`` that holds ``chunk_records`` records."""
    return float(2 * shapes["input_bytes"]
                 + shapes["chunk_records"] * shapes["lane_bytes"])


def exchange_ici_bytes(shapes: dict) -> float:
    """Least bytes one device has to send to the others in one step."""
    devices = shapes["devices"]
    return float(shapes["input_bytes"]) * (devices - 1) / devices


def exchange_least_s(shapes: dict, peaks: dict) -> float:
    """Least seconds of one step: the larger of its memory time and its
    interconnect time at the chip's published peaks."""
    return max(exchange_hbm_bytes(shapes) / peaks["hbm_bytes_per_s"],
               exchange_ici_bytes(shapes) / (peaks["ici_bits_per_s"] / 8.0))
