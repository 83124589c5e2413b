"""Bytes one chip sends in one step's compressed exchange, counted from
the sizes, whatever implements it.

The flat 1-bit exchange of ``workers`` chips over a padded vector of
``d`` elements (a multiple of ``workers x block``): each chip compresses
its whole vector to a payload of a bit per element and a float32 scale
per ``block`` elements (``costs.payload_bytes``), and in the all-to-all
sends every other chip that chip's chunk, ``(workers - 1) / workers``
of its payload.  Each chip then compresses its averaged chunk again,
and in the all-gather every other chip must get that chunk's payload:
``workers - 1`` copies of a ``d / workers`` payload, the same bytes
again.  A chip keeps its own chunk; on one chip nothing is sent.

Only the bytes a chip sends are counted, not those it receives or
forwards, so the time they take at the chip's interconnect peak
(``peaks.py``'s ``ici_bits_per_s``) is a least time.
"""
from __future__ import annotations

from harness.costs import payload_bytes


def all_to_all_send_bytes(d: int, block: int, workers: int) -> float:
    return payload_bytes(d, block) * (workers - 1) / workers


def all_gather_send_bytes(d: int, block: int, workers: int) -> float:
    return payload_bytes(d / workers, block) * (workers - 1)


def onebit_send_bytes(d: int, block: int, workers: int) -> float:
    """The bytes one chip sends in one flat 1-bit exchange."""
    if d % (workers * block):
        raise ValueError(f"{d} elements do not split into {workers} chunks "
                         f"of whole {block}-element blocks")
    return (all_to_all_send_bytes(d, block, workers)
            + all_gather_send_bytes(d, block, workers))
