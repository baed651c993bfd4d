"""The least time a PX exchange's rows need on the interconnect.

What is counted: ``px.exchange_bytes``, the program's own count of the LIVE
rows each exchange received, summed over the mesh, times the width of the
exchanged relation's row (every column's element, a byte a validity mask,
the row mask's byte).  Padding sent over the wire is not in it, so the
share it gives can only be lowered by padding.

Of the bytes a chip receives, ``(ndev - 1) / ndev`` come from other chips
(an ``all_to_all`` keeps a row's own chip's slice at home; an
``all_gather`` receives its own shard from itself), and each chip receives
over its own links: with rows spread evenly by a hash, ``1 / ndev`` of the
mesh's total arrives at each.  The published peak (``peaks.json``:
``ici_bits_per_s``) is one chip's.
"""

from __future__ import annotations


def crossed_bytes_per_chip(received_bytes: float, ndev: int) -> float:
    """Bytes that reached ONE chip from the others, of ``received_bytes``
    received over the whole mesh."""
    if ndev < 2:
        return 0.0
    return received_bytes * (ndev - 1) / ndev / ndev


def least_seconds(received_bytes: float, ndev: int,
                  ici_bits_per_s: float) -> float:
    return crossed_bytes_per_chip(received_bytes, ndev) \
        / (ici_bits_per_s / 8.0)
