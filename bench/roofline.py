"""Bytes and operations that a program must move or do, computed from
its shapes.  The per-layer roofline metrics divide these by the program's
device time in the trace and by the chip's peaks (``peaks.json``)."""

from __future__ import annotations


def lb_stage_bytes(queries: int, block: int, length: int, itemsize: int = 4) -> int:
    """One dense LB stage call of the host driver: it must read the query
    rows and their upper and lower envelopes (``queries`` x ``length``
    each) and the candidate block (``block`` x ``length``), and write one
    bound per (query, candidate) pair."""
    return itemsize * (3 * queries * length + block * length + queries * block)
