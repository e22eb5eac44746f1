"""Bytes each fused device read must move, from the request shape and the
view's published sizes.

Whatever implements the call, a query reads the same work: its key, the
``ceil(log2 G)`` anchors a binary search probes, one group's ``D``
selectors and ``D`` candidate keys to place the start, and then, for each
of the ``W`` slots of its window, the slot's key, value and liveness words
(tombstone byte and TTL expiry word). The count is of useful queries
only; the padding the program adds to a batch is not work a user asked
for. Both reads need a handful of integer compares per byte, so they are
bound by memory bandwidth and no FLOP count is kept.
"""
from __future__ import annotations

import math

U32 = 4


def query_bytes(q: int, g: int, d: int, kw: int) -> int:
    """Locating ``q`` starts in a view of ``g`` anchor groups."""
    probes = max(1, math.ceil(math.log2(max(2, g))))
    return q * (kw * U32 + probes * kw * U32 + d * (1 + kw * U32))


def window_bytes(q: int, width: int, kw: int, vw: int) -> int:
    """Reading ``width`` slots per query: key, value, tombstone, expiry."""
    return q * width * (kw * U32 + vw * U32 + 1 + U32)


def live_read_bytes(q: int, width: int, g: int, d: int, kw: int,
                    vw: int) -> int:
    """One fused read of ``q`` queries: ``scan_live`` at its window width,
    ``get_live`` at width 1."""
    return query_bytes(q, g, d, kw) + window_bytes(q, width, kw, vw)
