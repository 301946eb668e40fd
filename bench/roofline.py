"""Bytes and operations a kernel needs, computed from its shapes.

``dbl_query`` (``repro.kernels.dbl_query``) reads, for each query lane,
eight label word-rows (DL out/in of u and v, BL in/out of u and v: k/32
and k'/32 uint32 words each), a stack of int32 lane flags (u == v, and the
edge-count and tombstone freshness of the lane), and writes one int32
verdict.  Its work is a handful of bitwise word operations per word read,
so it is bound by memory traffic, never by arithmetic: its roofline is the
bytes it moves over the chip's HBM bandwidth.
"""
from __future__ import annotations

WORD = 4          # bytes of a uint32 label word or an int32 flag/verdict


def dbl_query_bytes(q: int, *, k: int, k_prime: int, nflags: int = 3,
                    il_dim: int = 0) -> int:
    """HBM bytes one ``dbl_query`` grid-kernel call moves for ``q`` lanes
    (``q`` already padded to the kernel's ``q_block``)."""
    wd, wb = -(-k // 32), -(-k_prime // 32)
    per_lane = (4 * wd + 4 * wb) * WORD          # eight label word-rows
    per_lane += nflags * WORD                    # the (R, 1, Q) flag stack
    per_lane += 4 * 2 * il_dim * WORD            # interval rank rows, if on
    per_lane += WORD                             # the verdict written back
    return q * per_lane


def roofline_share(bytes_moved: float, kernel_s: float,
                   hbm_bytes_per_s: float) -> float | None:
    """Percent of the bandwidth roofline: the least time the bytes take at
    peak bandwidth, over the time the kernel took.  None without a time."""
    if kernel_s <= 0:
        return None
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / kernel_s
