"""Small integer-math helpers (reference: core utils/MoreMath.java:5-18)."""

from __future__ import annotations


def log2(x: int) -> int:
    """Floor of log base 2 of a positive int; raises on x <= 0 like the
    reference."""
    if x <= 0:
        raise ValueError(f"x={x}")
    return x.bit_length() - 1


def round_pow2(x: int) -> int:
    """n rounded UP to the next power of two; n itself if already a power of
    two (reference MoreMath.roundPow2: highestOneBit, << 1 if not exact)."""
    if x <= 0:
        raise ValueError(f"x={x}")
    res = 1 << (x.bit_length() - 1)
    if res != x:
        res <<= 1
    return res
