"""Bitset helpers.

The oracle side uses plain Python ints as bitsets (arbitrary precision, fast
or/and/popcount).  The batched side uses packed uint32 arrays — see
wittgenstein_tpu.ops.bitops for the jnp/pallas twins.

Reference semantics: core utils/BitSetUtils.java:8-13 (`include`).
"""

from __future__ import annotations

import numpy as np


def include(big: int, small: int) -> bool:
    """True iff every bit set in `small` is set in `big`."""
    return (small & ~big) == 0


def cardinality(bits: int) -> int:
    return bits.bit_count()


def to_ids(bits: int) -> list:
    """Ascending indices of the set bits (BitSet.nextSetBit iteration)."""
    res = []
    while bits:
        lsb = bits & -bits
        res.append(lsb.bit_length() - 1)
        bits ^= lsb
    return res


def int_to_packed(bits: int, n_words: int) -> np.ndarray:
    """Python-int bitset -> packed little-endian uint32 words."""
    if bits >> (32 * n_words):
        raise ValueError(f"bitset needs more than {n_words} words")
    out = np.zeros(n_words, dtype=np.uint32)
    for w in range(n_words):
        out[w] = (bits >> (32 * w)) & 0xFFFFFFFF
    return out


def packed_to_int(words: np.ndarray) -> int:
    bits = 0
    for w, v in enumerate(np.asarray(words, dtype=np.uint32).tolist()):
        bits |= int(v) << (32 * w)
    return bits


class JavaBitSet:
    """Mutable bitset with java.util.BitSet semantics: value-based equality
    and hashing, get() beyond length() returns False, or/andNot mutate in
    place.  Used by oracle protocols that rely on BitSet aliasing across
    shared message objects (e.g. P2PHandel's checkSigs2)."""

    __slots__ = ("bits",)

    def __init__(self, bits: int = 0):
        self.bits = bits

    def get(self, i: int) -> bool:
        return (self.bits >> i) & 1 == 1

    def set(self, i: int, value: bool = True) -> None:
        if value:
            self.bits |= 1 << i
        else:
            self.bits &= ~(1 << i)

    def or_(self, other: "JavaBitSet") -> None:
        self.bits |= other.bits

    def and_(self, other: "JavaBitSet") -> None:
        self.bits &= other.bits

    def and_not(self, other: "JavaBitSet") -> None:
        self.bits &= ~other.bits

    def cardinality(self) -> int:
        return self.bits.bit_count()

    def length(self) -> int:
        """Highest set bit + 1 (java.util.BitSet.length)."""
        return self.bits.bit_length()

    def is_empty(self) -> bool:
        return self.bits == 0

    def clone(self) -> "JavaBitSet":
        return JavaBitSet(self.bits)

    def __eq__(self, other):
        return isinstance(other, JavaBitSet) and self.bits == other.bits

    def __hash__(self):
        return hash(self.bits)

    def __repr__(self):
        return "{" + ", ".join(str(i) for i in to_ids(self.bits)) + "}"

    @staticmethod
    def from_string(binary: str) -> "JavaBitSet":
        """Bit i set iff binary[i] == '1' (test helper parity)."""
        binary = binary.replace(" ", "")
        bs = JavaBitSet()
        for i, c in enumerate(binary):
            if c == "1":
                bs.set(i)
        return bs
