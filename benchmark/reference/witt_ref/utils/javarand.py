"""Bit-exact reimplementation of java.util.Random (the 48-bit LCG specified
in the JavaDoc), plus java.util.Collections.shuffle.

The reference simulator derives *all* its determinism from a single
`new Random(0)` per network (reference: core Network.java:32).  Implementing
the exact generator lets the oracle engine reproduce the reference's runs
bit-for-bit, which turns the reference's published outputs (e.g. the README
PingPong progression) into executable golden tests for this repo.
"""

from __future__ import annotations

import math

_MULT = 0x5DEECE66D
_ADD = 0xB
_MASK48 = (1 << 48) - 1


def _i32(x: int) -> int:
    """Wrap to signed 32-bit like a Java int."""
    x &= 0xFFFFFFFF
    return x - 0x100000000 if x >= 0x80000000 else x


class JavaRandom:
    __slots__ = ("_seed", "_have_g", "_next_g")

    def __init__(self, seed: int = 0):
        self.set_seed(seed)

    def set_seed(self, seed: int) -> None:
        self._seed = (seed ^ _MULT) & _MASK48
        self._have_g = False
        self._next_g = 0.0

    # -- core generator ----------------------------------------------------
    def _next(self, bits: int) -> int:
        self._seed = (self._seed * _MULT + _ADD) & _MASK48
        return _i32(self._seed >> (48 - bits))

    # -- public API (names follow the Java API) ----------------------------
    def next_int(self, bound: int | None = None) -> int:
        if bound is None:
            return self._next(32)
        if bound <= 0:
            raise ValueError("bound must be positive")
        r = self._next(31)
        m = bound - 1
        if (bound & m) == 0:  # power of two
            return _i32((bound * r) >> 31)
        u = r
        r = u % bound
        while _i32(u - r + m) < 0:
            u = self._next(31)
            r = u % bound
        return r

    def next_long(self) -> int:
        hi = self._next(32)
        lo = self._next(32)
        v = (hi << 32) + lo
        v &= (1 << 64) - 1
        return v - (1 << 64) if v >= (1 << 63) else v

    def next_boolean(self) -> bool:
        return self._next(1) != 0

    def next_double(self) -> float:
        hi = self._next(26)
        lo = self._next(27)
        return ((hi << 27) + lo) / float(1 << 53)

    def next_float(self) -> float:
        return self._next(24) / float(1 << 24)

    def next_gaussian(self) -> float:
        if self._have_g:
            self._have_g = False
            return self._next_g
        while True:
            v1 = 2 * self.next_double() - 1
            v2 = 2 * self.next_double() - 1
            s = v1 * v1 + v2 * v2
            if 0 < s < 1:
                break
        mult = math.sqrt(-2 * math.log(s) / s)
        self._next_g = v2 * mult
        self._have_g = True
        return v1 * mult

    # -- java.util.Collections.shuffle -------------------------------------
    def shuffle(self, lst: list) -> None:
        """In-place Fisher–Yates exactly as Collections.shuffle(list, rnd)."""
        for i in range(len(lst) - 1, 0, -1):
            j = self.next_int(i + 1)
            lst[i], lst[j] = lst[j], lst[i]
