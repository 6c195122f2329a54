"""Java integer/float semantics helpers used for bit-exact oracle parity."""

from __future__ import annotations

import math

INT_MIN = -(1 << 31)
INT_MAX = (1 << 31) - 1


def i32(x: int) -> int:
    """Wrap to signed 32-bit (Java int overflow semantics)."""
    x &= 0xFFFFFFFF
    return x - 0x100000000 if x >= 0x80000000 else x


def java_abs(x: int) -> int:
    """Math.abs for Java ints: abs(Integer.MIN_VALUE) is still negative."""
    return x if x == INT_MIN else abs(x)


def java_mod(a: int, b: int) -> int:
    """Java % takes the sign of the dividend (Python's takes the divisor's)."""
    return int(math.fmod(a, b))


def java_int_div(a: int, b: int) -> int:
    """Java integer division truncates toward zero."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


def jint(x: float) -> int:
    """Java (int) cast of a double: truncation toward zero."""
    return int(x)  # Python int() truncates toward zero


def jround(x: float) -> int:
    """Java Math.round(double) == floor(x + 0.5)."""
    return math.floor(x + 0.5)


def ushift_r(x: int, n: int) -> int:
    """Java >>> on an int32 value."""
    return (x & 0xFFFFFFFF) >> n


def lshift32(x: int, n: int) -> int:
    """Java << on int32, wrapping."""
    return i32(x << n)
