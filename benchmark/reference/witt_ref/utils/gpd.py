"""Generalized Pareto distribution — closed-form inverse CDF.

Matches the reference implementation semantics
(core utils/GeneralizedParetoDistribution.java:31-47): clamping near 0/1 and
the three-branch inverse.  Because the inverse CDF is closed-form it is
directly jittable; `inverse_f_jnp` is the vectorized twin used by the
batched latency kernels.
"""

from __future__ import annotations

import math

_ONE = 0.999999
_ZERO = 0.000001


class GeneralizedParetoDistribution:
    __slots__ = ("shape", "location", "scale")

    def __init__(self, shape: float, location: float, scale: float):
        if scale <= 0.0:
            raise ValueError(f"scale={scale}")
        self.shape = shape
        self.location = location
        self.scale = scale

    def inverse_f(self, y: float) -> float:
        if y < 0.0 or y > 1.0:
            raise ValueError(f"y={y}")
        if y < _ZERO:
            return self.location
        if y > _ONE:
            if self.shape >= 0:
                return math.inf
            return self.location - self.scale / self.shape
        if abs(self.shape) < _ZERO:
            return self.location - self.scale * math.log1p(-y)
        return self.location + self.scale / self.shape * (-1 + (1 - y) ** -self.shape)


def inverse_f_jnp(shape: float, location: float, scale: float, y):
    """Vectorized inverse CDF on a jnp array y in [0, 1].

    Static distribution parameters, traced y.  The y<ZERO / y>ONE clamps are
    expressed with jnp.where so the function stays branch-free under jit.
    """
    import jax.numpy as jnp

    if scale <= 0.0:
        raise ValueError(f"scale={scale}")
    y = jnp.asarray(y)
    if abs(shape) < _ZERO:
        mid = location - scale * jnp.log1p(-jnp.clip(y, 0.0, _ONE))
    else:
        mid = location + scale / shape * (-1.0 + (1.0 - jnp.clip(y, 0.0, _ONE)) ** -shape)
    hi = jnp.inf if shape >= 0 else location - scale / shape
    out = jnp.where(y < _ZERO, location, jnp.where(y > _ONE, hi, mid))
    return out
