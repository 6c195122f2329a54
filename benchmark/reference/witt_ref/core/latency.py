"""Network latency models.

Reference semantics: core NetworkLatency.java (9 models + measurement
helpers).  Every model exists in two forms:

  * scalar `get_latency(from_node, to_node, delta)` — bit-exact with the
    reference (Java int truncation / Math.round semantics), used by the
    oracle DES;
  * vectorized `ext_vec(static, from_idx, to_idx, delta)` — pure jnp,
    jittable, used inside the batched tick kernel.  `delta` is an int array
    in [0, 99]; the shared wrapper `vec_latency` adds extra-latency columns,
    the from==to short-circuit, and the max(1, ·) clamp
    (NetworkLatency.getLatency, NetworkLatency.java:27-34).

All randomness is externalized into `delta` (reference design: a 0..99
uniform), which maps directly onto counter-based RNG in the batched engine.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from ..utils.gpd import GeneralizedParetoDistribution
from ..utils.javaops import java_int_div, jint, jround
from .geo import MAX_X, MAX_Y
from .node import MAX_DIST, Node

_WAN_GPD = GeneralizedParetoDistribution(1.4, -0.3, 0.35)
# delta only ever takes 100 values: precompute the jitter table once.
JITTER_TABLE = np.array([_WAN_GPD.inverse_f(d / 100.0) for d in range(100)])


class NetworkLatency:
    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        raise NotImplementedError

    def _check_delta(self, delta: int) -> None:
        if delta < 0 or delta > 99:
            raise ValueError(f"delta={delta}")

    def get_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        if from_node is to_node:
            return 1
        base = from_node.extra_latency + to_node.extra_latency
        base += self.get_extended_latency(from_node, to_node, delta)
        return max(1, base)

    # -- vectorized twin ---------------------------------------------------
    def ext_vec(self, static: "LatencyStatic", from_idx, to_idx, delta):
        """jnp latencies for index arrays; override per model."""
        raise NotImplementedError

    def __str__(self) -> str:
        return type(self).__name__


class LatencyStatic:
    """Static per-node columns the vectorized models read: positions,
    extra latency, city/region indices, plus any model tables."""

    def __init__(self, x, y, extra_latency, city_idx=None):
        import jax.numpy as jnp

        self.x = jnp.asarray(x, dtype=jnp.int32)
        self.y = jnp.asarray(y, dtype=jnp.int32)
        self.extra_latency = jnp.asarray(extra_latency, dtype=jnp.int32)
        self.city_idx = (
            None if city_idx is None else jnp.asarray(city_idx, dtype=jnp.int32)
        )

    @classmethod
    def from_columns(cls, cols: dict) -> "LatencyStatic":
        return cls(cols["x"], cols["y"], cols["extra_latency"], cols.get("city_idx"))


def vec_latency(model: NetworkLatency, static: LatencyStatic, from_idx, to_idx, delta):
    """Shared wrapper (getLatency semantics) around a model's ext_vec."""
    import jax.numpy as jnp

    ext = model.ext_vec(static, from_idx, to_idx, delta)
    extras = static.extra_latency[from_idx] + static.extra_latency[to_idx]
    lat = jnp.maximum(1, extras + ext)
    return jnp.where(from_idx == to_idx, 1, lat).astype(jnp.int32)


def _dist_vec(static: LatencyStatic, from_idx, to_idx):
    """Toroidal distance, int-truncated like Node.dist."""
    import jax.numpy as jnp

    dx = jnp.abs(static.x[from_idx] - static.x[to_idx])
    dx = jnp.minimum(dx, MAX_X - dx)
    dy = jnp.abs(static.y[from_idx] - static.y[to_idx])
    dy = jnp.minimum(dy, MAX_Y - dy)
    d2 = dx * dx + dy * dy
    # XLA's f32 sqrt can be 1 ulp off; snap to the exact integer sqrt so the
    # table lookups stay bit-exact with the scalar path.
    s = jnp.sqrt(d2.astype(jnp.float32)).astype(jnp.int32)
    s = jnp.where((s + 1) * (s + 1) <= d2, s + 1, s)
    s = jnp.where(s * s > d2, s - 1, s)
    return s


# ---------------------------------------------------------------------------
# 1. Distance + Generalized-Pareto jitter (the WAN default)
# ---------------------------------------------------------------------------


class NetworkLatencyByDistanceWJitter(NetworkLatency):
    """RTT = 0.022 * miles + 4.862 plus GPD(ξ=1.4, μ=-0.3, σ=0.35) jitter,
    halved for one-way (NetworkLatency.java:49-73)."""

    EARTH_PERIMETER = 24_860
    POINT_VALUE = (EARTH_PERIMETER / 2) / MAX_DIST

    def dist_to_mile(self, dist: int) -> float:
        return self.POINT_VALUE * dist

    def get_jitter(self, delta: int) -> float:
        return float(JITTER_TABLE[delta])

    def get_fixed_latency(self, dist: int) -> float:
        return self.dist_to_mile(dist) * 0.022 + 4.862

    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        self._check_delta(delta)
        raw = self.get_fixed_latency(from_node.dist(to_node)) + self.get_jitter(delta)
        return jint(raw / 2)

    # Exact-table trick: dist is an int <= MAX_DIST and delta < 100, so the
    # whole model is a [MAX_DIST+1, 100] int32 table computed in float64 on
    # the host.  The kernel is then a single gather — bit-exact with the
    # scalar path AND cheaper on TPU than transcendentals.
    _TABLE = None

    @classmethod
    def _table(cls) -> np.ndarray:
        if cls._TABLE is None:
            dists = np.arange(MAX_DIST + 1, dtype=np.float64)
            fixed = dists * (cls.POINT_VALUE * 0.022) + 4.862
            raw = fixed[:, None] + JITTER_TABLE[None, :]
            cls._TABLE = (raw / 2).astype(np.int32)  # trunc toward zero (>0)
        return cls._TABLE

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        table = jnp.asarray(self._table())
        dist = _dist_vec(static, from_idx, to_idx)
        return table[dist, delta]


# ---------------------------------------------------------------------------
# 2. AWS region ping matrix
# ---------------------------------------------------------------------------

AWS_REGION_PER_CITY: Dict[str, int] = {
    "Oregon": 0,
    "Virginia": 1,
    "Mumbai": 2,
    "Seoul": 3,
    "Singapore": 4,
    "Sydney": 5,
    "Tokyo": 6,
    "Canada central": 7,
    "Frankfurt": 8,
    "Ireland": 9,
    "London": 10,
}

# Upper-triangular ping matrix, ms RTT (NetworkLatency.java:112-128)
_AWS_PINGS = np.array(
    [
        [0, 81, 216, 126, 165, 138, 97, 64, 164, 131, 141],
        [0, 0, 182, 181, 232, 195, 167, 13, 88, 80, 75],
        [0, 0, 0, 152, 62, 223, 123, 194, 111, 122, 113],
        [0, 0, 0, 0, 97, 133, 35, 184, 259, 254, 264],
        [0, 0, 0, 0, 0, 169, 69, 218, 162, 174, 171],
        [0, 0, 0, 0, 0, 0, 105, 210, 282, 269, 271],
        [0, 0, 0, 0, 0, 0, 0, 156, 235, 222, 234],
        [0, 0, 0, 0, 0, 0, 0, 0, 101, 78, 87],
        [0, 0, 0, 0, 0, 0, 0, 0, 0, 24, 13],
        [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 12],
        [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
    ],
    dtype=np.int32,
)


def _aws_oneway_matrix() -> np.ndarray:
    """Symmetric one-way base matrix: ping/2, diagonal 0 (same-region handled
    separately)."""
    full = np.maximum(_AWS_PINGS, _AWS_PINGS.T)
    return full // 2


class AwsRegionNetworkLatency(NetworkLatency):
    ONEWAY = _aws_oneway_matrix()

    @staticmethod
    def cities():
        return sorted(AWS_REGION_PER_CITY.keys())

    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        reg1 = AWS_REGION_PER_CITY.get(from_node.city_name)
        reg2 = AWS_REGION_PER_CITY.get(to_node.city_name)
        if reg1 is None or reg2 is None:
            raise ValueError(
                f"{from_node} or {to_node} not in our aws cities list"
            )
        if reg1 == reg2:
            return 1
        base = int(self.ONEWAY[reg1, reg2])
        return max(1, base + jint(float(JITTER_TABLE[delta])))

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        m = jnp.asarray(self.ONEWAY, dtype=jnp.int32)
        jt = jnp.asarray(JITTER_TABLE, dtype=jnp.float32)
        r1 = static.city_idx[from_idx]
        r2 = static.city_idx[to_idx]
        lat = jnp.maximum(1, m[r1, r2] + jt[delta].astype(jnp.int32))
        return jnp.where(r1 == r2, 1, lat)


# ---------------------------------------------------------------------------
# 3/4. Wondernetwork city matrix, without and with jitter
# ---------------------------------------------------------------------------


class NetworkLatencyByCity(NetworkLatency):
    def __init__(self, reader=None):
        if reader is None:
            from ..tools.latency_csv import CSVLatencyReader

            reader = CSVLatencyReader()
        self._reader = reader
        self._index = reader.city_index()
        self._matrix = reader.matrix()

    @property
    def city_index(self):
        return self._index

    def _city_lat(self, city_from: str, city_to: str) -> float:
        return float(self._matrix[self._index[city_from], self._index[city_to]])

    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        if from_node.node_id == to_node.node_id:
            return 1
        if (
            from_node.city_name == Node.DEFAULT_CITY
            or to_node.city_name == Node.DEFAULT_CITY
        ):
            raise ValueError(
                "Can't use NetworkLatencyByCity model with default city location"
            )
        raw = np.float32(0.5) * np.float32(
            self._city_lat(from_node.city_name, to_node.city_name)
        )
        return max(1, jround(float(raw)))

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        m = jnp.asarray(self._matrix, dtype=jnp.float32)
        c1 = static.city_idx[from_idx]
        c2 = static.city_idx[to_idx]
        lat = jnp.maximum(1, jnp.floor(0.5 * m[c1, c2] + 0.5).astype(jnp.int32))
        return jnp.where(from_idx == to_idx, 1, lat)


class NetworkLatencyByCityWJitter(NetworkLatencyByCity):
    """City matrix + GPD jitter; same-city RTT approximated as 10 ms
    (NetworkLatency.java:200-233)."""

    SAME_CITY_RTT = 10.0

    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        if from_node.node_id == to_node.node_id:
            return 1
        if (
            from_node.city_name == Node.DEFAULT_CITY
            or to_node.city_name == Node.DEFAULT_CITY
        ):
            raise ValueError(
                "Can't use NetworkLatencyByCity model with default city location"
            )
        raw = float(JITTER_TABLE[delta])
        if from_node.city_name == to_node.city_name:
            raw += self.SAME_CITY_RTT
        else:
            raw += self._city_lat(from_node.city_name, to_node.city_name)
        return max(1, jint(jround(0.5 * raw)))

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        m = jnp.asarray(self._matrix, dtype=jnp.float32)
        jt = jnp.asarray(JITTER_TABLE, dtype=jnp.float32)
        c1 = static.city_idx[from_idx]
        c2 = static.city_idx[to_idx]
        base = jnp.where(c1 == c2, jnp.float32(self.SAME_CITY_RTT), m[c1, c2])
        raw = base + jt[delta]
        lat = jnp.maximum(1, jnp.floor(0.5 * raw + 0.5).astype(jnp.int32))
        return jnp.where(from_idx == to_idx, 1, lat)


# ---------------------------------------------------------------------------
# 5/6/7. Fixed / uniform / none
# ---------------------------------------------------------------------------


class NetworkFixedLatency(NetworkLatency):
    def __init__(self, fixed_latency: int):
        self.fixed_latency = max(1, fixed_latency)

    def get_extended_latency(self, from_node, to_node, delta) -> int:
        return self.fixed_latency

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        return jnp.full(jnp.shape(from_idx), self.fixed_latency, dtype=jnp.int32)

    def __str__(self):
        return f"fixedLatency:{self.fixed_latency}"


class NetworkUniformLatency(NetworkLatency):
    def __init__(self, max_latency: int):
        self.max_latency = max(1, max_latency)

    def get_extended_latency(self, from_node, to_node, delta) -> int:
        return jint((delta / 99.0) * self.max_latency)

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        return (
            (delta.astype(jnp.float32) / 99.0) * self.max_latency
        ).astype(jnp.int32)

    def __str__(self):
        return f"NetworkUniformLatency:{self.max_latency}"


class NetworkNoLatency(NetworkLatency):
    def get_extended_latency(self, from_node, to_node, delta) -> int:
        return 1

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        return jnp.ones(jnp.shape(from_idx), dtype=jnp.int32)


# ---------------------------------------------------------------------------
# 8. Measured distribution (100-bucket inverse CDF)
# ---------------------------------------------------------------------------


class MeasuredNetworkLatency(NetworkLatency):
    def __init__(self, distrib_prop, distrib_val):
        self.long_distrib = self._set_latency(distrib_prop, distrib_val)

    @staticmethod
    def _set_latency(proportions, values) -> np.ndarray:
        """Integer-step interpolation, exact reference arithmetic
        (NetworkLatency.java:284-303)."""
        out = np.zeros(100, dtype=np.int64)
        li = 0
        cur = 0
        total = 0
        for prop, val in zip(proportions, values):
            if prop == 0:
                cur = val
                continue
            total += prop
            step = java_int_div(val - cur, prop)  # Java int division
            for _ in range(prop):
                cur += step
                out[li] = cur
                li += 1
        if total != 100 or li != 100:
            raise ValueError("proportions must sum to 100")
        return out

    def get_extended_latency(self, from_node, to_node, delta) -> int:
        self._check_delta(delta)
        return int(self.long_distrib[delta])

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        table = jnp.asarray(self.long_distrib, dtype=jnp.int32)
        return table[delta]


# ---------------------------------------------------------------------------
# 9. EthStats block-propagation distribution
# ---------------------------------------------------------------------------


class EthScanNetworkLatency(NetworkLatency):
    DISTRIB_PROP = [16, 18, 17, 12, 8, 5, 4, 3, 3, 1, 1, 2, 1, 1, 8]
    DISTRIB_VAL = [
        250, 500, 1000, 1250, 1500, 1750, 2000, 2250, 2500, 2750,
        4500, 6000, 8500, 9750, 10000,
    ]

    def __init__(self):
        self._m = MeasuredNetworkLatency(self.DISTRIB_PROP, self.DISTRIB_VAL)

    def get_extended_latency(self, from_node, to_node, delta) -> int:
        # The reference delegates to MeasuredNetworkLatency.getLatency (adds
        # extras + clamps inside); kept exact (NetworkLatency.java:374-377).
        return self._m.get_latency(from_node, to_node, delta)

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        inner = vec_latency(self._m, static, from_idx, to_idx, delta)
        return inner


# ---------------------------------------------------------------------------
# 10. IC3 area-quantile latency
# ---------------------------------------------------------------------------


class IC3NetworkLatency(NetworkLatency):
    S10 = 92
    SW = 350

    def get_extended_latency(self, from_node: Node, to_node: Node, delta: int) -> int:
        dist = from_node.dist(to_node)
        surface = dist * dist * math.pi
        total_surface = MAX_X * MAX_Y
        position = jint((surface * 100) / total_surface)
        if position <= 10:
            return self.S10 // 2
        if position <= 33:
            return 125 // 2
        if position <= 50:
            return 152 // 2
        if position <= 67:
            return 200 // 2
        if position <= 90:
            return 276 // 2
        return self.SW // 2

    _TABLE = None

    @classmethod
    def _table(cls) -> np.ndarray:
        """Exact per-distance table (float64 host precompute, see
        NetworkLatencyByDistanceWJitter._table for the rationale)."""
        if cls._TABLE is None:
            out = np.empty(MAX_DIST + 1, dtype=np.int32)
            for dist in range(MAX_DIST + 1):
                surface = float(dist) * dist * math.pi
                position = jint((surface * 100) / (MAX_X * MAX_Y))
                if position <= 10:
                    out[dist] = cls.S10 // 2
                elif position <= 33:
                    out[dist] = 125 // 2
                elif position <= 50:
                    out[dist] = 152 // 2
                elif position <= 67:
                    out[dist] = 200 // 2
                elif position <= 90:
                    out[dist] = 276 // 2
                else:
                    out[dist] = cls.SW // 2
            cls._TABLE = out
        return cls._TABLE

    def ext_vec(self, static, from_idx, to_idx, delta):
        import jax.numpy as jnp

        table = jnp.asarray(self._table())
        dist = _dist_vec(static, from_idx, to_idx)
        return table[dist]


# ---------------------------------------------------------------------------
# Empirical re-measurement (estimateLatency family, NetworkLatency.java:432-509)
# ---------------------------------------------------------------------------


def _add_to_stats(lat: int, props, vals) -> None:
    p = 0
    while p < len(props) - 1 and vals[p] < lat:
        p += 1
    props[p] += 1


def estimate_latency(net, rounds: int, peer_getter=None) -> MeasuredNetworkLatency:
    """Sample the live latency model into a measured distribution, using the
    network's RNG stream exactly like the reference."""
    from ..utils.javarand import JavaRandom

    props = [0] * 50
    vals = [0] * 50
    pos = 0
    for i in range(10, 201, 10):
        vals[pos] = i
        pos += 1
    for i in range(300, 2001, 100):
        vals[pos] = i
        pos += 1
    while pos < len(vals):
        vals[pos] = vals[pos - 1] + 1000
        pos += 1

    if peer_getter is None:

        def peer_getter(n):
            prd = JavaRandom(0)
            res = n
            while res is n:
                res = net.all_nodes[prd.next_int(len(net.all_nodes))]
            return res

    node_ct = len(net.all_nodes)
    rounds_ct = rounds
    while rounds_ct > 0:
        n1 = net.all_nodes[net.rd.next_int(node_ct)]
        n2 = peer_getter(n1)
        if n1 is not n2:
            rounds_ct -= 1
            delay = net.network_latency.get_latency(n1, n2, net.rd.next_int(100))
            _add_to_stats(delay, props, vals)

    props = [jround((100.0 * p) / rounds) for p in props]
    tot = sum(props)
    while tot != 100:
        gap = 100 - tot
        tot = 0
        for i in range(len(props)):
            if gap > 0 and props[i] > 0:
                props[i] += 1
                gap -= 1
            elif gap < 0 and props[i] > 1:
                props[i] -= 1
                gap += 1
            tot += props[i]
    return MeasuredNetworkLatency(props, vals)


def estimate_p2p_latency(net, rounds: int) -> MeasuredNetworkLatency:
    from ..utils.javarand import JavaRandom

    def peer_getter(n):
        prd = JavaRandom(0)
        res = n
        while res is n:
            res = n.peers[prd.next_int(len(n.peers))]
        return res

    return estimate_latency(net, rounds, peer_getter)
