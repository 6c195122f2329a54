"""Node identity & per-node state + node builders.

Reference semantics: core Node.java (identity, position, aspects, traffic
counters) and NodeBuilder.java (id allocation, SHA-256 hash, random or
city-weighted positions).  The oracle engine uses these objects directly;
the batched engine converts a built node population into struct-of-arrays
columns via `build_node_columns`.
"""

from __future__ import annotations

import hashlib
import math
from typing import Dict, List, Optional

import numpy as np

from ..utils.gpd import GeneralizedParetoDistribution
from ..utils.javaops import i32, java_abs, java_mod, lshift32
from ..utils.javarand import JavaRandom
from .geo import DEFAULT_CITY, MAX_X, MAX_Y, CityInfo, Geo

MAX_DIST = int(math.sqrt((MAX_X / 2.0) ** 2 + (MAX_Y / 2.0) ** 2))


# ---------------------------------------------------------------------------
# Aspects: optional per-node attribute samplers (Node.java:145-244)
# ---------------------------------------------------------------------------


class Aspect:
    def get_value(self, rd: JavaRandom):
        return None


class ExtraLatencyAspect(Aspect):
    """Tor-style extra latency: 500 ms with probability `ratio`."""

    def __init__(self, ratio: float):
        self.ratio = ratio

    def get_value(self, rd: JavaRandom):
        return 500 if rd.next_double() < self.ratio else 0


class SpeedRatioAspect(Aspect):
    def __init__(self, speed_model: "SpeedModel"):
        self.sm = speed_model

    def get_value(self, rd: JavaRandom):
        return self.sm.get_speed_ratio(rd)


class SpeedModel:
    def get_speed_ratio(self, rd: JavaRandom) -> float:
        raise NotImplementedError


class ParetoSpeed(SpeedModel):
    def __init__(self, shape: float, location: float, scale: float, max_: float):
        self.gpd = GeneralizedParetoDistribution(shape, location, scale)
        self.max = max_

    def get_speed_ratio(self, rd: JavaRandom) -> float:
        return min(self.max, 1.0 + self.gpd.inverse_f(rd.next_double()))


class GaussianSpeed(SpeedModel):
    def get_speed_ratio(self, rd: JavaRandom) -> float:
        return max(0.33, rd.next_gaussian() + 1)


class UniformSpeed(SpeedModel):
    """Uniform from 3x faster to 3x slower (Node.java:233-244)."""

    def get_speed_ratio(self, rd: JavaRandom) -> float:
        if rd.next_boolean():
            return (rd.next_int(67) + 33) / 100.0
        return (rd.next_int(200) + 100) / 100.0


def _aspect_value(aspect_cls, aspects: List[Aspect], rd: JavaRandom, default):
    for a in aspects:
        if type(a) is aspect_cls:
            return a.get_value(rd)
    return default


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------


class Node:
    MAX_X = MAX_X
    MAX_Y = MAX_Y
    MAX_DIST = MAX_DIST
    DEFAULT_CITY = DEFAULT_CITY

    __slots__ = (
        "node_id",
        "hash256",
        "x",
        "y",
        "extra_latency",
        "byzantine",
        "speed_ratio",
        "city_name",
        "_down",
        "done_at",
        "msg_received",
        "msg_sent",
        "bytes_sent",
        "bytes_received",
        "_builder",
        "external",
    )

    def __init__(self, rd: JavaRandom, nb: "NodeBuilder", byzantine: bool = False):
        self.node_id = nb.allocate_node_id()
        if self.node_id < 0:
            raise ValueError(f"bad nodeId: {self.node_id}")
        rd_node = rd.next_int()
        self.city_name = nb.get_city_name(rd_node)
        self.x = nb.get_x(rd_node)
        self.y = nb.get_y(rd_node)
        if not (0 < self.x <= MAX_X):
            raise ValueError(f"bad x={self.x}")
        if not (0 < self.y <= MAX_Y):
            raise ValueError(f"bad y={self.y}")
        self.byzantine = byzantine
        self.hash256 = nb.get_hash(self.node_id)
        # aspect sampling order matters for RNG-stream parity (Node.java:265-266)
        self.speed_ratio = float(
            _aspect_value(SpeedRatioAspect, nb.aspects, rd, 1.0)
        )
        self.extra_latency = int(
            _aspect_value(ExtraLatencyAspect, nb.aspects, rd, 0)
        )
        if self.speed_ratio <= 0:
            raise ValueError(f"speedRatio={self.speed_ratio}")
        self._down = False
        self.done_at = 0
        self.msg_received = 0
        self.msg_sent = 0
        self.bytes_sent = 0
        self.bytes_received = 0
        self._builder = nb
        self.external = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        self._down = False

    def stop(self) -> None:
        self._down = True

    def is_down(self) -> bool:
        return self._down

    def generate_new_unique_int_id(self) -> int:
        return self._builder.next_unique_int_id()

    def dist(self, other: "Node") -> int:
        """Toroidal map distance (Node.java:278-282)."""
        dx = min(abs(self.x - other.x), MAX_X - abs(self.x - other.x))
        dy = min(abs(self.y - other.y), MAX_Y - abs(self.y - other.y))
        return int(math.sqrt(dx * dx + dy * dy))

    def __repr__(self) -> str:
        return f"Node{{nodeId={self.node_id}}}"

    def __eq__(self, other) -> bool:
        return isinstance(other, Node) and other.node_id == self.node_id

    def __hash__(self) -> int:
        return self.node_id


# ---------------------------------------------------------------------------
# Builders (NodeBuilder.java)
# ---------------------------------------------------------------------------


class NodeBuilder:
    def __init__(self):
        self._node_ids = 0
        self._uint_id = 0
        self.aspects: List[Aspect] = []

    def copy(self) -> "NodeBuilder":
        """Same builder with node ids reset (NodeBuilder.java:42-52); aspects
        and the unique-int counter are shared, like the Java shallow clone."""
        import copy as _copy

        nb = _copy.copy(self)
        nb._node_ids = 0
        return nb

    def allocate_node_id(self) -> int:
        nid = self._node_ids
        self._node_ids += 1
        return nid

    def next_unique_int_id(self) -> int:
        self._uint_id += 1
        return self._uint_id

    def get_x(self, rd_int: int) -> int:
        return 1

    def get_y(self, rd_int: int) -> int:
        return 1

    def get_city_name(self, rd_int: int) -> str:
        return DEFAULT_CITY

    def get_hash(self, node_id: int) -> bytes:
        return hashlib.sha256(node_id.to_bytes(4, "big", signed=True)).digest()


class NodeBuilderWithRandomPosition(NodeBuilder):
    """Position from the high/low 16 bits of one random int
    (NodeBuilder.java:77-96, including the int32 overflow on the y path)."""

    def get_x(self, rd_int: int) -> int:
        r = abs(rd_int >> 16)  # arithmetic shift, then abs as 64-bit
        return r % MAX_X + 1

    def get_y(self, rd_int: int) -> int:
        r = abs(lshift32(rd_int, 16))
        return r % MAX_Y + 1


class NodeBuilderWithCity(NodeBuilder):
    """Weighted-random city selection (NodeBuilder.java:98-148)."""

    def __init__(self, cities: List[str], geo: Geo):
        super().__init__()
        self.cities = [c.upper() for c in cities]
        wanted = set(self.cities)
        self.cities_info: Dict[str, CityInfo] = {
            k: v for k, v in geo.cities_position().items() if k.upper() in wanted
        }

    def get_city_name(self, rd_int: int) -> str:
        name = self._random_city(rd_int)
        if name is None:
            raise ValueError("no city matched")
        return name

    def _random_city(self, rd_int: int) -> Optional[str]:
        size = len(self.cities)
        rand = java_mod(java_abs(i32(rd_int)), size)
        p = rand / size
        for name, info in self.cities_info.items():
            if p <= info.cumulative_probability:
                return name
        return None

    def _pos(self, rd_int: int):
        info = self.cities_info[self.get_city_name(rd_int)]
        return info.merc_x, info.merc_y

    def get_x(self, rd_int: int) -> int:
        return self._pos(rd_int)[0]

    def get_y(self, rd_int: int) -> int:
        return self._pos(rd_int)[1]


# ---------------------------------------------------------------------------
# SoA conversion for the batched engine
# ---------------------------------------------------------------------------


def build_node_columns(nodes: List[Node], city_index: Dict[str, int] | None = None):
    """Convert built Node objects into the static struct-of-arrays columns the
    batched engine consumes.  city_index maps cityName -> int for city-matrix
    latency models (absent cities map to -1)."""
    n = len(nodes)
    cols = {
        "x": np.array([nd.x for nd in nodes], dtype=np.int32),
        "y": np.array([nd.y for nd in nodes], dtype=np.int32),
        "extra_latency": np.array([nd.extra_latency for nd in nodes], dtype=np.int32),
        "speed_ratio": np.array([nd.speed_ratio for nd in nodes], dtype=np.float32),
        "byzantine": np.array([nd.byzantine for nd in nodes], dtype=bool),
        "city_idx": np.full(n, -1, dtype=np.int32),
    }
    if city_index:
        for idx, nd in enumerate(nodes):
            cols["city_idx"][idx] = city_index.get(nd.city_name, -1)
    return cols
