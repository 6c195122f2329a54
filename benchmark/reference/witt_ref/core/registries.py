"""Name-keyed registries for latency models and node builders.

Reference semantics: core RegistryNetworkLatencies.java (FIXED/UNIFORM
pre-registered at 0..8000 + by-class-name fallback) and
RegistryNodeBuilders.java (the 54-entry {AWS, CITIES, RANDOM} x
{CONSTANT, GAUSSIAN speed} x tor-ratio cross-product).  The reflection
fallback becomes an explicit class map.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import latency as L
from .geo import GeoAllCities, GeoAWS
from .node import (
    ExtraLatencyAspect,
    NodeBuilder,
    NodeBuilderWithCity,
    NodeBuilderWithRandomPosition,
    SpeedRatioAspect,
    UniformSpeed,
)

# ---------------------------------------------------------------------------
# Latency registry
# ---------------------------------------------------------------------------

_LATENCY_CLASSES = {
    "NetworkLatencyByDistanceWJitter": L.NetworkLatencyByDistanceWJitter,
    "AwsRegionNetworkLatency": L.AwsRegionNetworkLatency,
    "NetworkLatencyByCity": L.NetworkLatencyByCity,
    "NetworkLatencyByCityWJitter": L.NetworkLatencyByCityWJitter,
    "NetworkNoLatency": L.NetworkNoLatency,
    "EthScanNetworkLatency": L.EthScanNetworkLatency,
    "IC3NetworkLatency": L.IC3NetworkLatency,
}


class RegistryNetworkLatencies:
    FIXED = "FIXED"
    UNIFORM = "UNIFORM"

    def __init__(self):
        self._registry: Dict[str, L.NetworkLatency] = {}
        for f in (0, 100, 200, 500, 1000, 2000, 4000, 8000):
            self._registry[self.name(self.FIXED, f)] = L.NetworkFixedLatency(f)
            self._registry[self.name(self.UNIFORM, f)] = L.NetworkUniformLatency(f)

    @staticmethod
    def name(type_: str, fixed: int) -> str:
        if type_ == RegistryNetworkLatencies.FIXED:
            return f"NetworkFixedLatency({fixed})"
        if type_ == RegistryNetworkLatencies.UNIFORM:
            return f"NetworkUniformLatency({fixed})"
        raise ValueError(type_)

    def get_by_name(self, name: Optional[str]) -> L.NetworkLatency:
        if name is None:
            name = "NetworkLatencyByDistanceWJitter"
        nl = self._registry.get(name)
        if nl is not None:
            return nl
        cls = _LATENCY_CLASSES.get(name)
        if cls is None:
            raise ValueError(f"unknown latency model {name!r}")
        return cls()


registry_network_latencies = RegistryNetworkLatencies()

# ---------------------------------------------------------------------------
# Node-builder registry
# ---------------------------------------------------------------------------

AWS = "AWS"
CITIES = "CITIES"
RANDOM = "RANDOM"

TOR_RATIOS = (0.0, 0.01, 0.10, 0.20, 0.33, 0.5, 0.6, 0.8, 1.0)
LOCATIONS = (AWS, CITIES, RANDOM)


def builder_name(location: str, speed_constant: bool, tor: float) -> str:
    """Exact name format of RegistryNodeBuilders.name (note: the non-constant
    speed model is UniformSpeed but the name says GAUSSIAN, matching the
    reference's quirk at RegistryNodeBuilders.java:24-27)."""
    speed = "CONSTANT" if speed_constant else "GAUSSIAN"
    tor_s = (_java_double_str(tor) + "000")[:4]
    return f"{location}_speed={speed}_tor={tor_s}".upper()


def _java_double_str(d: float) -> str:
    s = repr(float(d))
    return s


class RegistryNodeBuilders:
    def __init__(self):
        self._specs = {}
        for loc in LOCATIONS:
            for speed_constant in (True, False):
                for tor in TOR_RATIOS:
                    self._specs[builder_name(loc, speed_constant, tor)] = (
                        loc,
                        speed_constant,
                        tor,
                    )
        self._cache: Dict[str, NodeBuilder] = {}

    def names(self):
        return list(self._specs.keys())

    def get_by_name(self, name: Optional[str]) -> NodeBuilder:
        if name is None or not name.strip():
            name = builder_name(RANDOM, True, 0.0)
        if name not in self._specs:
            raise ValueError(f"{name} not in the registry")
        if name not in self._cache:
            self._cache[name] = self._build(*self._specs[name])
        return self._cache[name].copy()

    @staticmethod
    def _build(loc: str, speed_constant: bool, tor: float) -> NodeBuilder:
        if loc == AWS:
            nb = NodeBuilderWithCity(L.AwsRegionNetworkLatency.cities(), GeoAWS())
        elif loc == CITIES:
            from ..tools.latency_csv import CSVLatencyReader

            nb = NodeBuilderWithCity(CSVLatencyReader().cities(), GeoAllCities())
        elif loc == RANDOM:
            nb = NodeBuilderWithRandomPosition()
        else:
            raise ValueError(loc)
        if not speed_constant:
            nb.aspects.append(SpeedRatioAspect(UniformSpeed()))
        if tor > 0.001:
            nb.aspects.append(ExtraLatencyAspect(tor))
        return nb


registry_node_builders = RegistryNodeBuilders()
