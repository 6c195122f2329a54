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

# ---------------------------------------------------------------------------
# Batched-protocol registry (enumeration hook for tooling)
# ---------------------------------------------------------------------------
# Every `protocols/*_batched.py` implementation registers here with a
# SMALL-SCALE factory returning the usual `(net, state)` pair.  The point is
# enumeration, not construction convenience: the static checker
# (wittgenstein_tpu.analysis) iterates these entries to run its
# abstract-eval contract passes over EVERY protocol, and its SL301
# meta-rule fails CI when a new `*_batched.py` lands without an entry.
# Factories import lazily (inside the call) so this module stays cheap to
# import and free of protocol->core->protocol cycles.


@dataclasses.dataclass(frozen=True)
class BatchedProtocolEntry:
    """One registered batched protocol.

    name            registry key (stable id used in reports);
    module          module path under wittgenstein_tpu.protocols;
    factory         () -> (net, state) at a small analysis-friendly scale
                    (mirrors each protocol's standard-scenario test config);
    contract_checks False for implementations that are not BatchedProtocol
                    kernels on the generic engine (their `note` says why) —
                    SL301 still counts them as covered, the abstract-eval
                    pass skips them loudly rather than silently.
    """

    name: str
    module: str
    factory: Callable[[], Tuple[Any, Any]]
    contract_checks: bool = True
    note: str = ""


class RegistryBatchedProtocols:
    def __init__(self):
        self._entries: Dict[str, BatchedProtocolEntry] = {}

    def register(self, entry: BatchedProtocolEntry) -> None:
        if entry.name in self._entries:
            raise ValueError(f"duplicate batched protocol {entry.name!r}")
        self._entries[entry.name] = entry

    def names(self) -> List[str]:
        return sorted(self._entries)

    def get(self, name: str) -> BatchedProtocolEntry:
        return self._entries[name]

    def entries(self) -> List[BatchedProtocolEntry]:
        return [self._entries[n] for n in self.names()]

    def modules(self) -> List[str]:
        return sorted({e.module for e in self._entries.values()})


registry_batched_protocols = RegistryBatchedProtocols()


def _reg(name, module, factory, **kw):
    registry_batched_protocols.register(
        BatchedProtocolEntry(name, module, factory, **kw)
    )


def _make_pingpong_small():
    from ..protocols.pingpong_batched import make_pingpong

    return make_pingpong(64)


def _make_p2pflood_small():
    from ..protocols.p2pflood import P2PFloodParameters
    from ..protocols.p2pflood_batched import make_p2pflood

    return make_p2pflood(P2PFloodParameters(), capacity=2048)


def _make_p2pflood_faults_small():
    # the fault-LANE contract entry: same protocol/scale as "p2pflood"
    # but with the fault engine armed and a non-neutral schedule, so
    # simlint traces deliver/step against a state that actually carries
    # fault leaves (SL402/SL407 on the plain entry would be vacuous —
    # zero fault leaves to check ownership of)
    from ..faults import FaultConfig, FaultPlan
    from ..protocols.p2pflood import P2PFloodParameters
    from ..protocols.p2pflood_batched import make_p2pflood

    net, state = make_p2pflood(P2PFloodParameters(), capacity=2048)
    plan = (
        FaultPlan("contract")
        .crash(range(20, 30), at=200, recover=900)
        .drop(100, start=100)
        .inflate(1500, add_ms=5, start=100, end=800)
    )
    return net.with_faults(state, FaultConfig(), plan)


def _make_paxos_small():
    from ..protocols.paxos import PaxosParameters
    from ..protocols.paxos_batched import make_paxos

    return make_paxos(PaxosParameters())


def _make_slush_small():
    from ..protocols.avalanche_batched import make_slush

    return make_slush()


def _make_snowflake_small():
    from ..protocols.avalanche_batched import make_snowflake

    return make_snowflake()


def _make_handel_small():
    from ..protocols.handel import HandelParameters
    from ..protocols.handel_batched import make_handel

    return make_handel(
        HandelParameters(
            node_count=64,
            threshold=int(64 * 0.99),
            pairing_time=3,
            level_wait_time=50,
            extra_cycle=10,
            dissemination_period_ms=10,
            fast_path=10,
            nodes_down=0,
        )
    )


def _make_gsf_small():
    from ..protocols.gsf import GSFSignatureParameters
    from ..protocols.gsf_batched import make_gsf

    return make_gsf(
        GSFSignatureParameters(
            node_count=64,
            threshold=int(64 * 0.99),
            pairing_time=3,
            timeout_per_level_ms=50,
            period_duration_ms=10,
            accelerated_calls_count=10,
            nodes_down=0,
        )
    )


def _make_handeleth2_small():
    from ..protocols.handeleth2 import HandelEth2Parameters
    from ..protocols.handeleth2_batched import make_handeleth2

    return make_handeleth2(
        HandelEth2Parameters(
            node_count=32,
            pairing_time=3,
            level_wait_time=100,
            period_duration_ms=50,
            nodes_down=0,
        )
    )


def _make_optimistic_small():
    from ..protocols.optimistic_p2p_signature import (
        OptimisticP2PSignatureParameters,
    )
    from ..protocols.optimistic_p2p_signature_batched import make_optimistic

    return make_optimistic(
        OptimisticP2PSignatureParameters(
            node_count=64, threshold=56, connection_count=10, pairing_time=3
        )
    )


def _make_p2phandel_small():
    from ..protocols.p2phandel import P2PHandelParameters
    from ..protocols.p2phandel_batched import make_p2phandel

    return make_p2phandel(P2PHandelParameters())


def _make_sanfermin_small():
    from ..protocols.sanfermin import SanFerminSignatureParameters
    from ..protocols.sanfermin_batched import make_sanfermin

    return make_sanfermin(
        SanFerminSignatureParameters(
            node_count=64,
            threshold=64,
            pairing_time=2,
            signature_size=48,
            reply_timeout=300,
            candidate_count=1,
            shuffled_lists=False,
        )
    )


def _make_sanfermin_cappos_small():
    from ..protocols.sanfermin_cappos import SanFerminParameters
    from ..protocols.sanfermin_cappos_batched import make_sanfermin_cappos

    return make_sanfermin_cappos(
        SanFerminParameters(
            node_count=64,
            threshold=32,
            pairing_time=2,
            signature_size=48,
            timeout=150,
            candidate_count=4,
        )
    )


def _make_dfinity_small():
    from ..protocols.dfinity import DfinityParameters
    from ..protocols.dfinity_batched import make_dfinity

    return make_dfinity(DfinityParameters(), max_heights=64)


def _make_casper_small():
    from ..protocols.casper import CasperParameters
    from ..protocols.casper_batched import make_casper

    return make_casper(CasperParameters(), max_heights=16)


def _make_enr_small():
    from ..protocols.enr_gossiping import ENRParameters
    from ..protocols.enr_batched import make_enr

    return make_enr(
        ENRParameters(
            nodes=24,
            total_peers=4,
            max_peers=10,
            number_of_different_capabilities=5,
            cap_per_node=2,
            cap_gossip_time=5_000,
            time_to_leave=50_000,
            time_to_change=10_000_000,
            changing_nodes=1,
            discard_time=100,
        ),
        horizon_ms=30_000,
        capacity=1024,
    )


def _make_ethpow_small():
    raise NotImplementedError(
        "ethpow_batched is a standalone mining engine (EthPowState), not a "
        "BatchedProtocol on the generic message store"
    )


_reg("pingpong", "pingpong_batched", _make_pingpong_small)
_reg("p2pflood", "p2pflood_batched", _make_p2pflood_small)
_reg(
    "p2pflood_faults",
    "p2pflood_batched",
    _make_p2pflood_faults_small,
    note="fault-injection lane (wittgenstein_tpu.faults) traced on the "
    "p2pflood kernels; exercises SL406/SL407 on a non-neutral schedule",
)
_reg("paxos", "paxos_batched", _make_paxos_small)
_reg("slush", "avalanche_batched", _make_slush_small)
_reg("snowflake", "avalanche_batched", _make_snowflake_small)
_reg("handel", "handel_batched", _make_handel_small)
_reg("gsf", "gsf_batched", _make_gsf_small)
_reg("handeleth2", "handeleth2_batched", _make_handeleth2_small)
_reg("optimistic", "optimistic_p2p_signature_batched", _make_optimistic_small)
_reg("p2phandel", "p2phandel_batched", _make_p2phandel_small)
_reg("sanfermin", "sanfermin_batched", _make_sanfermin_small)
_reg("sanfermin_cappos", "sanfermin_cappos_batched", _make_sanfermin_cappos_small)
_reg("dfinity", "dfinity_batched", _make_dfinity_small)
_reg("casper", "casper_batched", _make_casper_small)
_reg("enr", "enr_batched", _make_enr_small)
_reg(
    "ethpow",
    "ethpow_batched",
    _make_ethpow_small,
    contract_checks=False,
    note="standalone chain-mining engine (EthPowState pytree, no generic "
    "message store); covered by tests/test_ethpow_batched.py instead",
)
