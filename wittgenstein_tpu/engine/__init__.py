"""The batched time-stepped TPU engine.

This is the TPU-native re-expression of the reference's discrete-event loop
(core Network.java:318-338 `runMs` / :586 `receiveUntil`): instead of an
event queue drained one message at a time on one thread, every (replica,
node) pair applies masked state transitions once per simulated millisecond,
under `jax.lax.scan`, `jax.vmap` over replicas, and `jax.sharding` over
devices.
"""

from .capacity import (
    CapacityEntry,
    load_capacity,
    lookup,
    size_from_hwm,
    sized_overrides,
    validate_table,
)
from .core import BatchedNetwork, Emission, FanOut, SimState, replicate_state, stack_states
from .density import LanePlan, NarrowLeaf, lane_plan, narrowest_int
from .protocol import (
    ENGINE_OWNED_FIELDS,
    HOST_HOOKS,
    KERNEL_HOOKS,
    BatchedProtocol,
)
from .rng import hash32, pseudo_delta

__all__ = [
    "BatchedNetwork",
    "BatchedProtocol",
    "CapacityEntry",
    "Emission",
    "FanOut",
    "LanePlan",
    "NarrowLeaf",
    "SimState",
    "hash32",
    "lane_plan",
    "load_capacity",
    "lookup",
    "narrowest_int",
    "pseudo_delta",
    "replicate_state",
    "size_from_hwm",
    "sized_overrides",
    "stack_states",
    "validate_table",
]
