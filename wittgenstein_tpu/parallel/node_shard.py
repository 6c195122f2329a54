"""Node-axis sharding: one simulation's node state split across devices.

The replica axis (replica_shard) scales the number of simulations; this
axis scales ONE simulation past a single device's memory — the analog of
the sequence/context parallelism axis in ML workloads (SURVEY §5).

Two layers:

1. **The real engine, GSPMD-partitioned** (`shard_state_by_node` +
   `run_ms_node_sharded`): every mutable per-node array of a batched
   simulation state — node columns, the aggregation protocols' channel
   and candidate buffers, counters — is annotated with a NamedSharding
   over the mesh's node axis, and the engine's existing `run_ms` program
   runs under XLA's SPMD partitioner, which inserts the peer-exchange
   collectives the cross-node scatters need (the scaling-book recipe:
   pick a mesh, annotate shardings, let XLA place collectives).  The
   result is bit-identical to the unsharded run — everything in the tick
   is integer or elementwise-float math, so partitioning cannot reorder
   a reduction.  Known limit, documented honestly: for scatter/gather
   ops with computed indices (the send path) XLA may choose to
   all-gather operands rather than all_to_all the update rows, so the
   per-device MEMORY win applies to the compute-heavy phases
   (candidate merge, scoring, commit) before it applies to the channel
   arrays; replacing those with explicit shard_map all_to_all exchange
   is the flagged next step (SURVEY §7).

2. **The shard_map spike** (`pingpong_progression`): the PingPong
   broadcast/reply pattern with explicit collectives — each device owns
   a block of node columns, computes its block's arrivals with the real
   latency models and counter RNG, and the witness's progression is a
   `psum` over the mesh axis.  Kept as the minimal, fully-explicit
   reference of the pattern.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.latency import LatencyStatic, vec_latency
from ..core.node import Node, build_node_columns
from ..core.registries import registry_network_latencies, registry_node_builders
from ..engine.rng import hash32, pseudo_delta
from ..utils.javarand import JavaRandom


def enable_node_sharding(net, mesh: Mesh, axis: str = "nodes",
                         exchange_capacity: Optional[int] = None):
    """Return a COPY of the engine whose aggregation-protocol send path
    commits through the explicit all_to_all exchange
    (BitsetAggBase._channel_commit_sharded) instead of GSPMD's
    gather-prone scatter partitioning.  Copying gives the engine a fresh
    jit-cache identity, so traces compiled for the mesh-less original can
    never be replayed for the sharded run (run_ms is jitted with the
    engine as an identity-keyed static argument).

    exchange_capacity bounds the per-destination exchange bucket (see
    _channel_commit_sharded: None = bit-exact worst-case capacity;
    a bound trades rare counted displacement for O(P) less transient
    exchange memory at large meshes)."""
    import copy

    net = copy.copy(net)
    net.node_mesh = mesh
    net.node_axis = axis
    net.exchange_capacity = exchange_capacity
    return net


def node_shard_bytes(state, n: int):
    """HBM proxy: {array_name: per_device_bytes} for every node-axis
    array of a sharded state, from the ACTUAL addressable shards (what
    the device really holds, not what the annotation promised)."""
    out = {}

    def visit(path, a):
        if hasattr(a, "addressable_shards") and a.ndim >= 1 and a.shape[0] == n:
            out[jax.tree_util.keystr(path)] = max(
                s.data.nbytes for s in a.addressable_shards
            )

    jax.tree_util.tree_map_with_path(visit, state)
    return out


# engine-owned message-store fields of SimState: the time wheel [W, B],
# its fill/occupancy summary [W] and the overflow lane [V] are indexed by
# arrival tick, not by node — they must be replicated even when a wheel
# dimension coincides with n_nodes.  (msg_received/msg_sent are NODE
# columns and deliberately absent.)
_MESSAGE_STORE_FIELDS = (
    ".msg_valid", ".msg_arrival", ".msg_from", ".msg_to", ".msg_type",
    ".msg_payload", ".whl_fill", ".ovf_valid", ".ovf_arrival", ".ovf_from",
    ".ovf_to", ".ovf_type", ".ovf_payload",
    # telemetry side-car: counter rows are mtype-/window-indexed, never
    # node-indexed — replicate even if a dimension coincides with n_nodes
    ".tele",
    # fault side-car: node-column lanes gather by from/to index, so a
    # replicated copy is correct everywhere, and the counter rows are
    # mtype-indexed like telemetry — replicate the whole schedule
    ".faults",
)


def shard_state_by_node(net, state, mesh: Mesh, axis: str = "nodes"):
    """Place ONE simulation's state onto the mesh with every [N, ...]
    array (leading dim == n_nodes) sharded over `axis` and everything
    else (scalars, the time-wheel message store, static tables)
    replicated.  Store fields are excluded BY NAME — the wheel's [W, B]
    shape can coincide with n_nodes without being node-indexed.

    Thin wrapper over mesh2d.MeshLayout with only the node axis active:
    the legacy 1D entry point and the 2D composition share one
    classification rule by construction."""
    from .mesh2d import MeshLayout

    layout = MeshLayout(mesh, replica_axis=None, node_axis=axis)
    return layout.place(net, state)


def run_ms_node_sharded(net, state, ms: int, layout=None):
    """Advance a node-sharded simulation `ms` milliseconds: the engine's
    own compiled program, partitioned by XLA over the state's shardings.
    Call with the output of shard_state_by_node (or pass a
    mesh2d.MeshLayout to place `state` here — sharding as a layout
    argument rather than a separate entry point)."""
    if layout is not None:
        state = layout.place(net, state)
    return net.run_ms(state, ms)


def _build_population(node_ct: int, node_builder_name, network_latency_name):
    nb = registry_node_builders.get_by_name(node_builder_name)
    latency = registry_network_latencies.get_by_name(network_latency_name)
    rd = JavaRandom(0)
    nodes = [Node(rd, nb) for _ in range(node_ct)]
    cols = build_node_columns(nodes, getattr(latency, "city_index", None))
    return latency, cols


def pingpong_progression(
    node_ct: int,
    query_times,
    mesh: Optional[Mesh] = None,
    axis: str = "nodes",
    node_builder_name: Optional[str] = None,
    network_latency_name: Optional[str] = None,
    seed: int = 0,
):
    """Witness pong counts at `query_times`.  With a mesh: node columns are
    sharded over `axis` via shard_map and the counts are psum-reduced; the
    result is bit-identical to the unsharded path."""
    latency, cols = _build_population(node_ct, node_builder_name, network_latency_name)
    qts = jnp.asarray(query_times, jnp.int32)

    # row 0 of the static table is the witness, replicated to every shard;
    # rows 1.. are the (shardable) node blocks
    x = np.asarray(cols["x"])
    y = np.asarray(cols["y"])
    el = np.asarray(cols["extra_latency"])
    ci = np.asarray(cols.get("city_idx", np.full(node_ct, -1)))
    ids = jnp.arange(node_ct, dtype=jnp.int32)

    def counts(x_b, y_b, el_b, ci_b, ids_b):
        """Pong-at-witness arrival times for this block, with the engine's
        send semantics: Ping multicast at t=1 with one shared seed +
        per-GLOBAL-destination pseudo delta (MultipleDestEnvelope), Pong
        replies one ms after delivery.  Static row 0 is the witness;
        gathers use local positions, RNG uses global ids."""
        static = LatencyStatic(
            jnp.concatenate([jnp.asarray(x[:1]), x_b]),
            jnp.concatenate([jnp.asarray(y[:1]), y_b]),
            jnp.concatenate([jnp.asarray(el[:1]), el_b]),
            jnp.concatenate([jnp.asarray(ci[:1]), ci_b]),
        )
        lpos = jnp.arange(ids_b.shape[0], dtype=jnp.int32) + 1
        zero = jnp.zeros_like(lpos)
        ping_seed = hash32(jnp.int32(seed), jnp.int32(1), jnp.int32(0xA0))
        d1 = pseudo_delta(ids_b, ping_seed)
        arr1 = 1 + vec_latency(latency, static, zero, lpos, d1)
        pong_seed = hash32(jnp.int32(seed), arr1 + 1, ids_b, jnp.int32(0xB0))
        d2 = pseudo_delta(zero, pong_seed)
        arr = arr1 + 1 + vec_latency(latency, static, lpos, zero, d2)
        return jnp.sum(
            (arr[None, :] <= qts[:, None]).astype(jnp.int32), axis=1
        )

    if mesh is None:
        return counts(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(el), jnp.asarray(ci), ids
        )

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(),
    )
    def sharded(x_b, y_b, el_b, ci_b, ids_b):
        local = counts(x_b, y_b, el_b, ci_b, ids_b)
        return jax.lax.psum(local, axis)

    return sharded(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(el), jnp.asarray(ci), ids
    )
