"""Batched time-stepped simulation core.

Re-expression of the reference DES (core Network.java) as a synchronous
per-millisecond state transition suitable for TPUs:

  * node state is a struct-of-arrays pytree of `[N]` columns
    (Node.java:22-88 fields become columns);
  * in-flight messages live in a TIME WHEEL — `[W, B]` buckets keyed by
    `arrival mod W` plus a small `[V]` overflow lane for beyond-horizon
    arrivals — the calendar-queue analog of MessageStorage
    (Network.java:116-299, which exists precisely so the reference never
    scans an unsorted event list).  A tick's delivery reads only its own
    bucket row(s) and the overflow lane: O(B + V) per tick instead of
    O(C) over a flat ring (see docs/engine_timewheel.md);
  * per-destination latency jitter comes from the reference's own xorshift
    counter hash (rng.pseudo_delta), so multicast costs no per-dest state,
    exactly like MultipleDestEnvelope (Envelope.java:46-56);
  * the event loop is `lax.scan` over milliseconds; one step delivers every
    due message, runs the protocol's vectorized handlers, fires periodic
    masks, and appends emissions (receiveUntil/nextMessage,
    Network.java:533-632, without the queue);
  * `jax.vmap` over the leading replica axis replaces RunMultipleTimes'
    sequential reseeded runs (RunMultipleTimes.java:48-63).

Semantics deltas vs the oracle (documented, by design — SURVEY §7):
  * same-millisecond deliveries are simultaneous (no LIFO order inside a
    ms); protocols must use commutative per-tick updates;
  * `run_ms(ms)` processes ticks [time, time+ms) — arrivals at exactly
    time+ms land at the start of the next call (the oracle includes the
    boundary tick in the earlier call);
  * randomness is counter-based, so message *distributions* match the
    oracle but individual draws differ.

Protocols see the wheel only through the delivery VIEW: `deliver` still
receives `state.msg_*` columns aligned with `deliver_mask` — the engine
gathers the due bucket rows + the overflow lane into flat `[D]` arrays
before the call and restores the wheel storage afterwards, so protocol
delivery kernels are layout-agnostic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.geo import MAX_X
from ..core.latency import LatencyStatic, NetworkLatency, vec_latency
from ..faults.state import (
    FaultConfig,
    deliver_suppress,
    inflate_latency,
    neutral_fault_state,
    send_suppress,
)
from ..ops.bitops import (
    bitops_backend,
    lowest_set_bit,
    pack_bool_words,
    popcount_words,
)
from ..ops.select import same_key_rank
from ..telemetry.state import (
    TelemetryConfig,
    count_by_type,
    init_telemetry,
    record_snapshot,
)
from .density import lane_plan
from .rng import hash32, pseudo_delta

MAX_PARTITIONS = 4
INT_MAX = np.int32(2**31 - 1)

# default wheel horizon, ms: covers the WAN latency models' bulk; rarer
# longer delays (heavy jitter tails, Mathis throughput delays, protocol
# timeouts) spill to the overflow lane, which stays exact — the wheel is
# a fast path, never a correctness boundary
DEFAULT_WHEEL_ROWS = 512

# named-scope phase map (docs/profiling.md): every engine phase is wrapped
# in jax.named_scope so jaxprs, HLO metadata and device profiles attribute
# ops to the phase that traced them.  Scopes are TRACE-TIME metadata only —
# they cannot change a single computed bit.  Sub-phases nest (e.g. a fault
# send check inside the send path shows up as "witt.send/witt.faults.send"),
# so consumers should substring-match.  A protocol's own scopes live beside
# the protocol, which states them as `REQUIRED_SCOPES` (simlint SL601).
ENGINE_PHASE_SCOPES = {
    "delivery": "witt.delivery",
    "fused_step": "witt.fused_step",
    "protocol_deliver": "witt.protocol_deliver",
    "send": "witt.send",
    "protocol_tick": "witt.protocol_tick",
    "beat": "witt.beat",
    "post": "witt.post",
    "telemetry": "witt.telemetry",
    "jump": "witt.jump",
    "faults_send": "witt.faults.send",
    "faults_deliver": "witt.faults.deliver",
}

# sub-scopes of the generic message store (the time wheel, its overflow
# lane and the delivery view: every protocol that sends through
# `apply_emission`), nested under the engine phase that runs them
# (witt.send, witt.delivery, witt.fused_step, a protocol's tick).
STORE_SCOPES = {
    "insert": "witt.store.insert",  # slot ranks, the wheel and overflow planes' scatters
    "view": "witt.store.view",  # the due rows and the overflow lane gathered flat
    "repack": "witt.store.repack",  # due entries cleared, visited rows dense again
}

# the store's fan-out form of a broadcast (`FanOut`, `apply_fanout`): the
# firing senders to the front, a round's `capacity x receivers` rows made
# and inserted (the insert's own scope nests inside), nested under
# witt.send.  Required of the protocols that emit a `FanOut` (their
# `REQUIRED_SCOPES`).
FANOUT_SCOPES = {
    "expand": "witt.store.fanout",  # a broadcast's rows, for the senders that fire
}

# a plain emission that states a `capacity` (`Emission`,
# `_apply_emission_rounds`): the firing rows numbered to the front and a
# round's columns read at their numbers, OUTSIDE the insert's own scope
# (each round's insert nests under witt.send beside it).  Required of the
# protocols whose emissions state one (their `REQUIRED_SCOPES`).
EMISSION_SCOPES = {
    "compact": "witt.store.compact",  # the firing rows to the front, a round's reads
}

# "can this row reach its receiver": both ends up, on one side of every
# partition line (`SimState.partition_x`), the latency under the discard
# time.  Asked where a row is sent (`latency_arrivals` through
# `_apply_emission_impl`, and a fan-out round's grid, `_store_grid`) and
# again where it is due (`delivery_view`'s `checked`), nested under the
# phase that asks.
REACH_SCOPES = {
    "send": "witt.reach.send",  # the `ok` mask of a send's rows, and the count of what it masks
    "deliver": "witt.reach.deliver",  # the delivery's re-check of the due rows
}


# what a send into the FLAT store writes (`latency_arrivals`, `_insert_rows`):
# the leaves `apply_emissions` carries through its branch under a due view
_SEND_FIELDS = (
    "send_ctr", "msg_sent", "bytes_sent", "ovf_valid", "ovf_arrival", "ovf_from",
    "ovf_to", "ovf_type", "ovf_payload", "msg_head", "dropped", "faults",
)

# the wheel's planes: written by a send into a store with a wheel, read and
# never written by a delivery under the wheel's due view (the due row is
# emptied after the branch)
_WHEEL_FIELDS = (
    "msg_valid", "msg_arrival", "msg_from", "msg_to", "msg_type", "msg_payload", "whl_fill",
)


# and the lane's planes that a delivery reads and never writes
_LANE_READ_FIELDS = ("ovf_arrival", "ovf_from", "ovf_to", "ovf_type", "ovf_payload")


class Census(NamedTuple):
    """The work census of a row: int32 scalars counted on the device where
    the work happens, always on (PERF.md section 3, docs/observability.md).
    Never read by the dynamics, no RNG is drawn for them and `send_ctr` is
    untouched, so every other leaf is what it is without them.  The run
    cache reduces the rows' census to one vector a chunk (`chunk_census`)
    and folds it into `run_cache_info()`; a mechanism a protocol lacks
    stays 0.  A new capacity takes a slot here, not a leaf of its own.

    Sums grow by what a step did; peaks keep the most seen since the row
    began, each against the static limit `BatchedNetwork.census_limits`
    names.  Rows accepted into the store are `SimState.msg_head`'s growth
    and Handel's landing peak is `proto["landing_peak"]`: no slot here."""

    steps: jnp.ndarray  # executed steps (loop trips that ran `step`)
    view_overflow_steps: jnp.ndarray  # steps whose due rows passed `due_view_rows`: the whole lane
    landed_rows: jnp.ndarray  # rows the claim let land of the two every-tick channel sends (`_send_stacked`): Handel's sender-rows send and GSF's accelerated calls on the level axis
    extra_commit_rounds: jnp.ndarray  # their commit rounds beyond a send's first (`landing_capacity(M)` passed by the sender rows, `firing_capacity(rows)` on the level axis)
    fired_rows: jnp.ndarray  # rows with their mask set of the sends that run over the firing rows: an every-tick channel send (`_send_fired`) or a store emission that states a capacity (`_apply_emission_rounds`); no program has both, the channel protocols never insert into the store
    firing_overflows: jnp.ndarray  # such sends whose fired rows passed their capacity (`firing_capacity`, `Emission.capacity`): a second round
    fanout_senders: jnp.ndarray  # senders a fan-out expanded into their rows (`apply_fanout`)
    fanout_overflows: jnp.ndarray  # fan-outs whose firing senders passed their capacity: another round
    masked_sends: jnp.ndarray  # rows a send's mask set and its `ok` did not: an end down, across a line, past the discard time (the oracle's `dropped`)
    discarded_rows: jnp.ndarray  # due rows the delivery did not deliver (`due & ~deliver`): an end went down or a line was drawn under them
    due_rows_peak: jnp.ndarray  # most rows due in a step (the lane's, or the wheel row's), against `due_view_rows`
    wheel_fill_peak: jnp.ndarray  # fullest wheel row after a step's inserts, against `wheel_slots`
    lane_live_peak: jnp.ndarray  # most live lane rows after a step's inserts, against `overflow_capacity`
    firing_peak: jnp.ndarray  # most rows one such send fired, against `firing_capacity` or the protocol's largest `Emission.capacity`
    fanout_peak: jnp.ndarray  # most senders one fan-out fired, against the protocol's largest `FanOut.capacity`


CENSUS_PEAKS = ("due_rows_peak", "wheel_fill_peak", "lane_live_peak", "firing_peak", "fanout_peak")
_CENSUS_ROW_SUMS = (
    "view_overflow_steps", "landed_rows", "extra_commit_rounds", "fired_rows", "firing_overflows",
    "fanout_senders", "fanout_overflows", "masked_sends", "discarded_rows",
)

# what a chunk's census vector holds, in order (`chunk_census`): the sums
# first (a chunk's own, from the rows' growth), then the peaks
CENSUS_VECTOR = (
    "steps", "store_rows", "view_overflow_steps", "landed_rows", "extra_commit_rounds",
    "fired_rows", "firing_overflows", "fanout_senders", "fanout_overflows",
    "masked_sends", "discarded_rows",
    "due_rows_peak", "wheel_fill_peak", "lane_live_peak", "firing_peak", "fanout_peak",
    "landing_peak",
)
CENSUS_VECTOR_PEAKS = CENSUS_PEAKS + ("landing_peak",)


def census_add(state, **amounts):
    """`state` with `amounts` in its census: added to a sum, the larger
    kept of a peak.  A state that carries no census (built without
    `init_state`) is returned as it is."""
    census = state.census
    if not isinstance(census, Census):
        return state
    new = {}
    for name, amount in amounts.items():
        old = getattr(census, name)
        amount = jnp.asarray(amount).astype(jnp.int32)
        new[name] = jnp.maximum(old, amount) if name in CENSUS_PEAKS else old + amount
    return state._replace(census=census._replace(**new))


def chunk_census(before, after) -> jnp.ndarray:
    """One int32 vector (`CENSUS_VECTOR`) for the chunk that took the
    rows `before` to `after`: sums as the rows' growth (steps by max, the
    rows being in lockstep; the others summed over rows), peaks as the
    most any row has seen."""
    zero = jnp.int32(0)
    old, new, proto = before.census, after.census, after.proto
    have = isinstance(old, Census) and isinstance(new, Census)

    def grown(name, reduce=jnp.sum):
        return reduce(getattr(new, name) - getattr(old, name)) if have else zero

    def peak(name):
        return jnp.max(getattr(new, name)) if have else zero

    values = {
        "steps": grown("steps", jnp.max),
        "store_rows": jnp.sum(after.msg_head - before.msg_head),
        **{name: grown(name) for name in _CENSUS_ROW_SUMS},
        **{name: peak(name) for name in CENSUS_PEAKS},
        "landing_peak": (
            jnp.max(proto["landing_peak"])
            if isinstance(proto, dict) and "landing_peak" in proto
            else zero
        ),
    }
    return jnp.stack([values[name].astype(jnp.int32) for name in CENSUS_VECTOR])


class SimState(NamedTuple):
    """Per-replica simulation state; every field is a jnp array so the whole
    thing is a pytree (checkpointable for free — an upgrade over the
    reference, whose Envelope.java:55 only muses about serialization)."""

    time: jnp.ndarray  # int32 scalar, ms (Network.java:46-49)
    seed: jnp.ndarray  # int32 scalar, per-replica base seed
    send_ctr: jnp.ndarray  # int32 scalar: per-send-event counter (seeds)
    # node columns (Node.java:22-88)
    down: jnp.ndarray  # bool[N]
    done_at: jnp.ndarray  # int32[N]
    msg_received: jnp.ndarray  # int32[N]
    msg_sent: jnp.ndarray  # int32[N]
    bytes_received: jnp.ndarray  # int32[N]
    bytes_sent: jnp.ndarray  # int32[N]
    # latency inputs (per replica so vmap covers heterogeneous layouts)
    x: jnp.ndarray  # int32[N]
    y: jnp.ndarray  # int32[N]
    extra_latency: jnp.ndarray  # int32[N]
    city_idx: jnp.ndarray  # int32[N]
    # partitions (Network.java:639-707)
    partition_x: jnp.ndarray  # int32[MAX_PARTITIONS], INT_MAX = unused
    # time wheel [W, B]: row r holds messages with eff-arrival ≡ r (mod W).
    # The msg_* names are shared with the delivery view handed to
    # protocol.deliver (flat [D] gathers of the due rows + overflow).
    # id/type lanes are STORED at the engine's lane_plan dtypes (int16
    # ids when N fits, int8/int16 types per the mtype count) and widened
    # to int32 at the delivery-view gather — see engine.density
    msg_valid: jnp.ndarray  # bool[W, B]
    msg_arrival: jnp.ndarray  # int32[W, B]
    msg_from: jnp.ndarray  # lanes.idx[W, B]
    msg_to: jnp.ndarray  # lanes.idx[W, B]
    msg_type: jnp.ndarray  # lanes.mtype[W, B]
    msg_payload: jnp.ndarray  # int32[W, B, P]
    whl_fill: jnp.ndarray  # int32[W]: valid entries per row (dense prefix)
    # overflow lane [V]: beyond-horizon arrivals + full-row spill; scanned
    # (arrival <= t) every tick like the old flat ring, but V << W*B
    ovf_valid: jnp.ndarray  # bool[V]
    ovf_arrival: jnp.ndarray  # int32[V]
    ovf_from: jnp.ndarray  # lanes.idx[V]
    ovf_to: jnp.ndarray  # lanes.idx[V]
    ovf_type: jnp.ndarray  # lanes.mtype[V]
    ovf_payload: jnp.ndarray  # int32[V, P]
    msg_head: jnp.ndarray  # int32 scalar: monotone sent-message counter
    dropped: jnp.ndarray  # int32 scalar: wheel+overflow overflow count
    proto: Any  # protocol-defined pytree
    # telemetry side-car: () when the engine's TelemetryConfig is unset
    # (zero pytree leaves, zero traced ops), a telemetry.TelemetryState
    # of pure counters otherwise — never read by sim dynamics, so an
    # instrumented run is bit-identical in every other field
    tele: Any = ()
    # fault side-car: () when the engine's FaultConfig is unset, a
    # faults.FaultState schedule + counters otherwise.  Unlike tele it IS
    # read by sim dynamics (that is its job) — but the neutral schedule
    # makes every fault predicate constant-false, so a fault-enabled run
    # on neutral_fault_state is bit-identical too (simlint SL406)
    faults: Any = ()
    # the work census (`Census`): counters beside the work, read by nothing
    # in the dynamics; () on a state built without `init_state`
    census: Any = ()


@dataclasses.dataclass
class Emission:
    """A batched send request: K candidate messages (the analog of one
    Network.send call, Network.java:341-447).

    mask[K] selects real sends; from_idx/to_idx[K] are node ids; payload is
    [K, P] (or None when P=0).  mtype may be a static int or a per-row
    [K] array (protocols with per-level message types).  arrival, when
    given, bypasses the latency model AND sender counters (the analog of
    sendArriveAt, Network.java:419-422, used for task-style self-messages);
    declare such types with msg_size 0 so receiver counters skip them too.

    `capacity` (static, as a `FanOut`'s): stated by a protocol that knows
    from its own shape that few of the K rows fire on any one step.  The
    store then numbers the rows whose mask is set to the front and takes
    them `capacity` a round through the latency draw and the insert, as
    many rounds as they take (`BatchedNetwork._apply_emission_rounds`):
    every leaf but the census is what the K rows would have left, bit for
    bit.  A capacity of K or more is the one pass over all K rows, counted
    in the census.  None, the default: all K rows in one pass."""

    mask: jnp.ndarray
    from_idx: jnp.ndarray
    to_idx: jnp.ndarray
    mtype: "int | jnp.ndarray"
    payload: Optional[jnp.ndarray] = None
    send_time: Optional[jnp.ndarray] = None  # default: state.time + 1
    arrival: Optional[jnp.ndarray] = None  # explicit arrival times [K]
    capacity: Optional[int] = None  # rows stored a round (static); None: all K at once


@dataclasses.dataclass
class FanOut:
    """A step's broadcasts of one message type, by their SENDERS: entry e
    of the S, where `mask[e]`, is one message from `from_idx[e]` to every
    node of the static list `receivers`, with the entry's payload row and
    send time.  What `Emission` spells as S x len(receivers) rows, most of
    them masked out, the store makes for the entries that fire
    (`BatchedNetwork.apply_fanout`): `capacity` of them a round, read
    from the deployment's shape, and another round where more fire,
    counted by the census.  The stored rows, the counters and the latency
    draws (keyed by destination id, not by row) are those of `dense()`,
    bit for bit.

    `events` > 1: the entries lie in that many equal groups, each a send
    event of its own (one per-event counter each, as one `Emission` each
    would tick): Dfinity's votes, one group a producer's slot, compacted
    over (slot, sender) pairs."""

    mask: jnp.ndarray  # bool[S]
    from_idx: jnp.ndarray  # int[S]
    receivers: Any  # int[R], static: the same list for every entry
    mtype: int
    capacity: int  # entries expanded a round (static)
    payload: Optional[jnp.ndarray] = None  # [S, P]
    send_time: Optional[jnp.ndarray] = None  # [S]; default: state.time + 1
    events: int = 1

    def dense(self) -> "list[Emission]":
        """The plain spelling: one `Emission` of S/events x R rows an event."""
        r = len(self.receivers)
        per = self.mask.shape[0] // self.events
        out = []
        for j in range(self.events):
            at = slice(j * per, (j + 1) * per)
            out.append(
                Emission(
                    mask=jnp.repeat(self.mask[at], r),
                    from_idx=jnp.repeat(self.from_idx[at], r),
                    to_idx=jnp.tile(jnp.asarray(self.receivers), per),
                    mtype=self.mtype,
                    payload=None if self.payload is None else jnp.repeat(self.payload[at], r, axis=0),
                    send_time=None if self.send_time is None else jnp.repeat(self.send_time[at], r),
                )
            )
        return out


_EMISSION_ARRAYS = ("mask", "from_idx", "to_idx", "mtype", "payload", "send_time", "arrival")
_FANOUT_STATICS = ("receivers", "mtype", "capacity", "events")


def _emission_leaves(emissions):
    """A step's emissions as (static part, arrays) so that they can leave
    a `lax.cond`: per emission its class, the names of its array fields
    and its static fields (an `Emission`'s static `mtype` and `capacity`,
    a `FanOut`'s receivers, type, capacity and events), and the arrays
    themselves."""
    shape, leaves = [], []
    for em in emissions:
        if isinstance(em, FanOut):
            static = {f: getattr(em, f) for f in _FANOUT_STATICS}
        else:
            static = {"mtype": em.mtype} if isinstance(em.mtype, int) else {}
            if em.capacity is not None:
                static["capacity"] = em.capacity
        arrays = {
            f: getattr(em, f)
            for f in _EMISSION_ARRAYS
            if f not in static and getattr(em, f, None) is not None
        }
        shape.append((type(em), tuple(arrays), static))
        leaves.append(tuple(arrays.values()))
    return shape, tuple(leaves)


def _kept(new, old, fields) -> None:
    """Trace-time check that `new` holds `old`'s very arrays in `fields`:
    what a due view's branch leaves out of its carry, nothing inside it
    may have written."""
    moved = [f for f in fields if getattr(new, f) is not getattr(old, f)]
    if moved:
        raise AssertionError(f"written inside a due view's branch: {moved}")


def _emissions_of(shape, leaves):
    return [
        cls(**static, **dict(zip(names, arrays)))
        for (cls, names, static), arrays in zip(shape, leaves)
    ]


class BatchedNetwork:
    """The engine: binds a latency model + protocol to compiled step/run
    functions.  One instance is reusable across replica counts (everything
    batched lives in SimState).

    Message storage is a time wheel `[wheel_rows, wheel_slots]` plus an
    `[overflow_capacity]` lane (see module docstring).  `wheel_rows=0`
    selects FLAT mode: everything goes through the overflow lane, which
    reproduces the old full-scan ring exactly — used by protocols whose
    scheduling is dominated by far-future explicit arrivals (Casper's 8 s
    slots, ENR's wake calendar) and by the agg protocols whose messaging
    bypasses the generic ring entirely.  `capacity` keeps its historical
    meaning (total in-flight budget) and sizes the wheel/overflow defaults.
    """

    def __init__(
        self,
        protocol: "BatchedProtocol",
        latency: NetworkLatency,
        n_nodes: int,
        capacity: int = 1 << 14,
        msg_discard_time: int = int(INT_MAX),
        throughput=None,  # optional core.throughput.MathisNetworkThroughput
        wheel_rows: Optional[int] = None,
        wheel_slots: Optional[int] = None,
        overflow_capacity: Optional[int] = None,
        telemetry: Optional[TelemetryConfig] = None,
        faults: Optional["FaultConfig"] = None,
        fuse_step: bool = False,
        narrow_lanes: Optional[bool] = None,
        batched_jumps: bool = False,
        due_view_rows: "Optional[int | tuple]" = None,
    ):
        self.protocol = protocol
        self.latency = latency
        self.n_nodes = n_nodes
        self.capacity = capacity
        self.msg_discard_time = msg_discard_time
        self.throughput = throughput
        # STATIC switch for the fused delivery+tick step (_step_core_fused,
        # docs/engine_fused_step.md): one traced phase instead of
        # delivery -> send -> tick with full-state round-trips between
        # them, plus a static empty-row clear that replaces the generic
        # sort/repack when the delivery window is a single row.
        # Bit-identical to the unfused path by construction (pinned by
        # tests/test_step_fusion.py); the unfused path stays the default
        # because its per-phase scopes are what the SL601 annotation
        # checks attribute against.
        self.fuse_step = bool(fuse_step)
        # STATIC switch for the batched consensus-jump loop
        # (_run_ms_batched_jumps, docs/engine_timewheel.md): replicas
        # advance time in lockstep and the whole batch jumps to the
        # minimum next-arrival across the replica axis.  Bit-identical to
        # the ungated vmapped fallback by construction (each lane steps
        # at exactly its own singleton tick set); default-off pending the
        # paired A/B in BENCH_FLOOR.json (profiling.md lever ledger)
        self.batched_jumps = bool(batched_jumps)
        # STATIC switch for the FLAT store's due view (`_deliver_and_clear`,
        # `apply_emissions`, docs/batched_blockchain_design.md): None hands
        # protocol.deliver the whole lane on every executed step; an int K
        # hands it the step's DUE rows, compacted in lane order to K rows
        # (the whole lane again on a step with more than K due), and skips
        # a step's emissions when every mask is empty.  Bit-identical to
        # None by construction (tests/test_casper_batched.py); what it buys
        # is a step that costs what is due, not what the lane holds.  On a
        # store WITH a wheel the view is of the due wheel row (a dense
        # prefix of `whl_fill[row]` entries, every one of them due): a tuple
        # of ascending sizes, a step taking the smallest its row fits, the
        # whole row's `wheel_slots` where it fits none (`_deliver_row_view`)
        if due_view_rows is None:
            self.due_view_rows = None
        elif isinstance(due_view_rows, (tuple, list)):
            self.due_view_rows = tuple(int(k) for k in due_view_rows)
        else:
            self.due_view_rows = int(due_view_rows)
        # STATIC switch: None compiles the exact pre-telemetry program
        # (state.tele is an empty pytree); a TelemetryConfig threads the
        # counter side-car through every send/deliver/jump site below
        self.telemetry = telemetry
        # STATIC switch for the fault-injection lanes (faults/state.py),
        # same pattern: None leaves state.faults an empty pytree and the
        # two choke points below trace zero fault ops
        self.faults = faults
        self.payload_width = protocol.PAYLOAD_WIDTH
        sizes = [protocol.msg_size(t) for t in range(protocol.n_msg_types())]
        self._msg_sizes = np.asarray(sizes, dtype=np.int32)
        # STATIC storage dtype plan for the message lanes (engine.density,
        # docs/density.md): ids/types are CARRIED narrow and widened back
        # to int32 at the delivery-view gather, so every protocol kernel
        # still sees the exact int32 program it was verified against.
        # narrow_lanes=False pins the historical all-int32 lanes — the
        # baseline side of the bit-identity sweep (tests/test_density.py)
        self.lanes = lane_plan(n_nodes, protocol.n_msg_types(), narrow_lanes)

        if wheel_rows is None:
            wheel_rows = DEFAULT_WHEEL_ROWS
        self.flat = wheel_rows == 0
        if self.flat:
            # degenerate 1x1 wheel keeps the pytree shape uniform; inserts
            # never target it, so per-tick cost is the overflow scan = the
            # old flat-ring behavior, bit for bit
            self.wheel_rows = 1
            self.wheel_slots = 1
            self.overflow_capacity = (
                capacity if overflow_capacity is None else overflow_capacity
            )
            if self.due_view_rows is not None and not (
                isinstance(self.due_view_rows, int)
                and 0 < self.due_view_rows < self.overflow_capacity
            ):
                raise ValueError(
                    f"due_view_rows={self.due_view_rows} must lie inside the "
                    f"lane's {self.overflow_capacity} rows"
                )
        else:
            if wheel_rows % 32:
                raise ValueError(
                    f"wheel_rows={wheel_rows} must be a multiple of 32 "
                    "(occupancy is scanned as packed uint32 words)"
                )
            self.wheel_rows = wheel_rows
            self.wheel_slots = (
                max(64, -(-2 * capacity // wheel_rows))
                if wheel_slots is None
                else wheel_slots
            )
            # capped: the lane serves far-future arrivals + full-row spill,
            # and it is scanned every tick — per-tick delivery cost must
            # not scale with total capacity C (the wheel's whole point)
            self.overflow_capacity = (
                max(128, min(1024, capacity // 8))
                if overflow_capacity is None
                else overflow_capacity
            )
            if self.due_view_rows is not None:
                if isinstance(self.due_view_rows, int):
                    self.due_view_rows = (self.due_view_rows,)
                tiers = self.due_view_rows
                if (
                    not tiers
                    or list(tiers) != sorted(set(tiers))
                    or not 0 < tiers[0] <= tiers[-1] < self.wheel_slots
                ):
                    raise ValueError(
                        f"due_view_rows={tiers} must ascend inside a wheel row's "
                        f"{self.wheel_slots} slots"
                    )
                if self._window() != 1:
                    raise ValueError(
                        "the wheel's due view needs a row visited once (TIME_QUANTUM 1)"
                    )

    # -- state construction (host-side) -------------------------------------
    def init_state(
        self, cols: dict, seed: int, proto: Any, down=None, partition=None
    ) -> SimState:
        """Build a fresh single-replica state from node columns
        (core.node.build_node_columns output).

        `down` (bool[N], default all-up) marks nodes dead for the WHOLE
        run — the batched twin of the oracle nodes `choose_bad_nodes`
        selects, which `Network.run_ms` never start()s.  Because the mask
        is set before the protocol's initial emissions are applied, a
        down node (a) never sends: its initial and later emissions fail
        `latency_arrivals`' send-time check, exactly like the oracle's
        `from_node.is_down()` (Network.java:476-487) — though msg_sent
        still ticks for the *attempts other protocols make toward it*,
        never for its own, since a node that receives nothing emits
        nothing; (b) never receives: the delivery view discards due rows
        addressed to it (Network.java:606); and (c) never reaches
        done_at > 0, so done counts and CDFs exclude it.  Pinned
        cross-protocol by tests/test_faults.py::test_statically_down_nodes.
        For crash/recovery *during* a run, see wittgenstein_tpu.faults.

        `partition` (a share of the x axis, default none) draws that line
        (`BatchedNetwork.partition`) before the initial emissions too, so
        that their crossing rows are masked at the send and counted in
        `census.masked_sends` like every later one; a line drawn on the
        state this returns finds them in flight, and the delivery
        discards them where they are due (`census.discarded_rows`)."""
        n, p = self.n_nodes, self.payload_width
        w, b, v = self.wheel_rows, self.wheel_slots, self.overflow_capacity
        zi = lambda shape: jnp.zeros(shape, dtype=jnp.int32)
        state = SimState(
            time=jnp.int32(0),
            seed=jnp.int32(np.int64(seed) & 0x7FFFFFFF),
            send_ctr=jnp.int32(0),
            down=(
                jnp.zeros(n, dtype=bool)
                if down is None
                else jnp.asarray(down, dtype=bool)
            ),
            done_at=zi(n),
            msg_received=zi(n),
            msg_sent=zi(n),
            bytes_received=zi(n),
            bytes_sent=zi(n),
            x=jnp.asarray(cols["x"], jnp.int32),
            y=jnp.asarray(cols["y"], jnp.int32),
            extra_latency=jnp.asarray(cols["extra_latency"], jnp.int32),
            city_idx=jnp.asarray(cols.get("city_idx", np.full(n, -1)), jnp.int32),
            partition_x=jnp.full(MAX_PARTITIONS, INT_MAX, dtype=jnp.int32),
            msg_valid=jnp.zeros((w, b), dtype=bool),
            msg_arrival=jnp.full((w, b), INT_MAX, dtype=jnp.int32),
            msg_from=jnp.zeros((w, b), dtype=self.lanes.idx),
            msg_to=jnp.zeros((w, b), dtype=self.lanes.idx),
            msg_type=jnp.zeros((w, b), dtype=self.lanes.mtype),
            msg_payload=zi((w, b, p)),
            whl_fill=zi(w),
            ovf_valid=jnp.zeros(v, dtype=bool),
            ovf_arrival=jnp.full(v, INT_MAX, dtype=jnp.int32),
            ovf_from=jnp.zeros(v, dtype=self.lanes.idx),
            ovf_to=jnp.zeros(v, dtype=self.lanes.idx),
            ovf_type=jnp.zeros(v, dtype=self.lanes.mtype),
            ovf_payload=zi((v, p)),
            msg_head=jnp.int32(0),
            dropped=jnp.int32(0),
            proto=proto,
            tele=(
                init_telemetry(self.telemetry, self.protocol.n_msg_types())
                if self.telemetry is not None
                else ()
            ),
            faults=(
                neutral_fault_state(n, self.protocol.n_msg_types())
                if self.faults is not None
                else ()
            ),
            census=Census(*(jnp.int32(0) for _ in Census._fields)),
        )
        if partition is not None:
            state = self.partition(state, partition)
        for em in self.protocol.initial_emissions(self, state):
            state = self._apply_one(state, em)
        return census_add(state, **self._store_fill(state))

    def census_limits(self) -> dict:
        """The static limit each peak of the census is read against
        (`CENSUS_VECTOR_PEAKS`): the store's here, the protocol's own
        from its `census_limits`; 0 where the mechanism is lacking."""
        return {
            # a wheel's due view is a tuple of sizes: the largest
            "due_rows_peak": max(np.atleast_1d(self.due_view_rows or 0).tolist()),
            "fanout_peak": 0,
            "wheel_fill_peak": 0 if self.flat else self.wheel_slots,
            "lane_live_peak": self.overflow_capacity,
            "firing_peak": 0,
            "landing_peak": 0,
            **self.protocol.census_limits(),
        }

    def _store_fill(self, state: SimState) -> dict:
        """The store's fill as the census's peaks take it, after a step's
        inserts: occupancy only grows there, and a step inserts after it
        has cleared."""
        fill = {"lane_live_peak": jnp.sum(state.ovf_valid.astype(jnp.int32))}
        if not self.flat:
            fill["wheel_fill_peak"] = jnp.max(state.whl_fill)
        return fill

    def _census_step(self, state: SimState) -> SimState:
        """What one executed step adds to the census (called before the
        time advance, from every loop)."""
        return census_add(state, steps=1, **self._store_fill(state))

    def stable_cache_key(self) -> tuple:
        """Explicit identity for compiled-program caches: protocol name +
        the static knobs that shape the trace, free of process-lifetime
        id()s, so it is also the cross-process identity the durable
        compile store keys on.  Two engines with equal stable keys trace
        the same program *provided* their behavior params round-trip
        through repr/str — true for the dataclass params and named
        latency models this codebase builds; an exotic latency whose
        str() hides state must not be served from the store (give it a
        distinguishing __str__)."""
        return (
            type(self.protocol).__name__,
            repr(getattr(self.protocol, "params", None)),
            str(self.latency),
            self.n_nodes,
            self.capacity,
            self.wheel_rows,
            self.wheel_slots,
            self.overflow_capacity,
            int(self.msg_discard_time),
            type(self.throughput).__name__ if self.throughput else None,
            getattr(self, "node_axis", None),
            self.telemetry.key() if self.telemetry is not None else None,
            self.faults.key() if self.faults is not None else None,
            self.fuse_step,
            self.batched_jumps,
            self.due_view_rows,
            self.lanes.key(),
            # the bitset-kernel backend is read from the environment at
            # trace time (WITT_BITOPS) — fold it in so a flipped override
            # can't be served a stale compiled program
            bitops_backend(),
        )

    def cache_key(self) -> tuple:
        """The in-process identity (parallel.replica_shard's run cache):
        `stable_cache_key` plus id(protocol), id(latency) and the node
        mesh's id, which disambiguate instances carrying different
        behavior params; cached programs keep those objects alive, so
        the ids cannot be recycled while an entry lives."""
        mesh = getattr(self, "node_mesh", None)
        return self.stable_cache_key() + (
            id(self.protocol),
            id(self.latency),
            id(mesh) if mesh is not None else None,
        )

    def _scope(self, name: str, scopes: dict = ENGINE_PHASE_SCOPES):
        """jax.named_scope for phase `name` of `scopes`: the engine's
        (ENGINE_PHASE_SCOPES, STORE_SCOPES, ...) or a table a protocol
        keeps beside itself."""
        return jax.named_scope(scopes[name])

    def with_telemetry(
        self, state: SimState, telemetry: TelemetryConfig
    ) -> "tuple[BatchedNetwork, SimState]":
        """Instrument an ALREADY-BUILT simulation: returns an engine copy
        carrying the TelemetryConfig (fresh jit identity, like
        enable_node_sharding's copy) and the state with a counter
        side-car attached.  The side-car's per-mtype `sent` is seeded
        with the current store census, so the store invariant
        (sent == delivered + discarded + dropped + pending) holds from
        the first tick even when initial emissions predate
        instrumentation.  Works on single and batched states (leading
        axes broadcast)."""
        import copy

        net = copy.copy(self)
        net.telemetry = telemetry
        t = self.protocol.n_msg_types()
        tele = init_telemetry(telemetry, t)
        lead = tuple(jnp.shape(state.time))
        if lead:
            tele = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, lead + a.shape), tele
            )
        # store census per mtype via one-hot (T is small): [..., W, B, T]
        # and [..., V, T] reduced over the store axes
        t_arr = jnp.arange(t, dtype=jnp.int32)
        in_wheel = (
            (state.msg_type[..., None] == t_arr) & state.msg_valid[..., None]
        ).sum((-3, -2))
        in_ovf = (
            (state.ovf_type[..., None] == t_arr) & state.ovf_valid[..., None]
        ).sum(-2)
        tele = tele._replace(sent=(in_wheel + in_ovf).astype(jnp.int32))
        return net, state._replace(tele=tele)

    def with_faults(
        self, state: SimState, faults: "FaultConfig | None" = None, plan=None
    ) -> "tuple[BatchedNetwork, SimState]":
        """Arm fault injection on an ALREADY-BUILT simulation: returns an
        engine copy carrying the (static) FaultConfig and the state with
        a FaultState side-car attached.  `plan` may be a host-side
        FaultPlan (lowered here), an already-lowered FaultState — e.g. a
        `lower_plans` stack for a per-replica heterogeneous sweep — or
        None for the neutral do-nothing schedule.  Works on single and
        batched states: an unstacked schedule broadcasts over the
        leading replica axes; a pre-stacked one is used as-is."""
        import copy

        from ..faults.state import FaultConfig, FaultState

        net = copy.copy(self)
        net.faults = FaultConfig() if faults is None else faults
        t = self.protocol.n_msg_types()
        if plan is None:
            fs = neutral_fault_state(self.n_nodes, t)
        elif isinstance(plan, FaultState):
            fs = plan
        else:
            fs = plan.lower(self.n_nodes, t)
        lead = tuple(jnp.shape(state.time))
        if lead and jnp.ndim(fs.crash_at) < 1 + len(lead):
            fs = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, lead + tuple(jnp.shape(a))), fs
            )
        return net, state._replace(faults=fs)

    def with_fuse_step(self, fuse: bool = True) -> "BatchedNetwork":
        """Engine copy with the fused delivery+tick step toggled (fresh
        jit identity via cache_key, same pattern as with_telemetry).
        Fusion is a pure trace restructure — the returned engine accepts
        the same states and produces bit-identical results."""
        import copy

        net = copy.copy(self)
        net.fuse_step = bool(fuse)
        return net

    def with_batched_jumps(self, jumps: bool = True) -> "BatchedNetwork":
        """Engine copy with the batched consensus-jump loop toggled
        (fresh jit identity via cache_key, same pattern as
        with_fuse_step).  Only changes which program run_ms_batched
        traces for TICK_INTERVAL-None protocols; results are
        bit-identical either way."""
        import copy

        net = copy.copy(self)
        net.batched_jumps = bool(jumps)
        return net

    # -- partitions (Network.partition, Network.java:693-707) ----------------
    @staticmethod
    def partition_id(state: SimState, x_col) -> jnp.ndarray:
        """pid = number of partition lines at or left of the node
        (Network.partitionId, Network.java:639-649): a node ON a line is
        right of it.  Two nodes reach each other where their pids are
        equal; nothing writes `partition_x` but `partition` and
        `end_partition` below."""
        return jnp.sum(
            state.partition_x[None, :] <= x_col[:, None], axis=-1
        ).astype(jnp.int32)

    @staticmethod
    def partition(state: SimState, part) -> SimState:
        """`state` with a vertical line at `int(MAX_X * part)`: the nodes
        left of it and those on or right of it cannot reach each other
        (Network.partition, Network.java:693-703; `oracle/network.py`
        `partition`).  Host-side, between runs: the line is data in
        `partition_x` (kept sorted, `INT_MAX` unused), so the program a
        state runs is the one it ran without it.  A batched state takes one
        `part` for all rows or one a row.  Rows sent before the line was
        drawn and due after it are discarded where they are due
        (`census.discarded_rows`); rows sent under it are masked at their
        send (`census.masked_sends`)."""
        lines = np.asarray(state.partition_x)
        parts = np.broadcast_to(np.asarray(part, np.float64), lines.shape[:-1])
        if ((parts <= 0) | (parts >= 1)).any():
            raise ValueError("part needs to be a percentage between 0 & 100 excluded")
        x_point = (MAX_X * parts).astype(np.int64)[..., None]
        if (lines == x_point).any():
            raise ValueError("this partition exists already")
        if (lines != INT_MAX).all(axis=-1).any():
            raise ValueError(f"a row has {MAX_PARTITIONS} partition lines already")
        lines = np.sort(np.concatenate([lines, x_point], axis=-1), axis=-1)
        return state._replace(
            partition_x=jnp.asarray(lines[..., :MAX_PARTITIONS], jnp.int32)
        )

    @staticmethod
    def end_partition(state: SimState) -> SimState:
        """`state` with no partition line (Network.endPartition,
        Network.java:705-707).  The protocol's own part of a heal
        (BlockChainNetwork.endPartition re-broadcasts every head) is not
        the engine's."""
        return state._replace(partition_x=jnp.full_like(state.partition_x, INT_MAX))

    # -- the send path (createMessageArrival, Network.java:469-487) ----------
    def latency_arrivals(
        self, state, mask, from_idx, to_idx, send_time, mtype, event_ctr=None
    ):
        """The createMessageArrival kernel shared by the generic ring and
        protocol-specific message channels: ticks sender counters (even for
        dropped sends, Network.java:476-477), samples the latency model via
        the counter RNG, applies partition/down/discard filters.  Returns
        (state, ok, arrival).

        `event_ctr` (int32[K] or a scalar; the rounds of a fan-out and of
        an emission that states a capacity) is the per-event counter of
        each row's own send event: the caller has taken the events' ticks,
        and `send_ctr` is left as it is."""
        k = mask.shape[0]
        from_idx = from_idx.astype(jnp.int32)
        to_idx = to_idx.astype(jnp.int32)
        mtype = jnp.asarray(mtype, jnp.int32)  # scalar or per-row [K]
        size = jnp.asarray(self._msg_sizes, jnp.int32)[mtype]
        state = state._replace(
            msg_sent=state.msg_sent.at[from_idx].add(mask.astype(jnp.int32)),
            bytes_sent=state.bytes_sent.at[from_idx].add(
                mask.astype(jnp.int32) * size
            ),
            send_ctr=state.send_ctr + (1 if event_ctr is None else 0),
        )
        if event_ctr is None:
            event_ctr = state.send_ctr
        # per-event seed: the batched analog of rd.nextInt() per send;
        # send_ctr decorrelates same-tick emissions, to_idx the rows of
        # one emission.  The destination id — NOT the row position — is
        # the per-row key so the draw is invariant to message-store
        # layout (flat ring vs time wheel order the delivery view
        # differently; a position-keyed seed would make reply latencies
        # depend on storage slots).  Known approximation: duplicate
        # (from, to, type) rows within ONE emission share a draw, where
        # the reference would draw twice — same-dest duplicate sends in
        # a single multicast, which the protocols don't emit.
        seed = hash32(
            state.seed,
            send_time,
            from_idx,
            mtype,
            event_ctr,
            to_idx,
        )
        delta = pseudo_delta(to_idx, seed)
        static = LatencyStatic(state.x, state.y, state.extra_latency, state.city_idx)
        if self.throughput is not None:
            # size-dependent Mathis delay (vectorized twin of the oracle's
            # transit_ms throughput path), priced off THIS network's latency
            lat = self.throughput.vec_delay(
                static, from_idx, to_idx, delta, size, nl=self.latency
            )
        else:
            lat = vec_latency(self.latency, static, from_idx, to_idx, delta)
        arrival = jnp.asarray(send_time, jnp.int32) + lat
        with self._scope("send", REACH_SCOPES):
            pid_f = self.partition_id(state, state.x[from_idx])
            pid_t = self.partition_id(state, state.x[to_idx])
            ok = (
                mask
                & ~state.down[from_idx]
                & ~state.down[to_idx]
                & (pid_f == pid_t)
                & (lat < self.msg_discard_time)
            )
        if self.faults is not None:
            # fault choke point 1 (send): crash/partition/silence/drop
            # suppress rows AFTER the counters ticked above (the oracle
            # ticks msg_sent before its down check too), and the
            # inflation/Byzantine-delay lanes rewrite the sampled
            # latency.  With the neutral schedule supp is constant-false
            # and lat_f == lat, so ok/arrival are bit-identical — the
            # SL406 contract.  The drop draw uses its own hash32 stream
            # without advancing send_ctr, leaving base RNG untouched.
            with self._scope("faults_send"):
                fs = state.faults
                mrows = jnp.broadcast_to(mtype, mask.shape).astype(jnp.int32)
                lat_f = inflate_latency(
                    self.faults, fs, state.time, from_idx, mrows, lat
                )
                supp = send_suppress(
                    self.faults, fs, state.time, from_idx, to_idx, mrows,
                    state.seed, event_ctr, send_time,
                )
                ok_f = (
                    mask
                    & ~state.down[from_idx]
                    & ~state.down[to_idx]
                    & (pid_f == pid_t)
                    & ~supp
                    & (lat_f < self.msg_discard_time)
                )
                state = state._replace(
                    faults=fs._replace(
                        dropped_by_fault=count_by_type(
                            fs.dropped_by_fault, ok & supp, mrows
                        ),
                        delayed_by_fault=count_by_type(
                            fs.delayed_by_fault, ok_f & (lat_f != lat), mrows
                        ),
                    )
                )
                ok = ok_f
                arrival = jnp.asarray(send_time, jnp.int32) + lat_f
        if self.telemetry is not None:
            # the latency kernel is the one choke point EVERY send crosses
            # (generic store and the agg protocols' channel commits alike),
            # so per-mtype traffic is counted here, not in apply_emission
            with self._scope("telemetry"):
                mrows = jnp.broadcast_to(mtype, mask.shape).astype(jnp.int32)
                tele = state.tele
                state = state._replace(
                    tele=tele._replace(
                        lat_sent=count_by_type(tele.lat_sent, ok, mrows),
                        lat_filtered=count_by_type(
                            tele.lat_filtered, mask & ~ok, mrows
                        ),
                    )
                )
        return state, ok, arrival

    def apply_emission(self, state: SimState, em: Emission) -> SimState:
        """Scatter an emission's ok-rows into the message store: wheel
        bucket `eff_arrival mod W` when the arrival is inside the horizon
        (t, t+W], overflow lane otherwise (or on full-row spill).  Wheel
        rows stay a dense prefix — a row is only ever cleared whole (or
        repacked) at delivery, so the next free slot is whl_fill[row] plus
        this call's same-row rank.  Only a genuinely full store drops, and
        it drops the NEW rows, counted in `dropped`.  The rows whose mask
        is set and that cannot reach their receiver are not stored and are
        counted in `census.masked_sends`, as the oracle counts them in its
        own `dropped`."""
        with self._scope("send"):
            if em.capacity is not None and em.capacity < em.mask.shape[0]:
                return self._apply_emission_rounds(state, em)
            state, masked = self._apply_emission_impl(state, em)
            counts = {}
            if em.capacity is not None:  # a round holds all K rows: the dense pass, counted
                with self._scope("compact", EMISSION_SCOPES):
                    fired = jnp.sum(em.mask.astype(jnp.int32))
                counts = {"fired_rows": fired, "firing_peak": fired}
            return census_add(state, masked_sends=masked, **counts)

    def _firing_rounds(self, state: SimState, mask, capacity: int, scope, store_round):
        """The scaffold of a send that runs over the rows that fire: the
        rows (a fan-out's: entries) whose `mask` is set numbered to the
        front in row order by one sort, then `capacity` (static, under the
        mask's K) of them a round through `store_round(state, live, at) ->
        (state, masked)`, where `live` is bool[capacity] and `at` the live
        rows' numbers (0 where not live), until all are through.  Returns
        (state, how many fired, the rounds' `masked` summed).  The first
        round is straight-line code and runs on every call; the rounds past
        it are a `lax.while_loop` that takes no trip where the firing rows
        fit one round, carries only what a send writes (`_send_fields`;
        `_kept` holds a round to it) and, under vmap, runs until the
        batch's slowest row is through.  `scope` names the numbering and a
        round's slice; `store_round` names its own reads."""
        k, c = mask.shape[0], capacity
        with scope():
            fired = jnp.sum(mask.astype(jnp.int32))
            # one sort: the firing rows' numbers first and ascending; k marks
            # the rest, and c more so that a round's slice fits
            order = lax.sort(jnp.where(mask, jnp.arange(k, dtype=jnp.int32), k), is_stable=False)
            order = jnp.concatenate([order, jnp.full(c, k, jnp.int32)])
        fields = self._send_fields()
        rest = [f for f in SimState._fields if f not in fields]

        def one_round(st, cursor):
            with scope():
                sel = lax.dynamic_slice(order, (cursor,), (c,))
                live = sel < k
                at = jnp.where(live, sel, 0)
            new, masked = store_round(st, live, at)
            _kept(new, st, rest)
            return new, masked

        first, masked = one_round(state, 0)

        def more(cursor, masked, vals):
            st, n = one_round(first._replace(**dict(zip(fields, vals))), cursor)
            return cursor + c, masked + n, tuple(getattr(st, f) for f in fields)

        _, masked, vals = lax.while_loop(
            lambda carry: carry[0] < fired,
            lambda carry: more(*carry),
            (jnp.int32(c), masked, tuple(getattr(first, f) for f in fields)),
        )
        return first._replace(**dict(zip(fields, vals))), fired, masked

    def _apply_emission_rounds(self, state: SimState, em: Emission) -> SimState:
        """Store an emission that states a `capacity` under its K rows: the
        rows whose mask is set, `capacity` of them a round as a plain
        emission of that many rows (`_firing_rounds`), until all are
        stored.  The latency draw is keyed on a row's sender, receiver,
        type, send time and the send's counter, never on its place; a
        masked row adds 0 to every counter, takes no rank and scatters out
        of bounds; and the rounds run in row order, so a wheel row's slots
        and the lane's free slots are handed out as one pass over all K
        rows hands them out: every leaf but the census is the dense form's.
        One emission is one send event, whatever the rounds.  An emission
        with more firing rows than its capacity is counted
        (`firing_overflows`), never cut."""
        scope = functools.partial(self._scope, "compact", EMISSION_SCOPES)
        # the counter the dense call would have drawn with (`latency_arrivals`)
        ctr = state.send_ctr + (0 if em.arrival is not None else 1)
        columns = {f: getattr(em, f) for f in _EMISSION_ARRAYS[1:]}

        def store_round(st, live, at):
            with scope():
                # a static or scalar `mtype` and a scalar `send_time` have no rows to read
                rows = {f: x[at] if getattr(x, "ndim", 0) else x for f, x in columns.items()}
            return self._apply_emission_impl(st, Emission(mask=live, **rows), event_ctr=ctr)

        state, fired, masked = self._firing_rounds(state, em.mask, em.capacity, scope, store_round)
        return census_add(
            state._replace(send_ctr=ctr),
            fired_rows=fired, firing_overflows=fired > em.capacity, firing_peak=fired,
            masked_sends=masked,
        )

    def _apply_emission_impl(self, state: SimState, em: Emission, event_ctr=None):
        """(state with the emission's ok rows stored, how many rows the
        mask set and `ok` did not): the caller adds the count to the
        census, outside whatever branch or loop it stores in."""
        k = em.mask.shape[0]
        send_time = em.send_time if em.send_time is not None else state.time + 1
        mask = em.mask
        from_idx = em.from_idx.astype(jnp.int32)
        to_idx = em.to_idx.astype(jnp.int32)

        mtype = jnp.asarray(em.mtype, jnp.int32)  # scalar or per-row [K]
        if em.arrival is not None:
            # sendArriveAt path: explicit arrival, no latency model and no
            # sender counters (Network.sendArriveAt, Network.java:419-422,
            # bypasses createMessageArrival's counter ticks)
            arrival = em.arrival.astype(jnp.int32)
            ok = mask
            masked = jnp.int32(0)
        else:
            state, ok, arrival = self.latency_arrivals(
                state, mask, from_idx, to_idx, send_time, mtype, event_ctr
            )
            with self._scope("send", REACH_SCOPES):
                masked = jnp.sum((mask & ~ok).astype(jnp.int32))

        payload = em.payload
        if self.payload_width and payload is None:
            payload = jnp.zeros((k, self.payload_width), dtype=jnp.int32)
        mtype_rows = jnp.broadcast_to(mtype, (k,)).astype(jnp.int32)
        with self._scope("insert", STORE_SCOPES):
            state, lost = self._insert_rows(
                state, ok, arrival, from_idx, to_idx, mtype_rows, payload
            )
        if self.telemetry is not None:
            # store accounting: every ok row is either inserted (wheel or
            # overflow) or dropped (`lost`, the rows behind `_insert_rows`'
            # scalar `overwritten`), so sent - dropped rows are live.
            # HWMs sample post-insert, the only moment occupancy can peak.
            with self._scope("telemetry"):
                tele = state.tele
                state = state._replace(
                    tele=tele._replace(
                        sent=count_by_type(tele.sent, ok, mtype_rows),
                        dropped=count_by_type(tele.dropped, lost, mtype_rows),
                        wheel_fill_hwm=jnp.maximum(
                            tele.wheel_fill_hwm, jnp.max(state.whl_fill)
                        ),
                        ovf_hwm=jnp.maximum(
                            tele.ovf_hwm,
                            jnp.sum(state.ovf_valid.astype(jnp.int32)),
                        ),
                    )
                )
        return state, masked

    def _insert_rows(
        self, state: SimState, ok, arrival, from_idx, to_idx, mtype_rows, payload,
        wide: bool = False,
    ):
        """One emission's ok-rows into the wheel or the overflow lane.
        Returns the state and the rows a full store dropped.

        `wide` (a fan-out round's 10^5 rows, `_store_grid`): the same
        slots and counts, with the wheel rows' fill read and written as
        reductions of a [rows, wheel_rows] comparison (an indexed read or
        an add into 256 bins costs the chip tens of ns a row), and the
        lane's half under a branch that runs where a row goes there."""
        n_ok = jnp.sum(ok.astype(jnp.int32))
        t = state.time
        w, b, v = self.wheel_rows, self.wheel_slots, self.overflow_capacity

        if self.flat:
            to_ovf = ok
        else:
            # routing tick: stale arrivals (<= t, possible via explicit
            # arrivals after a clock skip) deliver next tick like the flat
            # ring; arrival == t + W is safe because the current row is
            # delivered/cleared before emissions are applied
            eff = jnp.maximum(arrival, t + 1)
            cand = ok & (eff <= t + w)
            row = jnp.remainder(eff, w)
            # same-row rank (ties take consecutive slots in row order)
            rkey = jnp.where(cand, row, w)
            rank = same_key_rank(rkey)
            if wide:
                wheel = jnp.arange(w, dtype=jnp.int32)[None, :]
                fill = jnp.sum(
                    jnp.where(jnp.where(cand, row, 0)[:, None] == wheel, state.whl_fill[None, :], 0),
                    axis=1,
                )
            else:
                fill = state.whl_fill[jnp.where(cand, row, 0)]
            slot = fill + rank
            fits = cand & (slot < b)
            w_row = jnp.where(fits, row, w)  # OOB -> dropped scatter
            w_slot = jnp.where(fits, slot, 0)
            state = state._replace(
                msg_valid=state.msg_valid.at[w_row, w_slot].set(True, mode="drop"),
                msg_arrival=state.msg_arrival.at[w_row, w_slot].set(
                    arrival, mode="drop"
                ),
                msg_from=state.msg_from.at[w_row, w_slot].set(
                    from_idx.astype(self.lanes.idx), mode="drop"
                ),
                msg_to=state.msg_to.at[w_row, w_slot].set(
                    to_idx.astype(self.lanes.idx), mode="drop"
                ),
                msg_type=state.msg_type.at[w_row, w_slot].set(
                    mtype_rows.astype(self.lanes.mtype), mode="drop"
                ),
                whl_fill=(
                    state.whl_fill + jnp.sum((w_row[:, None] == wheel).astype(jnp.int32), axis=0)
                    if wide
                    else state.whl_fill.at[w_row].add(fits.astype(jnp.int32), mode="drop")
                ),
            )
            if self.payload_width:
                state = state._replace(
                    msg_payload=state.msg_payload.at[w_row, w_slot].set(
                        payload, mode="drop"
                    )
                )
            to_ovf = ok & ~fits  # beyond horizon, or full-row spill

        state = state._replace(msg_head=state.msg_head + n_ok)
        if not wide:
            return self._insert_lane(state, to_ovf, arrival, from_idx, to_idx, mtype_rows, payload)
        # a wave that fits its wheel rows sends nothing to the lane: its
        # ranks and six scatters of every row run where a row goes there
        lane = ("ovf_valid", "ovf_arrival", "ovf_from", "ovf_to", "ovf_type", "ovf_payload", "dropped")

        def spill(vals):
            st, lost = self._insert_lane(
                state._replace(**dict(zip(lane, vals))), to_ovf, arrival, from_idx, to_idx,
                mtype_rows, payload,
            )
            return tuple(getattr(st, f) for f in lane), lost

        vals, lost = lax.cond(
            jnp.any(to_ovf), spill, lambda vals: (vals, jnp.zeros_like(to_ovf)),
            tuple(getattr(state, f) for f in lane),
        )
        return state._replace(**dict(zip(lane, vals))), lost

    def _insert_lane(self, state, to_ovf, arrival, from_idx, to_idx, mtype_rows, payload):
        """The rows `to_ovf` into the overflow lane.  Returns the state
        and the rows a full lane dropped."""
        v = self.overflow_capacity
        # overflow lane: pack into FREE slots, k-th ok row takes the k-th
        # invalid slot (a head cursor would clobber still-pending long-lived
        # messages — ENR's wakes, Casper's slot calendar — once cumulative
        # traffic wraps the capacity, even with most slots free)
        free = ~state.ovf_valid  # [V]
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        slot_of_rank = jnp.full(v + 1, v, jnp.int32)
        slot_of_rank = slot_of_rank.at[
            jnp.where(free, free_rank, v)
        ].set(jnp.arange(v, dtype=jnp.int32), mode="drop")
        n_free = jnp.sum(free.astype(jnp.int32))
        orank = jnp.cumsum(to_ovf.astype(jnp.int32)) - 1
        ofits = to_ovf & (orank < n_free)
        pos = jnp.where(
            ofits,
            slot_of_rank[jnp.clip(orank, 0, v)],
            jnp.int32(v),  # OOB -> dropped
        )
        overwritten = jnp.sum((to_ovf & ~ofits).astype(jnp.int32))
        state = state._replace(
            ovf_valid=state.ovf_valid.at[pos].set(True, mode="drop"),
            ovf_arrival=state.ovf_arrival.at[pos].set(arrival, mode="drop"),
            ovf_from=state.ovf_from.at[pos].set(
                from_idx.astype(self.lanes.idx), mode="drop"
            ),
            ovf_to=state.ovf_to.at[pos].set(
                to_idx.astype(self.lanes.idx), mode="drop"
            ),
            ovf_type=state.ovf_type.at[pos].set(
                mtype_rows.astype(self.lanes.mtype), mode="drop"
            ),
            dropped=state.dropped + overwritten,
        )
        if self.payload_width:
            state = state._replace(
                ovf_payload=state.ovf_payload.at[pos].set(payload, mode="drop")
            )
        return state, to_ovf & ~ofits

    def _send_fields(self) -> tuple:
        """The leaves a send into this store writes."""
        fields = _SEND_FIELDS if self.flat else _SEND_FIELDS + _WHEEL_FIELDS
        return fields + ("tele",) if self.telemetry is not None else fields

    def apply_fanout(self, state: SimState, fo: FanOut) -> SimState:
        """Store a `FanOut`: the entries that fire to the front in entry
        order, then `capacity` of them a round, each round the plain
        `capacity x receivers` rows through `_apply_emission_impl`, until
        all are stored (no round where none fires).  Rows reach the store
        in the order `fo.dense()`'s do, with the per-event counter their
        own event would have drawn, so every leaf but the census is the
        dense form's.  A fan-out with more firing entries than its
        capacity is counted (`fanout_overflows`), never cut."""
        with self._scope("send"), self._scope("expand", FANOUT_SCOPES):
            return self._apply_fanout_impl(state, fo)

    def _apply_fanout_impl(self, state: SimState, fo: FanOut) -> SimState:
        s = fo.mask.shape[0]
        per = s // fo.events
        k = min(int(fo.capacity), s)
        receivers = jnp.asarray(fo.receivers, jnp.int32)
        r = receivers.shape[0]
        entries = jnp.arange(s, dtype=jnp.int32)
        fired = jnp.sum(fo.mask.astype(jnp.int32))
        if k < s:
            # one sort: the firing entries' numbers first and ascending, then
            # numbers past the end; k more of those so that a round's slice fits
            order = lax.sort(jnp.where(fo.mask, entries, entries + s), is_stable=False)
            order = jnp.concatenate([order, jnp.full(k, 2 * s, jnp.int32)])
        base = state.send_ctr  # event j of the fan-out draws with base + 1 + j
        send_time = (
            jnp.broadcast_to(state.time + 1, (s,)) if fo.send_time is None else fo.send_time
        ).astype(jnp.int32)
        fields = self._send_fields()
        # with no side-car and no throughput model a round's arrivals are
        # computed on the [capacity, receivers] grid; else as plain rows
        plain = self.faults is None and self.telemetry is None and self.throughput is None

        def one_round(cursor, masked, vals):
            st = state._replace(**dict(zip(fields, vals)))
            if k < s:
                live = cursor + jnp.arange(k, dtype=jnp.int32) < fired
                at = jnp.where(live, lax.dynamic_slice(order, (cursor,), (k,)), 0)
            else:
                live, at = fo.mask, entries
            senders = fo.from_idx.astype(jnp.int32)[at]
            payload = None if fo.payload is None else jnp.repeat(fo.payload[at], r, axis=0)
            if plain:
                st, n = self._store_grid(
                    st, live, senders, receivers, send_time[at], fo.mtype,
                    base + 1 + at // per, payload,
                )
            else:
                rows = Emission(
                    mask=jnp.repeat(live, r),
                    from_idx=jnp.repeat(senders, r),
                    to_idx=jnp.tile(receivers, k),
                    mtype=fo.mtype,
                    payload=payload,
                    send_time=jnp.repeat(send_time[at], r),
                )
                st, n = self._apply_emission_impl(
                    st, rows, event_ctr=jnp.repeat(base + 1 + at // per, r)
                )
            _kept(st, state, [f for f in SimState._fields if f not in fields])
            return cursor + k, masked + n, tuple(getattr(st, f) for f in fields)

        _, masked, vals = lax.while_loop(
            lambda c: c[0] < fired,
            lambda c: one_round(*c),
            (jnp.int32(0), jnp.int32(0), tuple(getattr(state, f) for f in fields)),
        )
        state = state._replace(**dict(zip(fields, vals)))
        state = state._replace(send_ctr=base + fo.events)
        return census_add(
            state, fanout_senders=fired, fanout_overflows=fired > k, fanout_peak=fired,
            masked_sends=masked,
        )

    def _store_grid(self, state, live, senders, receivers, send_time, mtype, event_ctr, payload):
        """A fan-out round's `len(senders) x len(receivers)` rows into the
        store: what `_apply_emission_impl` does with the same rows spelled
        out (sender-major), to the bit, with everything that depends on one
        end alone read once an end and not once a row: a row's position,
        extra latency, down flag and partition are those of its sender or
        of its receiver, so they are K + R reads broadcast over the grid
        where the rows' own `x[from_idx]` are K x R gathers, and the
        sender counters grow by R a live sender (an indexed read costs the
        chip tens of ns a row: ten of them were two thirds of a round).
        Returns the state and how many of the live senders' rows `ok`
        masked (`_apply_emission_impl`'s second result)."""
        k, r = senders.shape[0], receivers.shape[0]
        f, t = senders[:, None], receivers[None, :]
        size = jnp.asarray(self._msg_sizes, jnp.int32)[mtype]
        sent = live.astype(jnp.int32) * r
        state = state._replace(
            msg_sent=state.msg_sent.at[senders].add(sent),
            bytes_sent=state.bytes_sent.at[senders].add(sent * size),
        )
        seed = hash32(state.seed, send_time[:, None], f, mtype, event_ctr[:, None], t)
        delta = pseudo_delta(t, seed)
        static = LatencyStatic(state.x, state.y, state.extra_latency, state.city_idx)
        lat = vec_latency(self.latency, static, f, t, delta)
        arrival = send_time[:, None] + lat
        with self._scope("send", REACH_SCOPES):
            pid_f = self.partition_id(state, state.x[senders])
            pid_t = self.partition_id(state, state.x[receivers])
            ok = (
                (live & ~state.down[senders])[:, None]
                & ~state.down[receivers][None, :]
                & (pid_f[:, None] == pid_t[None, :])
                & (lat < self.msg_discard_time)
            )
            masked = jnp.sum((live[:, None] & ~ok).astype(jnp.int32))
        rows = lambda a: jnp.broadcast_to(a, (k, r)).reshape(k * r)
        if self.payload_width and payload is None:
            payload = jnp.zeros((k * r, self.payload_width), dtype=jnp.int32)
        with self._scope("insert", STORE_SCOPES):
            state, _lost = self._insert_rows(
                state, rows(ok), rows(arrival), rows(f), rows(t),
                jnp.full(k * r, mtype, jnp.int32), payload, wide=not self.flat,
            )
        return state, masked

    def _apply_one(self, state: SimState, em) -> SimState:
        if isinstance(em, FanOut):
            return self.apply_fanout(state, em)
        return self.apply_emission(state, em)

    def apply_emissions(self, state: SimState, emissions) -> SimState:
        emissions = list(emissions)
        if (
            self.due_view_rows is None
            or self.telemetry is not None
            or not emissions
            # a fan-out stores nothing where nothing fires, of itself, and an
            # emission that states a capacity one round of that many rows
            or any(isinstance(em, FanOut) or em.capacity is not None for em in emissions)
        ):
            for em in emissions:
                state = self._apply_one(state, em)
            return state
        # the due view's other half: a step whose every mask is empty (all
        # but a handful a slot, for a protocol that acts on timers) skips
        # the sampling of every row and the passes over the lane.  An empty
        # emission still ticks the per-event counter (`latency_arrivals`),
        # and nothing else: its rows add 0 to every counter and scatter out
        # of bounds.  Only the fields a send writes ride through the branch
        fields = self._send_fields()
        sampled = sum(em.arrival is None for em in emissions)
        any_send = functools.reduce(
            jnp.logical_or, [jnp.any(em.mask) for em in emissions]
        )

        def send(vals):
            s, masked = state._replace(**dict(zip(fields, vals))), jnp.int32(0)
            for em in emissions:
                with self._scope("send"):
                    s, n = self._apply_emission_impl(s, em)
                masked = masked + n
            _kept(s, state, [f for f in SimState._fields if f not in fields])
            return tuple(getattr(s, f) for f in fields), masked

        def skip(vals):
            s = state._replace(**dict(zip(fields, vals)))
            s = s._replace(send_ctr=s.send_ctr + sampled)
            return tuple(getattr(s, f) for f in fields), jnp.int32(0)

        vals, masked = lax.cond(
            any_send, send, skip, tuple(getattr(state, f) for f in fields)
        )
        return census_add(state._replace(**dict(zip(fields, vals))), masked_sends=masked)

    # -- delivery ------------------------------------------------------------
    def _window(self) -> int:
        """Wheel rows gathered per step: TIME_QUANTUM consecutive rows so a
        quantum-coarsened step delivers its whole window (t-q, t] at once;
        1 in flat mode (the overflow scan is already exact)."""
        if self.flat:
            return 1
        q = max(1, int(self.protocol.TIME_QUANTUM))
        if q > self.wheel_rows:
            raise ValueError(
                f"TIME_QUANTUM={q} exceeds wheel_rows={self.wheel_rows}; "
                "raise wheel_rows or use flat mode (wheel_rows=0)"
            )
        return q

    def delivery_view(self, state: SimState, ovf_due=None, row_slots=None):
        """Build the flat delivery VIEW protocol.deliver sees: msg_* columns
        are `[D]` gathers of the due wheel window rows + the overflow lane
        (see the module docstring).  Returns (vstate, due, deliver, ctx):
        `due` is bool[D] (arrival <= t), `deliver` additionally applies the
        delivery-time down/partition discards, and `ctx` carries the wheel
        internals `_deliver_and_clear` needs for the post-deliver repack.
        Exposed as API so the static checker (wittgenstein_tpu.analysis)
        can trace `deliver` against the exact view contract.

        `ovf_due` (bool[V], the lane's due rows; `due_view_rows` alone)
        puts those rows in place of the lane, compacted in lane order to
        `due_view_rows` rows: the caller has seen that they fit.  What
        is not due is not in this view at all; a protocol's `deliver`
        reads nothing but what its mask delivers, so it computes the same.

        `row_slots` (the wheel's due view alone) views that many leading
        slots of the due wheel row in place of all `wheel_slots`: the
        caller has seen that the row's dense prefix fits."""
        t = state.time
        w, b = self.wheel_rows, self.wheel_slots if row_slots is None else row_slots
        q = self._window()
        if ovf_due is not None:
            with self._scope("view", STORE_SCOPES):
                v = self.overflow_capacity
                lane = jnp.arange(v, dtype=jnp.int32)
                # one sort: the due rows' numbers first and ascending, then
                # numbers past the lane's end, which every gather below fills
                at = lax.sort(
                    jnp.where(ovf_due, lane, lane + v), is_stable=False
                )[: self.due_view_rows]
                take = lambda a, fill: a.at[at].get(mode="fill", fill_value=fill)
                state = state._replace(
                    ovf_valid=take(state.ovf_valid, False),
                    ovf_arrival=take(state.ovf_arrival, INT_MAX),
                    ovf_from=take(state.ovf_from, 0),
                    ovf_to=take(state.ovf_to, 0),
                    ovf_type=take(state.ovf_type, 0),
                    ovf_payload=take(state.ovf_payload, 0),
                )
        with self._scope("view", STORE_SCOPES):
            rows = jnp.remainder(
                t - q + 1 + jnp.arange(q, dtype=jnp.int32), jnp.int32(w)
            )  # [q] distinct rows covering ticks (t-q, t]
            if row_slots is None:
                take = lambda a: a[rows]
            else:  # q == 1: a slice of the one row, no gather; the payload
                # plane sliced as [rows, slots x P], as flat as the others
                # (XLA:TPU changed the layout of all of a 3-D plane first)
                def take(a):
                    width = int(np.prod(a.shape[2:], dtype=np.int64))
                    flat = a.reshape(a.shape[0], a.shape[1] * width)
                    cut = lax.dynamic_slice(flat, (rows[0], 0), (1, b * width))
                    if a.ndim > 2:  # or the reshapes fold into a 3-D slice again
                        cut = lax.optimization_barrier(cut)
                    return cut.reshape((1, b) + a.shape[2:])
            wv = take(state.msg_valid)  # [q, B]
            wa = take(state.msg_arrival)
            wf = take(state.msg_from)
            wt = take(state.msg_to)
            wk = take(state.msg_type)
            wp = take(state.msg_payload)  # [q, B, P]

            view_valid = jnp.concatenate([wv.reshape(-1), state.ovf_valid])
            view_arrival = jnp.concatenate([wa.reshape(-1), state.ovf_arrival])
            # the ONE widening point of the narrow-lane plan: protocols (and
            # every engine consumer below) see int32 ids/types regardless of
            # the storage dtypes, so kernels are unchanged by the plan
            view_from = jnp.concatenate(
                [wf.reshape(-1), state.ovf_from]
            ).astype(jnp.int32)
            view_to = jnp.concatenate(
                [wt.reshape(-1), state.ovf_to]
            ).astype(jnp.int32)
            view_type = jnp.concatenate(
                [wk.reshape(-1), state.ovf_type]
            ).astype(jnp.int32)
            view_payload = jnp.concatenate(
                [wp.reshape(q * b, -1), state.ovf_payload], axis=0
            )

        due = view_valid & (view_arrival <= t)

        # delivery-time checks: down destination or cross-partition messages
        # are discarded on arrival (Network.java:606, :518-520) and counted
        # (`census.discarded_rows`).  The reads are of `x` at the view's two
        # ends, as a send's are: where a protocol replies along the view
        # (SanFermin: 1280 view rows a replica), that send needs `x[from]`
        # and `x[to]` for its latency and its own `ok`, and the compiler
        # makes each read once for both.  Read a per-node side here instead
        # and the send's two reads stand alone: 8.6 ns a row a tick on the
        # chip (PR 45 was refused for two of them on `sanfermin-4096`;
        # tests/test_tpu_compile.py holds the count)
        def checked():
            with self._scope("deliver", REACH_SCOPES):
                pid_f = self.partition_id(state, state.x[view_from])
                pid_t = self.partition_id(state, state.x[view_to])
                deliver = due & ~state.down[view_to] & (pid_f == pid_t)
                return deliver, jnp.sum((due & ~deliver).astype(jnp.int32))

        if row_slots is None:
            deliver, discarded = checked()
        else:
            # a view of 10^5 rows: with no node down and no partition line
            # every due row is delivered, and the three indexed reads a view
            # row are not made.  Where they are (`dfinity-4096-part20`: a
            # line set, so every executed step) they cost 2.3 ms each over
            # a whole-row view of 270,336 rows, 8.6 ns a row (the per-node
            # operand sits in fast memory), 0.025-0.032 ms a simulated ms
            # of a 0.361-ms one (chip runs of PR 45, which read a per-node
            # side in place of `x`: an operand of the same 4171 words;
            # PERF.md section 5)
            deliver, discarded = lax.cond(
                jnp.any(state.down) | jnp.any(state.partition_x != INT_MAX),
                checked, lambda: (due, jnp.int32(0)),
            )
        if self.faults is not None:
            # fault choke point 2 (arrival): suppress delivery to
            # fault-crashed destinations and across an active group
            # partition.  Recovery needs no extra work — the crash
            # predicate simply stops holding at recover_at.  The
            # suppression mask rides in ctx so _deliver_and_clear can
            # count the rows; they still leave the store like any other
            # due row (the store invariant is fault-agnostic).
            with self._scope("faults_deliver"):
                fault_supp = due & deliver_suppress(
                    self.faults, state.faults, t, view_from, view_to
                )
                deliver = deliver & ~fault_supp
                discarded = jnp.sum((due & ~deliver).astype(jnp.int32))
        else:
            fault_supp = None

        vstate = state._replace(
            msg_valid=view_valid,
            msg_arrival=view_arrival,
            msg_from=view_from,
            msg_to=view_to,
            msg_type=view_type,
            msg_payload=view_payload,
        )
        ctx = (rows, wv, wa, wf, wt, wk, wp, q, b, fault_supp, discarded)
        return vstate, due, deliver, ctx

    def _deliver_and_clear(self, state: SimState):
        """One tick's delivery: gather the due view (window rows + overflow
        lane), update receiver counters, run protocol.deliver on the view,
        then clear delivered entries and repack the visited rows to a dense
        prefix.  Returns (state, emissions)."""
        with self._scope("delivery"):
            return self._deliver_and_clear_impl(state)

    def _deliver_and_clear_impl(self, state: SimState):
        if self.due_view_rows is None:
            return self._deliver_view_and_clear(state, None)
        if not self.flat:
            return self._deliver_row_view(state)
        # the FLAT store's due view: the step's due rows alone where they
        # fit, the whole lane where they do not (the same rows either way,
        # so the same step: a wave's fullest ms decides the size).  The
        # lane's planes that a delivery only reads stay out of the branch
        ovf_due = state.ovf_valid & (state.ovf_arrival <= state.time)
        n_due = jnp.sum(ovf_due.astype(jnp.int32))
        fits = n_due <= self.due_view_rows
        state = census_add(state, view_overflow_steps=~fits, due_rows_peak=n_due)
        through, carried, shape = self._view_branches(_LANE_READ_FIELDS)
        vals, leaves = lax.cond(fits, through(ovf_due), through(None), state)
        return state._replace(**dict(zip(carried, vals))), _emissions_of(shape, leaves)

    def _view_branches(self, read):
        """What a due view's branches share: `through(ovf_due, row_slots)`
        makes the branch that delivers from that view and returns the
        fields it may write (`carried`: all but `read`, which it must
        leave as they are) and its emissions' arrays; `shape` takes the
        emissions' static part, the same in every branch."""
        carried = [f for f in SimState._fields if f not in read]
        shape = []

        def through(ovf_due=None, row_slots=None):
            def run(s):
                out, emissions = self._deliver_view_and_clear(s, ovf_due, row_slots)
                _kept(out, s, read)
                shape[:], leaves = _emission_leaves(emissions)
                return tuple(getattr(out, f) for f in carried), leaves

            return run

        return through, carried, shape

    def _deliver_row_view(self, state: SimState):
        """A step's delivery under the WHEEL's due view.  The due row is a
        dense prefix of `whl_fill[row]` entries and, visited once, every
        one of them is due (`_step_core_fused` has the argument), so the
        step views the smallest of `due_view_rows` leading slots that the
        prefix fits, all `wheel_slots` where it fits none: the same rows
        in the same order either way.  The wheel's planes are read in the
        branch and never written; the row is emptied whole after it."""
        tiers = self.due_view_rows
        w, b = self.wheel_rows, self.wheel_slots
        row = jnp.remainder(state.time, jnp.int32(w))
        n_due = state.whl_fill[row]
        tier = functools.reduce(jnp.add, [(n_due > k).astype(jnp.int32) for k in tiers])
        state = census_add(
            state, view_overflow_steps=tier == len(tiers), due_rows_peak=n_due
        )
        through, carried, shape = self._view_branches(_WHEEL_FIELDS + _LANE_READ_FIELDS)
        vals, leaves = lax.switch(tier, [through(row_slots=k) for k in (*tiers, b)], state)
        state = state._replace(**dict(zip(carried, vals)))
        with self._scope("repack", STORE_SCOPES):
            empty = lambda a, fill: lax.dynamic_update_slice(
                a, jnp.full((1,) + a.shape[1:], fill, a.dtype), (row,) + (0,) * (a.ndim - 1)
            )
            state = state._replace(
                msg_valid=empty(state.msg_valid, False),
                msg_arrival=empty(state.msg_arrival, INT_MAX),
                msg_from=empty(state.msg_from, 0),
                msg_to=empty(state.msg_to, 0),
                msg_type=empty(state.msg_type, 0),
                msg_payload=(
                    empty(state.msg_payload, 0) if self.payload_width else state.msg_payload
                ),
                whl_fill=state.whl_fill.at[row].set(0),
            )
        return state, _emissions_of(shape, leaves)

    def _deliver_view_and_clear(self, state: SimState, ovf_due, row_slots=None):
        vview, due, deliver, ctx = self.delivery_view(state, ovf_due, row_slots)
        rows, wv, wa, wf, wt, wk, wp, q, b, fault_supp, discarded = ctx
        state = census_add(state, discarded_rows=discarded)
        view_to = vview.msg_to
        view_type = vview.msg_type

        # receiver counters skip size-0 (task-style) types, mirroring the
        # Task exemption at Network.java:522-526
        if row_slots is not None and len(set(self._msg_sizes.tolist())) == 1:
            sizes = jnp.full(view_type.shape, int(self._msg_sizes[0]), jnp.int32)  # no read a row
        else:
            sizes = jnp.asarray(self._msg_sizes, jnp.int32)[view_type]
        dm = (deliver & (sizes > 0)).astype(jnp.int32)
        state = state._replace(
            msg_received=state.msg_received.at[view_to].add(dm, mode="drop"),
            bytes_received=state.bytes_received.at[view_to].add(
                dm * sizes, mode="drop"
            ),
        )
        if self.telemetry is not None:
            # due rows leave the store exactly once, as delivered or as
            # delivery-time discards (down dest / cross-partition) — the
            # split the store invariant needs
            with self._scope("telemetry"):
                tele = state.tele
                state = state._replace(
                    tele=tele._replace(
                        delivered=count_by_type(
                            tele.delivered, deliver, view_type
                        ),
                        discarded=count_by_type(
                            tele.discarded, due & ~deliver, view_type
                        ),
                    )
                )
        if self.faults is not None:
            # delivery-time fault discards (crashed destination / active
            # partition window); telemetry already folded them into
            # `discarded` above, this is the per-lane attribution
            fs = state.faults
            state = state._replace(
                faults=fs._replace(
                    dropped_by_fault=count_by_type(
                        fs.dropped_by_fault, fault_supp, view_type
                    )
                )
            )

        # hand the protocol a view-state whose msg_* columns are the flat
        # [D] gathers; protocols must not touch msg_* (the engine owns the
        # store), so the wheel fields are restored below
        vstate = state._replace(
            msg_valid=vview.msg_valid,
            msg_arrival=vview.msg_arrival,
            msg_from=vview.msg_from,
            msg_to=vview.msg_to,
            msg_type=vview.msg_type,
            msg_payload=vview.msg_payload,
        )
        with self._scope("protocol_deliver"):
            pstate, emissions = self.protocol.deliver(self, vstate, deliver)

        if row_slots is not None:
            # the wheel's due view: the lane's due entries leave here, the
            # wheel's row is emptied by the caller, outside its branch
            with self._scope("repack", STORE_SCOPES):
                q, b = ctx[7], ctx[8]
                return pstate._replace(
                    **{f: getattr(state, f) for f in _WHEEL_FIELDS},
                    ovf_valid=state.ovf_valid & ~due[q * b :],
                ), emissions
        state = self._clear_visited_rows(pstate, state, ctx, due, ovf_due)
        return state, emissions

    def _clear_visited_rows(self, pstate, state, ctx, due, ovf_due=None) -> SimState:
        """Clear due entries from the visited window rows + overflow lane;
        surviving entries (a row visited early by a quantum window) repack
        to the slot prefix so whl_fill stays the next-free-slot index.
        `pstate` carries the protocol's post-deliver columns; the wheel
        fields are taken from the pre-view `state`."""
        with self._scope("repack", STORE_SCOPES):
            return self._clear_visited_rows_impl(pstate, state, ctx, due, ovf_due)

    def _clear_visited_rows_impl(self, pstate, state, ctx, due, ovf_due=None) -> SimState:
        rows, wv, wa, wf, wt, wk, wp, q, b = ctx[:9]
        keep = wv & ~due[: q * b].reshape(q, b)
        pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
        tgt = jnp.where(keep, pos, b)  # OOB -> dropped scatter
        ii = jnp.arange(q, dtype=jnp.int32)[:, None]
        nv = jnp.zeros_like(wv).at[ii, tgt].set(keep, mode="drop")
        na = jnp.full_like(wa, INT_MAX).at[ii, tgt].set(wa, mode="drop")
        nf = jnp.zeros_like(wf).at[ii, tgt].set(wf, mode="drop")
        nt = jnp.zeros_like(wt).at[ii, tgt].set(wt, mode="drop")
        nk = jnp.zeros_like(wk).at[ii, tgt].set(wk, mode="drop")
        state = pstate._replace(
            msg_valid=state.msg_valid.at[rows].set(nv),
            msg_arrival=state.msg_arrival.at[rows].set(na),
            msg_from=state.msg_from.at[rows].set(nf),
            msg_to=state.msg_to.at[rows].set(nt),
            msg_type=state.msg_type.at[rows].set(nk),
            msg_payload=state.msg_payload,
            whl_fill=state.whl_fill.at[rows].set(
                jnp.sum(keep.astype(jnp.int32), axis=1)
            ),
            # under a due view the view's rows are not the lane's: the
            # lane's own due mask says which leave
            ovf_valid=state.ovf_valid
            & ~(due[q * b :] if ovf_due is None else ovf_due),
        )
        if self.payload_width:
            np_ = jnp.zeros_like(wp).at[ii, tgt].set(wp, mode="drop")
            state = state._replace(
                msg_payload=state.msg_payload.at[rows].set(np_)
            )
        return state

    # -- one millisecond (receiveUntil body, Network.java:586-632) -----------
    def _step_core(self, state: SimState) -> SimState:
        """One tick WITHOUT the time advance and WITHOUT tick_beat: wheel
        delivery + protocol.tick.  run_ms_batched's beat path guards
        tick_beat separately with a real branch."""
        if self.fuse_step:
            return self._step_core_fused(state)
        state, emissions = self._deliver_and_clear(state)
        state = self.apply_emissions(state, emissions)
        with self._scope("protocol_tick"):
            return self.protocol.tick(self, state)

    def _step_core_fused(self, state: SimState) -> SimState:
        """The fuse_step fast path (docs/engine_fused_step.md): the whole
        deliver -> clear -> send -> tick sequence traced under ONE scope,
        with the intermediate full-state round-trips removed — receiver
        counters, telemetry and fault attribution land in a single
        _replace together with the delivery view, and when the delivery
        window is one row the post-deliver repack collapses to a static
        empty-row fill (every valid entry in a singly-visited row is due:
        eff-arrival ≡ row (mod W) and eff ∈ (insert, insert+W] pin the
        visit tick to eff exactly, and jumps never overshoot an occupied
        row).  Bit-identical to _step_core by construction; pinned across
        every registered protocol by tests/test_step_fusion.py."""
        with self._scope("fused_step"):
            vview, due, deliver, ctx = self.delivery_view(state)
            q, b = ctx[7], ctx[8]
            fault_supp = ctx[9]
            state = census_add(state, discarded_rows=ctx[10])
            view_to = vview.msg_to
            view_type = vview.msg_type
            sizes = jnp.asarray(self._msg_sizes, jnp.int32)[view_type]
            dm = (deliver & (sizes > 0)).astype(jnp.int32)
            upd = dict(
                msg_received=state.msg_received.at[view_to].add(
                    dm, mode="drop"
                ),
                bytes_received=state.bytes_received.at[view_to].add(
                    dm * sizes, mode="drop"
                ),
            )
            if self.telemetry is not None:
                tele = state.tele
                upd["tele"] = tele._replace(
                    delivered=count_by_type(tele.delivered, deliver, view_type),
                    discarded=count_by_type(
                        tele.discarded, due & ~deliver, view_type
                    ),
                )
            if self.faults is not None:
                fs = state.faults
                upd["faults"] = fs._replace(
                    dropped_by_fault=count_by_type(
                        fs.dropped_by_fault, fault_supp, view_type
                    )
                )
            # one _replace: counters + side-cars + the flat delivery view
            vstate = state._replace(
                msg_valid=vview.msg_valid,
                msg_arrival=vview.msg_arrival,
                msg_from=vview.msg_from,
                msg_to=vview.msg_to,
                msg_type=vview.msg_type,
                msg_payload=vview.msg_payload,
                **upd,
            )
            with self._scope("protocol_deliver"):
                pstate, emissions = self.protocol.deliver(
                    self, vstate, deliver
                )
            if q == 1:
                # all-due invariant: the visited row empties entirely, so
                # the sort/cumsum/scatter repack is a constant fill (in
                # flat mode the degenerate 1x1 row is never occupied and
                # the same constants are what it already holds)
                with self._scope("repack", STORE_SCOPES):
                    w_shape = (q, b)
                    state = pstate._replace(
                        msg_valid=state.msg_valid.at[ctx[0]].set(
                            jnp.zeros(w_shape, bool)
                        ),
                        msg_arrival=state.msg_arrival.at[ctx[0]].set(
                            jnp.full(w_shape, INT_MAX, jnp.int32)
                        ),
                        msg_from=state.msg_from.at[ctx[0]].set(
                            jnp.zeros(w_shape, dtype=self.lanes.idx)
                        ),
                        msg_to=state.msg_to.at[ctx[0]].set(
                            jnp.zeros(w_shape, dtype=self.lanes.idx)
                        ),
                        msg_type=state.msg_type.at[ctx[0]].set(
                            jnp.zeros(w_shape, dtype=self.lanes.mtype)
                        ),
                        msg_payload=(
                            state.msg_payload.at[ctx[0]].set(
                                jnp.zeros(
                                    w_shape + (self.payload_width,), jnp.int32
                                )
                            )
                            if self.payload_width
                            else state.msg_payload
                        ),
                        whl_fill=state.whl_fill.at[ctx[0]].set(
                            jnp.zeros(q, jnp.int32)
                        ),
                        ovf_valid=state.ovf_valid & ~due[q * b :],
                    )
            else:
                state = self._clear_visited_rows(pstate, state, ctx, due)
            state = self.apply_emissions(state, emissions)
            with self._scope("protocol_tick"):
                return self.protocol.tick(self, state)

    def _tele_tick(self, state: SimState) -> SimState:
        """Per-executed-tick telemetry: tick census + (optionally) the
        progress-snapshot write, keyed by the tick just executed (called
        BEFORE the time advance, from both run paths)."""
        if self.telemetry is None:
            return state
        with self._scope("telemetry"):
            tele = state.tele._replace(ticks=state.tele.ticks + 1)
            if self.telemetry.snapshots:
                tele = record_snapshot(tele, self.telemetry, state)
            return state._replace(tele=tele)

    def step(self, state: SimState) -> SimState:
        state = self._step_core(state)
        with self._scope("beat"):
            state = self.protocol.tick_beat(self, state)
        with self._scope("post"):
            state = self.protocol.tick_post(self, state)
        state = self._census_step(self._tele_tick(state))
        return state._replace(time=state.time + 1)

    # -- occupancy summaries --------------------------------------------------
    def _wheel_next_arrival(self, state: SimState) -> jnp.ndarray:
        """Earliest tick >= state.time with an occupied wheel row: the
        occupancy bitmap (whl_fill > 0, packed uint32 words) rotated to
        start at the current tick, then a first-set-bit scan over W/32
        words — O(W) instead of a min over all W*B slots.  Row candidates
        equal the true arrival for in-horizon entries and never overshoot
        for stale ones, so jumps never skip a pending message."""
        t = state.time
        w = self.wheel_rows
        occ = state.whl_fill > 0  # [W]
        rot = occ[jnp.remainder(t + jnp.arange(w, dtype=jnp.int32), jnp.int32(w))]
        words = pack_bool_words(rot)
        d = lowest_set_bit(words)
        return jnp.where(jnp.any(rot), t + d, INT_MAX).astype(jnp.int32)

    def pending_messages(self, state: SimState) -> jnp.ndarray:
        """Quiescence summary: occupied wheel rows (popcount over the
        packed occupancy words) + live overflow entries.  Zero iff no
        message is pending — the DES "event queue empty" test."""
        ovf = jnp.sum(state.ovf_valid.astype(jnp.int32))
        if self.flat:
            return ovf
        return popcount_words(pack_bool_words(state.whl_fill > 0)) + ovf

    def occupancy(self, state: SimState) -> dict:
        """Observability: wheel fill high-water and overflow census of the
        CURRENT state."""
        return {
            "wheel_fill_max": jnp.max(state.whl_fill),
            "overflow_count": jnp.sum(state.ovf_valid.astype(jnp.int32)),
        }

    def _step_jump(self, state: SimState, end) -> SimState:
        """step() plus empty-ms skipping: when the protocol has no per-ms
        tick work (TICK_INTERVAL None), jump straight to the next arrival —
        the batched analog of the oracle's event loop skipping idle time
        (nextMessage's per-ms poll, Network.java:533-545, exists only
        because conditional tasks poll empty milliseconds).  The next
        arrival comes from the wheel's occupancy-word scan plus a min over
        the small overflow lane — O(W + V), not O(C).  A protocol
        TIME_QUANTUM > 1 additionally rounds the jump target UP to the
        quantum grid, so a whole window of arrivals is delivered in one
        step (each delayed < quantum ms)."""
        state = self.step(state)
        if self.protocol.TICK_INTERVAL is None:
            with self._scope("jump"):
                q = self.protocol.TIME_QUANTUM
                ovf_next = jnp.min(
                    jnp.where(state.ovf_valid, state.ovf_arrival, INT_MAX)
                )
                if self.flat:
                    next_arrival = ovf_next
                else:
                    next_arrival = jnp.minimum(
                        self._wheel_next_arrival(state), ovf_next
                    )
                t = jnp.clip(next_arrival, state.time, end).astype(jnp.int32)
                if q > 1:
                    t = jnp.minimum(
                        (t + q - 1) // q * q, jnp.asarray(end, jnp.int32)
                    ).astype(jnp.int32)
                if self.telemetry is not None:
                    with self._scope("telemetry"):
                        tele = state.tele
                        state = state._replace(
                            tele=tele._replace(
                                jumps=tele.jumps
                                + (t > state.time).astype(jnp.int32),
                                jumped_ms=tele.jumped_ms + (t - state.time),
                            )
                        )
                state = state._replace(time=t)
        return state

    # -- the loop ------------------------------------------------------------
    def _run_ms_impl(self, state: SimState, ms: int, stop_when_done: bool) -> SimState:
        end = state.time + ms

        def cond(s):
            c = s.time < end
            if stop_when_done:
                c = c & ~self.protocol.all_done(s)
                if self.protocol.TICK_INTERVAL is None:
                    # quiescence: no pending message and no per-ms tick
                    # work means nothing can ever change — stop scanning
                    c = c & (self.pending_messages(s) > 0)
            return c

        def body(s):
            return self._step_jump(s, end)

        state = lax.while_loop(cond, body, state)
        return state._replace(time=end)

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def _run_ms(self, state: SimState, ms: int, stop_when_done: bool) -> SimState:
        return self._run_ms_impl(state, ms, stop_when_done)

    @functools.partial(jax.jit, static_argnums=(0, 2, 3), donate_argnums=(1,))
    def _run_ms_donated(
        self, state: SimState, ms: int, stop_when_done: bool
    ) -> SimState:
        return self._run_ms_impl(state, ms, stop_when_done)

    def run_ms(
        self,
        state: SimState,
        ms: int,
        stop_when_done: bool = False,
        donate: bool = False,
    ) -> SimState:
        """Advance `ms` simulated milliseconds (ticks [time, time+ms)).

        stop_when_done=True adds the protocol's `all_done` predicate to the
        loop condition: once the observable outcome is decided (e.g. every
        live Handel node aggregated), remaining ticks are skipped and the
        clock jumps to `end` — the batched analog of the oracle DES going
        quiescent when no events remain.  Post-done side effects (periodic
        re-offers' traffic counters) are NOT simulated, so keep the default
        for traffic-parity runs.

        donate=True donates the input state's buffers to the compiled call
        (chunked drivers that overwrite `state` each chunk stop paying a
        full state copy per chunk).  The input is INVALID afterwards —
        callers that reuse it must keep the default."""
        fn = self._run_ms_donated if donate else self._run_ms
        return fn(state, ms, stop_when_done)

    def _run_ms_batched_jumps(
        self, states: SimState, ms: int, stop_when_done: bool
    ) -> SimState:
        """Consensus-jump loop for TICK_INTERVAL-None protocols: the time
        loop runs OUTSIDE the vmap and every iteration executes ONE
        replica-uniform tick — the minimum clock over still-running lanes
        — then each lane's own `_step_jump` advances it past its empty
        milliseconds exactly as on the singleton path.

        Bitwise identity with the ungated vmapped fallback is by
        construction, not by an emptiness argument: a lane steps iff the
        consensus tick equals its own clock, and lane clocks only move
        when the lane steps, so each lane executes exactly its singleton
        tick set (same per-event RNG stream — every executed tick burns
        one send_ctr).  Lanes not at the consensus tick are computed and
        discarded by the element-wise select, like any masked vmap lane.

        What the gate buys over the fallback: `time` is carried as a
        loop-scalar, so the wheel-row addressing inside the step
        (delivery gather, occupancy rotation) is replica-uniform —
        shared dynamic slices instead of per-lane gathers.  Iterations
        count the UNION of lane tick sets rather than the per-lane max,
        so the lever is priced by the paired A/B (profiling.md), not
        assumed."""
        proto = self.protocol
        ends = states.time + ms  # per-lane horizon, like _run_ms_impl

        def lane_alive(s, e):
            c = s.time < e
            if stop_when_done:
                c = c & ~proto.all_done(s)
                # quiescence: no pending message and no per-ms tick work
                # means nothing can ever change — stop scanning
                c = c & (self.pending_messages(s) > 0)
            return c

        alive_v = jax.vmap(lane_alive)
        # time rides as an UNBATCHED scalar through the step: every lane
        # that executes does so at the shared consensus tick, so wheel
        # addressing is replica-uniform (the whole point of the gate)
        axes = SimState(
            **{f: (None if f == "time" else 0) for f in SimState._fields}
        )
        jump_v = jax.vmap(self._step_jump, in_axes=(axes, 0), out_axes=0)

        def w_cond(ss):
            return jnp.any(alive_v(ss, ends))

        def w_body(ss):
            alive = alive_v(ss, ends)
            t = jnp.min(
                jnp.where(alive, ss.time, jnp.int32(INT_MAX))
            ).astype(jnp.int32)
            active = alive & (ss.time == t)
            stepped = jump_v(ss._replace(time=t), ends)
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    active.reshape(active.shape + (1,) * (old.ndim - 1)),
                    new,
                    old,
                ),
                stepped,
                ss,
            )

        states = lax.while_loop(w_cond, w_body, states)
        return states._replace(time=ends)

    def _run_ms_batched_impl(
        self, states: SimState, ms: int, stop_when_done: bool
    ) -> SimState:
        proto = self.protocol
        batch = jnp.shape(states.time)  # static
        if self.due_view_rows is not None and batch == (1,):
            # one row is no batch: under `vmap` the due view's branches are
            # selects that run both sides, so the row runs as it is
            one = jax.tree_util.tree_map(lambda a: a[0], states)
            one = self._run_ms_impl(one, ms, stop_when_done)
            return jax.tree_util.tree_map(lambda a: a[None], one)
        if self.batched_jumps and proto.TICK_INTERVAL is None:
            return self._run_ms_batched_jumps(states, ms, stop_when_done)
        period, residues = proto.BEAT_PERIOD, proto.BEAT_RESIDUES
        if (
            proto.TICK_INTERVAL != 1
            or not period
            or residues is None
            or len(residues) >= period
        ):
            return jax.vmap(
                lambda s: self._run_ms_impl(s, ms, stop_when_done)
            )(states)

        step_v = jax.vmap(self._step_core)

        def _beat(s):
            with self._scope("beat"):
                return proto.tick_beat(self, s)

        def _post(s):
            with self._scope("post"):
                return proto.tick_post(self, s)

        beat_v = jax.vmap(_beat)
        post_v = jax.vmap(_post)
        res = jnp.asarray(sorted(residues), jnp.int32)

        def skip_beat(s):
            # keep the per-event RNG stream identical to the ungated path,
            # where the masked beat call still advanced send_ctr
            return s._replace(send_ctr=s.send_ctr + proto.BEAT_SEND_CALLS)

        def body(_, s):
            # any-over-replicas: for the normal lockstep batch this equals
            # replica 0's beat test; for a batch with non-uniform clocks
            # (stacked mid-run states) tick_beat fires whenever ANY replica
            # beats, and its per-node masks no-op the others — correct
            # either way, and send_ctr advances by exactly 1 on every path
            is_beat = jnp.any(
                lax.rem(s.time.reshape(-1)[:, None], jnp.int32(period))
                == res[None, :]
            )
            s = step_v(s)
            s = lax.cond(is_beat, beat_v, skip_beat, s)
            s = post_v(s)
            if self.telemetry is not None:
                s = jax.vmap(self._tele_tick)(s)
            s = jax.vmap(self._census_step)(s)
            return s._replace(time=s.time + 1)

        if not stop_when_done:
            return lax.fori_loop(0, ms, body, states)

        def w_cond(carry):
            i, s = carry
            return (i < ms) & ~jnp.all(jax.vmap(proto.all_done)(s))

        def w_body(carry):
            i, s = carry
            return i + 1, body(i, s)

        i_fin, states = lax.while_loop(w_cond, w_body, (jnp.int32(0), states))
        # normalize the lockstep clocks to the full horizon, like run_ms
        return states._replace(time=states.time + (ms - i_fin))

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def _run_ms_batched(
        self, states: SimState, ms: int, stop_when_done: bool
    ) -> SimState:
        return self._run_ms_batched_impl(states, ms, stop_when_done)

    @functools.partial(jax.jit, static_argnums=(0, 2, 3), donate_argnums=(1,))
    def _run_ms_batched_donated(
        self, states: SimState, ms: int, stop_when_done: bool
    ) -> SimState:
        return self._run_ms_batched_impl(states, ms, stop_when_done)

    def run_ms_batched(
        self,
        states: SimState,
        ms: int,
        stop_when_done: bool = False,
        donate: bool = False,
    ) -> SimState:
        """vmapped run over the leading replica axis — the TPU replacement
        for RunMultipleTimes' sequential reseeded loop.

        When the protocol declares a sparse beat structure (BEAT_PERIOD +
        BEAT_RESIDUES), the time loop runs OUTSIDE the vmap: replicas
        advance time in lockstep, so the tick index is replica-uniform and
        tick_beat can be guarded by a real lax.cond — off-beat ticks skip
        the periodic work instead of executing it masked (a vmapped
        lax.cond would execute both branches).

        stop_when_done stops the LOCKSTEP loop once every replica's
        all_done holds (see run_ms).  On the ungated fallback path the
        flag is semantics-only: vmapped while_loops mask finished lanes
        rather than skip them, so the body runs until the SLOWEST replica
        finishes either way.

        donate=True: see run_ms — the input pytree is consumed."""
        fn = self._run_ms_batched_donated if donate else self._run_ms_batched
        return fn(states, ms, stop_when_done)


def replicate_state(state: SimState, n_replicas: int, seeds=None) -> SimState:
    """Tile a single-replica state along a new leading replica axis, giving
    each replica its own dynamics seed.  (Distinct node layouts per replica
    can be had by stacking init_state outputs instead.)"""
    if seeds is None:
        seeds = np.arange(n_replicas, dtype=np.int32)
    seeds = jnp.asarray(seeds, jnp.int32)
    tiled = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n_replicas,) + a.shape), state
    )
    return tiled._replace(seed=seeds)


def stack_states(states) -> SimState:
    """Stack independently-built single-replica states (heterogeneous node
    layouts, the exact analog of RunMultipleTimes' per-seed re-init)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
