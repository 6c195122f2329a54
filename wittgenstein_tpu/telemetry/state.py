"""In-graph telemetry state: the device-side counter tier.

The reference exposes its internals through StatsHelper reductions and
wserver polling — both host-side, both O(host round-trip) per sample.
On the batched engine a host read mid-run would sync the device and
destroy lockstep replica throughput, so the counters live INSIDE the
compiled program as a `TelemetryState` pytree side-car on `SimState`:

  * per-mtype message-store counters (sent / delivered / discarded /
    dropped) updated where the engine already touches the rows —
    `apply_emission` and `_deliver_and_clear`;
  * per-mtype latency-kernel counters (`lat_sent` / `lat_filtered`)
    updated in `latency_arrivals`, so the aggregation protocols whose
    channel messaging bypasses the generic store entirely
    (_agg_batched) still show per-mtype traffic;
  * wheel / overflow high-water marks and the empty-ms jump census;
  * an optional fixed-size snapshot ring (one slot per
    `snapshot_every_ms` window of sim time) holding (time, done-node
    count, store-pending, cumulative node sent/received) so progress
    curves and time-to-aggregation CDFs come off the device in ONE
    transfer at the end of the run.

Everything here is pure accounting: no field of the simulation proper is
read-modified, no RNG is consumed, so a telemetry-enabled run is
bit-identical in sim state to a disabled one (pinned by
tests/test_telemetry.py).  The enable switch is STATIC (a
`TelemetryConfig` on the engine, part of its jit cache key): disabled
engines carry `tele=()` — an empty pytree, zero leaves, zero traced ops.

Store-counter invariant (tests/test_dropped_invariant.py):

    sent == delivered + discarded + dropped + pending

where `pending` is the live store census (`pending_count`) and
`discarded` counts delivery-time drops (down destination or
cross-partition, Network.java:606) — zero in the standard scenarios.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static telemetry knobs; hashable, stamped into the engine's
    cache_key (a different config is a different traced program).

    snapshots: ring slots S for the progress time-series (0 = counters
    only).  One slot per `snapshot_every_ms` window, written at every
    executed tick keyed by `time // every mod S` — a run longer than
    S * every wraps, keeping the most recent S windows (snap_time
    disambiguates; export.progress_series sorts it out)."""

    snapshots: int = 0
    snapshot_every_ms: int = 10

    def __post_init__(self):
        if self.snapshots < 0:
            raise ValueError(f"snapshots={self.snapshots} must be >= 0")
        if self.snapshot_every_ms <= 0:
            raise ValueError(
                f"snapshot_every_ms={self.snapshot_every_ms} must be > 0"
            )

    def key(self) -> tuple:
        return (self.snapshots, self.snapshot_every_ms)


class TelemetryState(NamedTuple):
    """The counter side-car (all int32; leading replica axis appears
    under vmap exactly like every other SimState leaf).  [T] = one row
    per protocol message type; [S] = snapshot ring slots."""

    # message-store counters [T]
    sent: jnp.ndarray  # rows accepted into wheel/overflow
    delivered: jnp.ndarray  # rows removed from the store and delivered
    discarded: jnp.ndarray  # due rows dropped at delivery (down/partition)
    dropped: jnp.ndarray  # per-mtype twin of SimState.dropped (store full)
    # latency-kernel counters [T] (generic ring AND protocol channels)
    lat_sent: jnp.ndarray  # ok sends through latency_arrivals
    lat_filtered: jnp.ndarray  # masked-but-filtered sends (down/partition/
    #                            discard-time, Network.java:476-487)
    # occupancy high-water marks + loop census (scalars)
    wheel_fill_hwm: jnp.ndarray  # max whl_fill ever seen post-insert
    ovf_hwm: jnp.ndarray  # max live overflow entries post-insert
    ticks: jnp.ndarray  # executed engine ticks
    jumps: jnp.ndarray  # empty-ms jumps taken (_step_jump)
    jumped_ms: jnp.ndarray  # total ms skipped by those jumps
    # progress snapshot ring [S] (S may be 0)
    snap_time: jnp.ndarray  # last executed tick in the window, -1 = never
    snap_done: jnp.ndarray  # nodes with done_at > 0
    snap_pending: jnp.ndarray  # store-pending messages (counter diff)
    snap_sent: jnp.ndarray  # cumulative node msg_sent sum
    snap_delivered: jnp.ndarray  # cumulative node msg_received sum


def init_telemetry(cfg: TelemetryConfig, n_msg_types: int) -> TelemetryState:
    t, s = n_msg_types, cfg.snapshots
    zt = lambda: jnp.zeros(t, dtype=jnp.int32)
    zs = lambda: jnp.zeros(s, dtype=jnp.int32)
    return TelemetryState(
        sent=zt(),
        delivered=zt(),
        discarded=zt(),
        dropped=zt(),
        lat_sent=zt(),
        lat_filtered=zt(),
        wheel_fill_hwm=jnp.int32(0),
        ovf_hwm=jnp.int32(0),
        ticks=jnp.int32(0),
        jumps=jnp.int32(0),
        jumped_ms=jnp.int32(0),
        snap_time=jnp.full(s, -1, dtype=jnp.int32),
        snap_done=zs(),
        snap_pending=zs(),
        snap_sent=zs(),
        snap_delivered=zs(),
    )


def count_by_type(counts: jnp.ndarray, mask, mtype_rows) -> jnp.ndarray:
    """counts[T] += per-mtype census of the masked rows (one scatter-add,
    the same shape the engine uses for node counters)."""
    return counts.at[mtype_rows].add(mask.astype(jnp.int32), mode="drop")


def pending_scalar(tele: TelemetryState) -> jnp.ndarray:
    """Store-pending message count as a counter diff — O(T), no store
    scan (the exact census `pending_count` lives in export.py, host
    side; the two agree by the store invariant)."""
    return jnp.sum(tele.sent - tele.delivered - tele.discarded - tele.dropped)


def record_snapshot(
    tele: TelemetryState, cfg: TelemetryConfig, state
) -> TelemetryState:
    """Write this tick's progress sample into its window slot (later
    ticks in the same window overwrite — the slot ends up holding the
    window's LAST executed tick, which equals the window-end state
    because jumped ticks change nothing)."""
    slot = jnp.remainder(
        state.time // cfg.snapshot_every_ms, jnp.int32(cfg.snapshots)
    )
    return tele._replace(
        snap_time=tele.snap_time.at[slot].set(state.time),
        snap_done=tele.snap_done.at[slot].set(
            jnp.sum((state.done_at > 0).astype(jnp.int32))
        ),
        snap_pending=tele.snap_pending.at[slot].set(pending_scalar(tele)),
        snap_sent=tele.snap_sent.at[slot].set(jnp.sum(state.msg_sent)),
        snap_delivered=tele.snap_delivered.at[slot].set(
            jnp.sum(state.msg_received)
        ),
    )
